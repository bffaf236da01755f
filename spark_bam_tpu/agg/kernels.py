"""Fused on-device reduction kernels over the parser's flat planes.

Each metric in an :class:`~spark_bam_tpu.agg.plan.AggConfig` lowers to
masked sums / scatter-adds over the already-parsed record planes
(``flag``, ``mapq``, ``tlen``, ``l_seq``, ``pos``, ``ref_span``,
``ref_id``, masked by ``valid``) — one XLA program per window for the
WHOLE plan, with the partial-state carry threaded device-to-device so a
multi-window file reduces without host round-trips. Predicate pushdown
happens before any of this: interval/flag/tag filters narrow ``valid``
(load/tpu_load.py ``_apply_filter``) and the kernels only ever read the
mask — filtered records are never materialized.

Overflow discipline (the mesh tier's contract, parallel/mesh.py): the
device state is int32 — record-scale counters are safe per flush
interval, and :func:`aggregate_planes` drains the carry into host int64
totals every ``_FLUSH_RECORDS`` records (sized so ≤2³⁰ bases accumulate
between flushes at ≤512 b mean read length; shrink ``chunk`` for
ultralong data). The wire result is always int64 (agg/plan.py).

Two execution shapes share ``_reduce_chunk``:

- the plain jit path (:func:`update_fn`) — the one-shot API / CPU
  fallback, no mesh required;
- :func:`make_shard_map_agg_step` — records sharded over the mesh's
  ``data`` axis, per-device partial deltas ``psum``'d over ICI, state
  replicated. Registered once per (plan, nc) in ``MeshSteps`` so the
  serve daemon dispatches every aggregate tick through one compiled
  executable (the build-at-startup, serve-forever contract).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from spark_bam_tpu.agg.plan import FLAG_BITS, AggConfig
from spark_bam_tpu.tpu.parser import _next_pow2

#: Planes a reduction reads, in the positional order every step takes.
PLANES = ("valid", "flag", "mapq", "tlen", "l_seq", "pos", "ref_span",
          "ref_id")

#: Default records per device window (pow2 — at most log2 distinct
#: compile shapes across files).
DEFAULT_CHUNK = 1 << 16

#: Host-flush interval, in records: ≤2³⁰ bases accumulate in the int32
#: carry between flushes at ≤512 b mean reads.
_FLUSH_RECORDS = 1 << 21


def state_zeros(plan: AggConfig, nc: int) -> "dict[str, np.ndarray]":
    """Fresh int32 carry for one reduction pass."""
    return {
        spec.name: np.zeros(spec.length(nc), dtype=np.int32)
        for spec in plan.specs
    }


@jax.named_scope("agg_reduce")
def _reduce_chunk(plan: AggConfig, nc: int, planes: dict) -> dict:
    """One window's partial vectors (int32) — the traced core shared by
    the plain jit and the shard_map step."""
    valid = planes["valid"].astype(jnp.int32)
    flag = planes["flag"]
    out: dict = {}
    for spec in plan.specs:
        if spec.name == "count":
            mapped = valid * ((flag & 4) == 0).astype(jnp.int32)
            bases = jnp.sum(valid * planes["l_seq"])
            out["count"] = jnp.stack(
                [jnp.sum(valid), jnp.sum(mapped), bases]
            )
        elif spec.name == "flagstat":
            out["flagstat"] = jnp.concatenate([
                jnp.sum(valid)[None],
                jnp.stack([
                    jnp.sum(valid * ((flag & bit) != 0).astype(jnp.int32))
                    for bit in FLAG_BITS
                ]),
            ])
        elif spec.name == "mapq":
            idx = jnp.clip(planes["mapq"], 0, 255)
            out["mapq"] = jnp.zeros(256, dtype=jnp.int32).at[idx].add(valid)
        elif spec.name == "tlen":
            mx = spec.get("max")
            idx = jnp.minimum(jnp.abs(planes["tlen"]), mx + 1)
            out["tlen"] = (
                jnp.zeros(mx + 2, dtype=jnp.int32).at[idx].add(valid)
            )
        elif spec.name == "coverage":
            out["coverage"] = _coverage_chunk(spec, nc, planes, valid)
    return out


def _coverage_chunk(spec, nc: int, planes: dict, valid) -> jnp.ndarray:
    """Segment-sum of (pos, pos+ref_span) intervals into per-contig
    buckets — a static ``cap``-step unroll of the bucket walk, each step
    one masked scatter-add (the wire contract's clamps: last-bucket
    collapse, ``cap``-bucket truncation; agg/plan.py)."""
    B, bins, cap = spec.get("bin"), spec.get("bins"), spec.get("cap")
    ref = planes["ref_id"]
    pos = planes["pos"]
    flag = planes["flag"]
    span = jnp.maximum(planes["ref_span"], 1)
    use = (
        (valid > 0) & ((flag & 4) == 0)
        & (ref >= 0) & (ref < nc) & (pos >= 0)
    )
    s = pos
    e = s + span
    sb = jnp.minimum(s // B, bins - 1)
    eb = jnp.minimum(jnp.minimum((e - 1) // B, bins - 1), sb + cap - 1)
    base = jnp.clip(ref, 0, nc - 1) * bins
    cov = jnp.zeros(nc * bins, dtype=jnp.int32)
    for j in range(cap):
        k = sb + j
        active = use & (k <= eb)
        lo = jnp.maximum(s, k * B)
        hi = jnp.where(k == bins - 1, e, jnp.minimum(e, (k + 1) * B))
        ov = jnp.where(active, jnp.maximum(hi - lo, 0), 0)
        cov = cov.at[base + jnp.clip(k, 0, bins - 1)].add(ov)
    return cov


@functools.lru_cache(maxsize=64)
def update_fn(plan: AggConfig, nc: int):
    """The plain jit carry step: ``state' = state + reduce(planes)``.
    Cached per (plan, nc) — the plan is frozen/hashable by design."""

    @jax.jit
    def agg_update(state: dict, planes: dict) -> dict:
        delta = _reduce_chunk(plan, nc, planes)
        return {k: state[k] + delta[k] for k in state}

    return agg_update


def make_shard_map_agg_step(mesh, plan: AggConfig, nc: int,
                            axis: str = "data"):
    """Sharded carry step: record planes shard over the mesh's ``data``
    axis, each device reduces its slice, deltas all-reduce with
    ``lax.psum`` over ICI, and the replicated state advances — the same
    explicit-collective shape as the count/serve steps
    (parallel/mesh.py), with the aggregate state as the carried operand.
    Rows pad with ``valid=False`` so the pad never counts."""

    def agg_step(state: dict, planes: dict) -> dict:
        delta = _reduce_chunk(plan, nc, planes)
        with jax.named_scope("agg_reduce"):
            delta = {k: jax.lax.psum(v, axis) for k, v in delta.items()}  # ← ICI
        return {k: state[k] + delta[k] for k in state}

    return jax.jit(
        jax.shard_map(
            agg_step,
            mesh=mesh,
            in_specs=(P(), P(axis)),
            out_specs=P(),
            check_vma=False,
        )
    )


def _pad_planes(columns: dict, lo: int, hi: int, multiple: int) -> dict:
    """One window's planes, padded to pow2 (≥ ``multiple``) with
    valid=False rows — at most log2 distinct shapes reach the jit."""
    m = hi - lo
    m_pad = max(_next_pow2(m), multiple)
    out = {}
    for name in PLANES:
        col = np.asarray(columns[name])
        if name == "valid":
            pad = np.zeros(m_pad, dtype=bool)
        else:
            pad = np.zeros(m_pad, dtype=np.int32)
        pad[:m] = col[lo:hi]
        out[name] = pad
    return out


def aggregate_planes(
    columns: "dict[str, np.ndarray]",
    plan: AggConfig,
    nc: int,
    *,
    steps=None,
    chunk: "int | None" = None,
) -> "dict[str, np.ndarray]":
    """Reduce flat planes to the plan's int64 vectors on device.

    ``steps`` is a ``MeshSteps`` registry: when given, windows dispatch
    through its compiled-once sharded agg step; otherwise the plain jit
    carry runs on the default device. ``chunk`` bounds records per
    window (tests shrink it to force the multi-window carry). Returns
    metric name → int64 vector, byte-compatible with the host oracle.
    """
    m = len(columns["valid"])
    chunk = int(chunk or DEFAULT_CHUNK)
    if chunk < 1:
        raise ValueError(f"agg chunk must be >= 1: {chunk}")
    multiple = 1
    if steps is not None:
        step = steps.agg_step(plan, nc)
        multiple = int(steps.mesh.devices.size)
    else:
        step = update_fn(plan, nc)
    totals = {
        spec.name: np.zeros(spec.length(nc), dtype=np.int64)
        for spec in plan.specs
    }
    state = {k: jnp.asarray(v) for k, v in state_zeros(plan, nc).items()}
    since_flush = 0
    for lo in range(0, max(m, 1), chunk):
        hi = min(lo + chunk, m)
        if hi <= lo:
            break
        planes = {
            k: jnp.asarray(v)
            for k, v in _pad_planes(columns, lo, hi, multiple).items()
        }
        state = step(state, planes)       # device-to-device carry
        since_flush += hi - lo
        if since_flush >= _FLUSH_RECORDS:
            for k, v in state.items():
                totals[k] += np.asarray(v, dtype=np.int64)
            state = {
                k: jnp.asarray(v)
                for k, v in state_zeros(plan, nc).items()
            }
            since_flush = 0
    for k, v in state.items():
        totals[k] += np.asarray(v, dtype=np.int64)
    return totals
