#!/usr/bin/env python3
"""The programs alone, every variant of a piece of the lane stage beside the
others: the sweep that decides a change to ``tpu/checker``'s lane stage
before any cell is run (``ROADMAP.md`` D14; ``PERF.md`` §6, PRs 48 and 49).

One process. The operands are captured as the package puts them: a
``wgs-short`` file of the benchmark's own generator, counted once by
``count_reads_tpu`` (the stream's 32 MiB windows, kept on the device) and
once through a ``SplitService`` (the first served step of eight 1 MiB rows).
Every variant replaces functions of ``checker`` while its two programs,
``jit_count_window`` and the served step, are lowered and compiled; nothing
else of a program differs. Then a warm run, ``--rounds`` rounds over the
variants in turn, the median a program, and every output of every variant
compared with the first column's. ``--profile`` runs each variant's programs
once more under the profiler and prints its operations by self time, each
divided by the lanes the program ran besides (what a gather index or a row
fetch costs is an operation's self time a lane).

    chiprun -- python tools/lane_sweep.py --profile               # the words
    chiprun -- python tools/lane_sweep.py --sweep search --profile
    JAX_PLATFORMS=cpu python tools/lane_sweep.py --rehearse   # both, small, CPU

Two families of columns, ``--sweep``:

``words`` (PR 49, the default): how a lane reads its words of the window's
word view, ``checker._words_at`` / ``checker._lane_words`` (and, where a
layout keeps an array beside the view, what ``checker._flag_stage`` hands
the lanes). ``tree`` is the package as it stands (the view followed by
itself from its 64th word on, ONE array; a lane fetches the one row that
holds its words whole and picks a word by a compare with the column),
``parent`` the element gathers it had until PR 49, one index a word, from
the view alone; the other columns are the layouts that were tried: two
rows of the view alone a site (``rows2``, the first sweep's ``tree``), one
fetch of two rows, the package's rows out of a copy beside the view, the
bytes as aligned words. They run with the tree's search.

``search`` (PR 48): the compaction's search, ``checker._rank_table`` /
``checker._ranked_positions``. ``tree`` is the package as it stands (rows of
``checker.RANK_ROW`` keys under a top of at most ``checker.RANK_TOP``, the
word out of a fetched row too), ``parent`` the binary search it had until
PR 48; the other columns are kept as the record of what was tried, and
every one of them gathers the word by element as the parent does. They run
with the tree's words.

It is a builder's instrument: no cell runs it, and no module imports it.
"""

from __future__ import annotations

import argparse
import functools
import json
import shutil
import statistics
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import NamedTuple

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from spark_bam_tpu.tpu import checker as ck  # noqa: E402

_I32 = jnp.int32
_NO_RANK = np.iinfo(np.int32).max
OUT = REPO / "chiprun_out"
DATA = REPO / ".smoke_data" / "lane_sweep"
PROFILE_TOP = 24


# ------------------------------------------------------ variants: search

class Table(NamedTuple):
    """What a variant's search may read; XLA drops what it does not."""
    words: jnp.ndarray
    wpc: jnp.ndarray
    wcnt: jnp.ndarray
    levels: tuple
    n_set: jnp.ndarray


def _bit_in_word(table, k, wi, excl):
    """The search's last steps as the parent had them: the word gathered,
    then the package's own in-word search (unchanged by PR 48)."""
    word = jnp.take(table.words, wi, mode="clip")
    lane = ck._bit_of_rank(word, k + 1 - excl)
    return jnp.where((k >= 0) & (k < table.n_set), wi * 32 + lane, _I32(-1))


def _gathered_excl(table, wi):
    return jnp.take(
        table.wcnt - table.wpc, jnp.clip(wi, 0, table.wcnt.shape[0] - 1),
        mode="clip")


def _base_table(mask, levels=lambda wcnt: ()) -> Table:
    words = ck._pack_bits(mask)
    wpc = lax.population_count(words).astype(_I32)
    wcnt = jnp.cumsum(wpc)
    return Table(words, wpc, wcnt, levels(wcnt), wcnt[-1])


def parent():
    """The binary search: one gather a halving (the tree until PR 48)."""
    def positions(table, k):
        wi = jnp.searchsorted(table.wcnt, k + 1, side="left").astype(_I32)
        return _bit_in_word(table, k, wi, _gathered_excl(table, wi))

    return _base_table, positions


def kary(row: int, top: int, excl: str = "row"):
    """Levels of ``row`` keys under a top of at most ``top``: a row fetch a
    level below the top, the word gathered as the parent gathers it (the
    tree fetches the word's row too). ``excl``: the prefix before the word
    from the fetched rows, or gathered as the parent does."""
    def levels(keys):
        out = []
        while keys.shape[0] > top:
            rows = -(-keys.shape[0] // row)
            keys = jnp.pad(keys, (0, rows * row - keys.shape[0]),
                           constant_values=_NO_RANK).reshape(rows, row)
            out.append(keys)
            keys = keys[:, -1]
        return (keys, *reversed(out))

    def positions(table, k):
        target = (k + 1)[:, None]

        def descend(wi, below_max, keys):
            below = keys < target
            return (
                wi * keys.shape[1] + jnp.sum(below, axis=1, dtype=_I32),
                jnp.maximum(below_max, jnp.max(
                    jnp.where(below, keys, _I32(0)), axis=1)),
            )

        wi, below_max = descend(_I32(0), _I32(0), table.levels[0][None, :])
        for level in table.levels[1:]:
            wi = jnp.minimum(wi, level.shape[0] - 1)
            wi, below_max = descend(
                wi, below_max, jnp.take(level, wi, axis=0, mode="clip"))
        if excl == "gather":
            below_max = _gathered_excl(table, wi)
        return _bit_in_word(table, k, wi, below_max)

    return (lambda mask: _base_table(mask, levels)), positions


def top_then_bisect(top: int):
    """``top`` keys by compare alone, then the binary search over the group
    that is left: element gathers only, whose price is known."""
    def levels(wcnt):
        n = wcnt.shape[0]
        size = 1 << max(n - 1, 1).bit_length()
        padded = jnp.pad(wcnt, (0, size - n), constant_values=_NO_RANK)
        keys = min(top, size)
        return (padded.reshape(keys, size // keys)[:, -1], padded)

    def positions(table, k):
        heads, padded = table.levels
        group = padded.shape[0] // heads.shape[0]
        target = k + 1
        lo = jnp.minimum(
            jnp.sum(heads[None, :] < target[:, None], axis=1, dtype=_I32),
            heads.shape[0] - 1) * group
        half = group // 2
        while half:
            probe = jnp.take(padded, lo + (half - 1), mode="clip")
            lo = jnp.where(probe < target, lo + half, lo)
            half //= 2
        return _bit_in_word(table, k, lo, _gathered_excl(table, lo))

    return (lambda mask: _base_table(mask, levels)), positions


#: name -> (rank_table, ranked_positions); None leaves the package's own.
VARIANTS = {
    "parent": parent(),
    "tree": None,
    "row128": kary(128, 128),
    "row128.excl-gathered": kary(128, 128, excl="gather"),
    "row128.top1024": kary(128, 1024),      # the tree, but for the word
    "row256": kary(256, 256),
    "row1024": kary(1024, 1024),
    "row32": kary(32, 32),
    "top128.bisect": top_then_bisect(128),
    "top256.bisect": top_then_bisect(256),
    "top1024.bisect": top_then_bisect(1024),
}


# ------------------------------------------------------- variants: words

_ROW = ck.WORD_ROW


def _flat_view(p):
    """The word view alone, in whole rows: what the layouts that fetch two
    rows a site read, and what ``checker._words_at`` returned before it was
    followed by itself (``checker.WORD_REACH``)."""
    total = p.shape[0]
    whole = -(-total // _ROW) * _ROW
    p = jnp.concatenate([p, jnp.zeros(whole + 3 - total, dtype=p.dtype)])
    return lax.bitcast_convert_type(ck._i32_at(p, total - ck.PAD), jnp.int32)


def _on_flat_view(lane_words, view=None) -> dict:
    """A column that reads the view alone (``_flat_view``), or ``view(U,
    padded)`` made of it once a window."""
    column = {"words_at": _flat_view, "lane_words": lane_words}
    if view is not None:
        column["view"] = view
    return column


def _columns():
    return jnp.arange(_ROW, dtype=_I32)[None, :]


def _fetch(rows, at):
    return jnp.take(rows, at, axis=0, mode="clip")


def _pick(held, column):
    """The word of each lane's ``held`` row at its ``column``."""
    mine = _columns() == column[:, None]
    return jnp.sum(jnp.where(mine, held, jnp.zeros((), held.dtype)), axis=1,
                   dtype=held.dtype)


def _wrapped(rows, at, second):
    """The ``_ROW`` words from ``at`` on, each in its own column, out of the
    row of ``at`` and the row ``second``, folded by one select."""
    return jnp.where(_columns() >= (at % _ROW)[:, None],
                     _fetch(rows, at // _ROW), _fetch(rows, second))


def elements():
    """One gathered element a word: the tree until PR 49."""
    def lane_words(U, pos, offsets):
        return tuple(jnp.take(U, pos + off, mode="clip") for off in offsets)

    return _on_flat_view(lane_words)


def rows2(second: str = "next", wrap: bool = True):
    """Two row fetches a site, no array besides the view: the position's
    row and the ``next``, or the row of the site's ``last`` word (most
    lanes fetch their own row twice). ``wrap``: the two folded into one
    row's width before the picks, or every word picked out of both."""
    def lane_words(U, pos, offsets):
        rows = U.reshape(-1, _ROW)
        row = pos // _ROW
        then = row + 1 if second == "next" else (pos + max(offsets)) // _ROW
        if wrap:
            held = _wrapped(rows, pos, then)
            return tuple(_pick(held, (pos + off) % _ROW) for off in offsets)
        first, after = _fetch(rows, row), _fetch(rows, then)
        column = pos % _ROW
        return tuple(
            _pick(first, column + off) + _pick(after, column + off - _ROW)
            for off in offsets)

    return _on_flat_view(lane_words)


def slab2():
    """ONE fetch a site of two rows, 1 KiB behind one index."""
    def lane_words(U, pos, offsets):
        rows = U.reshape(-1, _ROW)
        row = jnp.minimum(pos // _ROW, rows.shape[0] - 2)
        slab = lax.gather(
            rows, row[:, None], lax.GatherDimensionNumbers(
                offset_dims=(1, 2), collapsed_slice_dims=(),
                start_index_map=(0,)),
            slice_sizes=(2, _ROW), mode="clip")
        column = jnp.arange(2 * _ROW, dtype=_I32).reshape(1, 2, _ROW)
        at = pos - row * _ROW
        return tuple(
            jnp.sum(jnp.where(column == (at + off)[:, None, None], slab,
                              _I32(0)), axis=(1, 2), dtype=_I32)
            for off in offsets)

    return _on_flat_view(lane_words)


def stride64_copied():
    """The package's one row a site, but out of a SECOND array beside the
    view (the view, then the view from its 64th word on: a copy of 258 MiB
    a 32 MiB window), where the package's view is that array itself."""
    half = _ROW // 2

    def view(U, _padded):
        return jnp.concatenate([U, U[half:], jnp.zeros(half, U.dtype)])

    def lane_words(V, pos, offsets):
        assert max(offsets) < half
        rows = V.reshape(-1, _ROW)
        late = pos % _ROW >= half
        row = pos // _ROW + jnp.where(late, rows.shape[0] // 2, 0)
        held = _fetch(rows, row)
        column = pos % _ROW - jnp.where(late, half, 0)
        return tuple(_pick(held, column + off) for off in offsets)

    return _on_flat_view(lane_words, view)


def aligned():
    """Rows of the window's own bytes as aligned u32 words (a quarter of the
    word view's bytes: does the price of a row follow its operand's size?),
    two row fetches a site, a word funnel-shifted out of two aligned ones by
    the position's last two bits."""
    def view(U, _padded):
        return lax.bitcast_convert_type(U[::4], jnp.uint32)

    def lane_words(A, pos, offsets):
        assert all(off % 4 == 0 for off in offsets)
        rows = A.reshape(-1, _ROW)
        at = pos >> 2
        need = sorted({off // 4 + i for off in offsets for i in (0, 1)})
        held = _wrapped(rows, at, at // _ROW + 1)
        got = {i: _pick(held, (at + i) % _ROW) for i in need}
        shift = ((pos & 3) * 8).astype(jnp.uint32)
        high = jnp.where(shift == 0, jnp.uint32(0), jnp.uint32(32) - shift)

        def word(off):
            lo, hi = got[off // 4], got[off // 4 + 1]
            both = (lo >> shift) | jnp.where(shift == 0, jnp.uint32(0),
                                             hi << high)
            return lax.bitcast_convert_type(both, jnp.int32)

        return tuple(word(off) for off in offsets)

    return _on_flat_view(lane_words, view)


#: name -> what a column replaces: ``words_at`` and ``lane_words`` take the
#: place of ``checker._words_at`` / ``checker._lane_words``; ``view(U,
#: padded)`` is what the lanes read in place of the word view ``U``, made
#: once a window behind a barrier of its own. None leaves the package's own.
WORD_VARIANTS = {
    "parent": elements(),
    "tree": None,
    "rows2": rows2(),
    "rows2.both": rows2(wrap=False),
    "rows2.last": rows2(second="last"),
    "slab2": slab2(),
    "stride64.copied": stride64_copied(),
    "aligned": aligned(),
}


def _search_patch(variant) -> dict:
    rank_table, ranked_positions = variant
    return {"_rank_table": rank_table, "_ranked_positions": ranked_positions}


def _words_patch(variant) -> dict:
    patch = {"_words_at": variant["words_at"],
             "_lane_words": variant["lane_words"]}
    view = variant.get("view")
    if view is not None:
        flag_stage = ck._flag_stage

        def staged(padded, lengths, num_contigs, n, at_eof, funnel):
            S = flag_stage(padded, lengths, num_contigs, n, at_eof, funnel)
            if funnel:
                V = lax.optimization_barrier(view(S["U"], padded))
                S = {**S, "U": V,
                     "misc_at": functools.partial(ck._misc_at, V, n)}
            return S

        patch["_flag_stage"] = staged
    return patch


#: family -> (its columns, what a column replaces in ``checker``).
FAMILIES = {
    "words": (WORD_VARIANTS, _words_patch),
    "search": (VARIANTS, _search_patch),
}


class patched:
    """Functions of the package replaced while a program is traced
    (``{name: replacement}``; None leaves the package as it is)."""

    def __init__(self, patch):
        self.patch = patch or {}

    def __enter__(self):
        self.saved = {name: getattr(ck, name) for name in self.patch}
        for name, fn in self.patch.items():
            setattr(ck, name, fn)
        # An inner jit (``check_window`` in the served step) keeps its trace.
        jax.clear_caches()

    def __exit__(self, *exc):
        for name, fn in self.saved.items():
            setattr(ck, name, fn)
        jax.clear_caches()


# --------------------------------------------------------------- capture

def make_file(seed: int, rehearse: bool) -> Path:
    from bench.generators import shortread

    config = json.loads((REPO / "bench/configs/wgs-short.json").read_text())
    size = int(config["rehearsal" if rehearse else "scale"]
               ["uncompressed_bytes"])
    DATA.mkdir(parents=True, exist_ok=True)
    path = DATA / f"wgs-short-{seed}.bam"
    shortread.generate(config["params"], seed, size, path)
    return path


def capture_count_windows(path: Path, config, keep: int) -> list:
    """The operands of the stream's first ``keep`` windows, on the device."""
    from spark_bam_tpu.load.tpu_load import count_reads_tpu

    taken: list = []
    make = ck.make_count_window

    def capturing(*args, **kw):
        kernel = make(*args, **kw)

        def run(*operands):
            if len(taken) < keep:
                # A copy: on the CPU the operand is a view of a frame that
                # the stream fills again.
                taken.append((tuple(jnp.array(x) for x in operands),
                              kernel.keywords))
            return kernel(*operands)

        return run

    ck.make_count_window = capturing
    try:
        records = count_reads_tpu(path, config)
    finally:
        ck.make_count_window = make
    print(json.dumps({"captured": "count_window", "windows": len(taken),
                      "records": int(records)}), flush=True)
    return taken


def capture_serve_step(path: Path, config) -> tuple:
    """The operands of the first served step whose rows are all live."""
    from spark_bam_tpu.parallel.mesh import local_mesh
    from spark_bam_tpu.serve import SplitService

    svc = SplitService(config, mesh=local_mesh())
    taken: list = []
    step = svc.batcher._step

    def capturing(*operands):
        owns = np.asarray(operands[4])
        if not taken and (owns > 0).all():
            taken.append(tuple(jnp.array(x) for x in operands))
        return step(*operands)

    svc.batcher._step = capturing
    try:
        reply = svc.submit({"op": "count", "path": str(path), "id": 1}).result()
    finally:
        svc.close()
    print(json.dumps({"captured": "serve_step", "steps": len(taken),
                      "count": reply.get("count")}), flush=True)
    return svc.mesh, taken[0]


# --------------------------------------------------------------- programs

def lower_count_window(operands, statics):
    fn = jax.jit(ck.count_window.__wrapped__, static_argnames=(
        "reads_to_check", "window", "funnel", "escapes"))
    return fn.lower(*operands, **statics).compile()


def lower_serve_step(mesh, config, operands):
    from spark_bam_tpu.parallel.mesh import make_shard_map_serve_step

    step = make_shard_map_serve_step(
        mesh, config.reads_to_check, funnel=config.funnel_enabled())
    return step.lower(*operands).compile()


def equal_outputs(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def time_rounds(programs: dict, operands, rounds: int) -> dict:
    """``{variant: (median ms, every round's ms, output)}``: a warm run
    each, then the rounds, every variant once a round."""
    outs = {name: jax.block_until_ready(prog(*operands))
            for name, prog in programs.items()}
    ms = defaultdict(list)
    for _ in range(rounds):
        for name, prog in programs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(prog(*operands))
            ms[name].append((time.perf_counter() - t0) * 1e3)
    return {name: (statistics.median(ms[name]), ms[name], outs[name])
            for name in programs}


def profile_ops(where: Path, prog, operands, runs: int = 3) -> list:
    """``[(operation, self ms a run)]`` of one program, most first; the
    capture goes under ``where`` and is removed again."""
    from bench import trace_reduce

    with jax.profiler.trace(str(where)):
        for _ in range(runs):
            jax.block_until_ready(prog(*operands))
    found = sorted(where.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    ops: dict = defaultdict(float)
    for plane, lines in trace_reduce.load_planes(found[-1]):
        if plane.startswith(trace_reduce.DEVICE_PREFIX):
            for events in trace_reduce._op_lines(lines):
                trace_reduce._self_times(events, ops)
    shutil.rmtree(where)  # the capture is large; the table of it is kept
    return sorted(((op, ns / 1e6 / runs) for op, ns in ops.items()),
                  key=lambda kv: -kv[1])


# --------------------------------------------------------------- the sweep

def sweep(family: str, lower, operand_sets: dict, names, rounds: int,
          lanes_of, profile_to: Path | None) -> dict:
    """Every variant's program compiled once, then timed on every set of
    operands (``{label: operands}``, one shape); the first set profiled
    where ``profile_to`` names a directory for the tables.
    ``lanes_of(output)`` is ``(survivors, lanes)`` of a run."""
    variants, patch_of = FAMILIES[family]
    programs = {}
    for name in names:
        t0 = time.perf_counter()
        variant = variants[name]
        with patched(variant and patch_of(variant)):
            programs[name] = lower()
        print(json.dumps({"compiled": name, "for": list(operand_sets),
                          "seconds": time.perf_counter() - t0}), flush=True)
    table = {}
    for label, operands in operand_sets.items():
        timed = time_rounds(programs, operands, rounds)
        base = timed[names[0]][2]
        survivors, lanes = lanes_of(base)
        print(json.dumps({"program": label, "survivors": survivors,
                          "lanes": lanes}), flush=True)
        table[label] = {"survivors": survivors, "lanes": lanes, "ms": {}}
        for name in names:
            median, every, out = timed[name]
            table[label]["ms"][name] = row = {
                "ms": median, "rounds": every,
                "equal": equal_outputs(out, base),
            }
            print(json.dumps({"program": label, "variant": name, **row}),
                  flush=True)
        if profile_to is not None and label == next(iter(operand_sets)):
            for name in names:
                ops = profile_ops(
                    profile_to / "capture", programs[name], operands)
                print(json.dumps({
                    "program": label, "variant": name, "lanes": lanes,
                    "operations": len(ops),
                    "self_ms_and_ns_a_lane": [
                        [op, round(ms, 4), round(ms * 1e6 / lanes, 2)]
                        for op, ms in ops[:PROFILE_TOP]],
                }), flush=True)
                (profile_to / f"profile.{label}.{name}.json").write_text(
                    json.dumps(ops, indent=0))
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sweep", choices=list(FAMILIES),
                    help="the family of columns: words (the default) or "
                         "search; --rehearse without it runs both")
    ap.add_argument("--seed", type=int, default=2147483684)
    ap.add_argument("--rounds", type=int, default=7)
    ap.add_argument("--windows", type=int, default=2,
                    help="windows of the stream to sweep (the first ones)")
    ap.add_argument("--variants",
                    help="columns of the family (all of them), the first the "
                         "one the others are compared with")
    ap.add_argument("--programs", default="count,serve")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's rehearsal size and small "
                         "windows, on whatever backend is there")
    args = ap.parse_args(argv)

    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.core.platform import enable_compile_cache
    from spark_bam_tpu.native.build import require_native

    enable_compile_cache()
    require_native("tools/lane_sweep.py")
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print("no TPU: the sweep's times are the chip's (--rehearse runs it "
              "small on this backend and times nothing worth keeping)",
              file=sys.stderr)
        return 3
    families = [args.sweep] if args.sweep else (
        list(FAMILIES) if args.rehearse else ["words"])
    if args.variants and len(families) > 1:
        ap.error("--variants names one family's columns: give --sweep")
    config = Config()
    if args.rehearse:
        config = Config(window_size=768 << 10, halo_size=128 << 10,
                        serve="window=128KB,halo=16KB,batch=4")
    print(json.dumps({"device": device.device_kind, "seed": args.seed,
                      "sweep": families, "rehearse": args.rehearse}),
          flush=True)
    path = make_file(args.seed, args.rehearse)
    try:
        taken = served = None
        if "count" in args.programs:
            taken = capture_count_windows(path, config, args.windows)
        if "serve" in args.programs:
            served = capture_serve_step(path, config)
    finally:
        path.unlink(missing_ok=True)

    equal = True
    for family in families:
        names = (args.variants.split(",") if args.variants
                 else list(FAMILIES[family][0]))
        print(json.dumps({"sweep": family, "variants": names}), flush=True)
        out = OUT / (f"lane_sweep.{family}"
                     + (".rehearsal" if args.rehearse else ""))
        out.mkdir(parents=True, exist_ok=True)
        profile_to = out if args.profile else None
        table = {}
        if taken is not None:
            statics = taken[0][1]
            table.update(sweep(
                family, lambda: lower_count_window(taken[0][0], statics),
                {f"count_window.{i}": operands
                 for i, (operands, _) in enumerate(taken)},
                names, args.rounds,
                lambda got: (int(got["survivors"]), int(got["lanes"])),
                profile_to))
        if served is not None:
            mesh, operands = served

            def served_lanes(got):
                per_row = np.asarray(got)
                return int(per_row[:, 2].sum()), int(per_row[:, 3].sum())

            table.update(sweep(
                family, lambda: lower_serve_step(mesh, config, operands),
                {"serve_step": operands}, names, args.rounds, served_lanes,
                profile_to))

        print(f"\n| {family}: program (survivors / lanes) | "
              + " | ".join(names) + " |")
        print("|---|" + "---|" * len(names))
        for label, found in table.items():
            rows = found["ms"]
            cells = [f"{rows[n]['ms']:.2f}"
                     + ("" if rows[n]["equal"] else " DIFFERS") for n in names]
            print(f"| {label} ({found['survivors']:,} / {found['lanes']:,}) | "
                  + " | ".join(cells) + " |")
        (out / "table.json").write_text(json.dumps(table, indent=1))
        equal = equal and all(r["equal"] for found in table.values()
                              for r in found["ms"].values())
    return 0 if equal else 1


if __name__ == "__main__":
    raise SystemExit(main())
