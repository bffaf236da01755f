"""TPU-backed end-to-end loading: the production fast path.

Composes the pipeline the BASELINE north star describes: BGZF blocks →
flat windows in HBM → vectorized boundary checking → batched columnar
record parsing with on-device filters. The host only inflates, steers
windows, and re-checks the (rare) escaped candidates.

- ``record_starts``: every record-start flat offset of a file, from the
  checker's verdicts (positions ≥ the header end; the eager battery has no
  known false calls — SURVEY.md §6 "spark-bam miscalls: 0 known")
- ``count_reads_tpu``: boundary count — the count-reads workload with zero
  per-record host work
- ``check_bam_tpu``: check-bam — the verdict at every position against the
  ``.records`` truth, the confusion matrix and where the two disagree
- ``load_reads_columnar``: ReadBatch columnar views of all (or
  interval/flag-filtered) records
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from spark_bam_tpu import obs
from spark_bam_tpu.bam.header import read_header
from spark_bam_tpu.bgzf.flat import FlatView, flatten_file
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.core.pos import Pos
from spark_bam_tpu.load.intervals import BadLociError, LociSet
from spark_bam_tpu.tpu.checker import TpuChecker
from spark_bam_tpu.tpu.parser import (
    ReadBatch,
    _next_pow2,
    interval_flag_filter,
    parse_flat_records,
)


@dataclass
class TpuLoadResult:
    view: FlatView
    header: object
    starts: np.ndarray  # flat record-start offsets

    def positions(self) -> list[Pos]:
        blocks, offs = self.view.pos_of_flat_many(self.starts)
        return [Pos(int(b), int(o)) for b, o in zip(blocks, offs)]


def _cached_record_starts(view, path, config, store, strict):
    """Flat record-start offsets from a valid ``.sbi`` sidecar, or None.
    Cached positions are stored virtual (portable across re-flattenings);
    the conversion is vectorized against the view's block tables."""
    from spark_bam_tpu.sbi.format import SbiFormatError, record_starts_to_flat

    index = store.load(path, config, strict=strict)
    if index is None or index.record_starts is None:
        return None
    try:
        return record_starts_to_flat(view, index.record_starts)
    except SbiFormatError:
        # Position names a block the file lacks — the fingerprint should
        # preclude this; recompute rather than trust it.
        return None


def record_starts(
    path, config: Config = Config(), checker: TpuChecker | None = None
) -> TpuLoadResult:
    """Whole-file record starts with the flat view retained (small files /
    callers that need the bytes, e.g. columnar parsing). For inputs larger
    than memory use ``record_starts_streaming`` / ``count_reads_tpu``, which
    run in O(window) host memory. With ``Config.cache`` enabled, a valid
    ``.sbi`` sidecar supplies the starts with zero checker work."""
    header = read_header(path)
    view = flatten_file(path)
    mode = config.cache_mode
    store = None
    if mode.enabled:
        from spark_bam_tpu.sbi.store import CacheStore

        store = CacheStore.from_env(policy=config.fault_policy)
        if mode.read:
            starts = _cached_record_starts(
                view, path, config, store, mode.strict
            )
            if starts is not None:
                obs.count("load.record_starts", len(starts))
                return TpuLoadResult(view, header, starts)
    if checker is None:
        # Size the window to the input: a small file in one kernel call, big
        # files stream through config.window_size windows. Power-of-two sizes
        # keep the jit cache small across files.
        want = min(config.window_size, max(view.size, 1))
        window = 1 << max(20, (want - 1).bit_length())
        checker = TpuChecker(
            np.array(header.contig_lengths.lengths_list(), dtype=np.int32),
            window=window,
            halo=min(config.halo_size, window // 4),
            reads_to_check=config.reads_to_check,
        )
    with obs.span("check.window", kind="whole_file", bytes=view.size):
        res = checker.check_buffer(view.data, at_eof=True)
    header_end = view.flat_of_pos(header.end_pos.block_pos, header.end_pos.offset)
    starts = np.flatnonzero(res.verdict)
    starts = starts[starts >= header_end]
    obs.count("load.record_starts", len(starts))
    if store is not None and mode.write:
        from spark_bam_tpu.sbi.format import (
            SbiIndex,
            fingerprint_of,
            record_starts_to_virtual,
        )

        store.merge_and_store(
            path, config,
            SbiIndex(
                fingerprint_of(path, config),
                record_starts=record_starts_to_virtual(view, starts),
            ),
        )
    return TpuLoadResult(view, header, starts)


def record_starts_streaming(path, config: Config = Config()):
    """Absolute flat record-start offsets, streamed per window in O(window)
    host memory (the WGS-scale path; reference CanLoadBam.scala:173-243 is
    likewise streaming per split)."""
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    yield from StreamChecker(path, config).record_starts()


def _interval_table(header, loci: LociSet | str) -> np.ndarray:
    """(R, 3) int32 rows of (ref_id, start, end) for the device filter."""
    if isinstance(loci, str):
        loci = LociSet.parse(loci, header.contig_lengths)
    name_to_idx = {
        name: idx for idx, (name, _) in header.contig_lengths.items()
    }
    rows = []
    for contig, ivs in loci.intervals.items():
        if contig not in name_to_idx:
            # ``20`` against a header of ``chr20`` would load nothing and
            # say nothing.
            raise BadLociError(
                f"bad loci: the header names no contig {contig!r} "
                f"(it has {', '.join(list(name_to_idx)[:3])}, ...)")
        ref = name_to_idx[contig]
        if not ivs:
            ivs = [(0, header.contig_lengths[ref][1])]
        rows.extend((ref, s, e) for s, e in ivs)
    return np.array(rows or [(-2, 0, 0)], dtype=np.int32)


#: tag value-type byte → fixed payload size; Z/H are NUL-terminated and
#: B is typed-array-counted — both handled inline by the scan.
_TAG_SIZES = {
    ord("A"): 1, ord("c"): 1, ord("C"): 1,
    ord("s"): 2, ord("S"): 2,
    ord("i"): 4, ord("I"): 4, ord("f"): 4,
}


def _tag_presence_mask(batch: ReadBatch, tags_required) -> np.ndarray:
    """Per-row mask: does the record's tag region contain every tag in
    ``tags_required`` (two-character names, e.g. ``("NM", "MD")``)?

    Guard-boundary-clean by construction (core/guard.py discipline for
    untrusted bytes, without the raise half): every offset is clamped to
    the buffer, the walk is bounded by the record's declared extent, and
    a malformed entry (unknown type byte, truncated payload, unbounded
    B-array count) STOPS the walk — the remaining tags read as absent,
    never as an exception or an over-extent read. No struct unpacks, no
    unbounded loops.
    """
    cols = batch.columns
    buf = batch.buf
    wanted = [t.encode("latin-1") for t in tags_required]
    mask = np.zeros(len(cols["valid"]), dtype=bool)
    if buf is None:
        raise ValueError(
            "tag filter needs the flat record buffer (batch.buf)"
        )
    nbuf = len(buf)
    starts = batch.starts
    name_off = cols["name_offset"]
    l_name = cols["l_read_name"]
    n_cigar = cols["n_cigar"]
    l_seq = cols["l_seq"]
    block_size = cols["block_size"]
    for i in np.flatnonzero(cols["valid"]):
        ls = int(l_seq[i])
        p = (int(name_off[i]) + int(l_name[i]) + 4 * int(n_cigar[i])
             + (ls + 1) // 2 + ls)
        end = int(starts[i]) + 4 + int(block_size[i])
        end = max(0, min(end, nbuf))
        p = max(0, min(p, end))
        present = set()
        while p + 3 <= end:
            tag = bytes(buf[p: p + 2])
            typ = int(buf[p + 2])
            p += 3
            if typ in _TAG_SIZES:
                q = p + _TAG_SIZES[typ]
            elif typ in (ord("Z"), ord("H")):
                nuls = np.flatnonzero(buf[p:end] == 0)
                if len(nuls) == 0:
                    break                     # unterminated: stop clean
                q = p + int(nuls[0]) + 1
            elif typ == ord("B"):
                if p + 5 > end:
                    break
                elem = _TAG_SIZES.get(int(buf[p]))
                count = (int(buf[p + 1]) | (int(buf[p + 2]) << 8)
                         | (int(buf[p + 3]) << 16) | (int(buf[p + 4]) << 24))
                if elem is None or count < 0 or count > end - p:
                    break                     # malformed: stop clean
                q = p + 5 + elem * count
            else:
                break                         # unknown type byte: stop clean
            if q > end:
                break                         # truncated payload: stop clean
            present.add(tag)
            p = q
        mask[i] = all(t in present for t in wanted)
    return mask


def _apply_filter(
    batch: ReadBatch,
    header,
    loci: LociSet | str | None,
    flags_required: int,
    flags_forbidden: int,
    tags_required=None,
) -> ReadBatch:
    """Narrow a batch's ``valid`` mask by loci/flags/tag-presence (the
    pushdown shared by the whole-file and streaming loads and the serve
    ``batch``/``aggregate`` ops). Flag-only filtering is a pure flag
    predicate — unmapped reads pass unless a flag excludes them; only a
    loci filter imposes the reference's unmapped-reads-never-overlap
    rule (CanLoadBam.scala:109-133). ``tags_required`` is an iterable of
    two-character tag names that must ALL be present in a record's tag
    region (e.g. ``("NM",)``)."""
    if tags_required:
        for t in tags_required:
            if not isinstance(t, str) or len(t) != 2:
                raise ValueError(
                    f"Bad tag name {t!r}: expected two characters (e.g. 'NM')"
                )
        batch.columns["valid"] = (
            batch.columns["valid"] & _tag_presence_mask(batch, tags_required)
        )
    if loci is None:
        flag = batch.columns["flag"]
        ok = ((flag & flags_required) == flags_required) & (
            (flag & flags_forbidden) == 0
        )
        batch.columns["valid"] = batch.columns["valid"] & ok
        return batch
    import jax.numpy as jnp

    # Only the columns the device filter reads make the trip; rows pad to
    # a power of two (valid=False ⇒ masked out) so the jit sees at most
    # log2 distinct shapes across batches, not one compile per batch size.
    m = len(batch.columns["valid"])
    m_pad = _next_pow2(m)

    def padded(k):
        col = batch.columns[k]
        if m_pad == m:
            return jnp.asarray(col)
        out = np.zeros(m_pad, dtype=col.dtype)
        out[:m] = col
        return jnp.asarray(out)

    cols = {
        k: padded(k) for k in ("pos", "ref_span", "ref_id", "flag", "valid")
    }
    mask = np.asarray(
        interval_flag_filter(
            cols, jnp.asarray(_interval_table(header, loci)),
            jnp.int32(flags_required), jnp.int32(flags_forbidden),
        )
    )[:m]
    batch.columns["valid"] = batch.columns["valid"] & mask
    return batch


def stream_read_batches(
    path,
    config: Config = Config(),
    loci: LociSet | str | None = None,
    flags_required: int = 0,
    flags_forbidden: int = 0,
):
    """Columnar ``ReadBatch``es per streaming window: the load path in
    O(window) host memory (WGS scale). Every window is put once and runs
    one program (``jit_load_window``: the count's check, and the records it
    accepts parsed and tested against ``loci`` and the flag masks there);
    what comes back is the rows that passed. Yields ``(abs_base, batch)``:
    ``batch.starts`` index ``batch.buf`` and ``abs_base + batch.starts`` are
    flat offsets; ``(-1, batch)`` entries carry records longer than the
    window lookahead, decoded exactly from the seekable stream and tested on
    the host (``StreamChecker.read_batches``).

    A pass is one trace (``obs.pass_span("load.reads")``), open from the
    first batch asked for to the last one handed out."""
    from spark_bam_tpu.tpu.parser import RowFilter
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    with obs.pass_span("load.reads", path=str(path)):
        checker = StreamChecker(path, config)
        rows = RowFilter.of(
            None if loci is None else _interval_table(checker.header, loci),
            flags_required, flags_forbidden)
        yield from checker.read_batches(rows)
    obs.count("load.passes")


def counts_across_chips() -> bool:
    """Whether a whole-file count runs on the mesh engine: the backend is a
    TPU and this process sees more than one of its chips. Observed, never
    asked for; the CPU backend's virtual devices do not select it."""
    import jax

    return jax.default_backend() == "tpu" and jax.local_device_count() > 1


def count_reads_tpu(path, config: Config = Config()) -> int:
    """count-reads on the device: O(window) host memory, the windows
    inflated on the host's worker pool, every position checked and the
    per-window counts reduced on the device. On a host with several TPU
    chips the file is counted across all of them
    (``parallel/stream_mesh.count_reads_sharded``: the rows of a step are
    inflated side by side and every chip checks its own); on one device the
    streaming checker's windows are put and dispatched up to ``ring_depth``
    ahead of it. This is the same code path chip_smoke.py drives.

    A call is one pass (``obs.pass_span``): a trace of its own under a
    live registry, with ``load.head_ms`` / ``load.drain_ms`` at its end."""
    with obs.pass_span("load.count", path=str(path)):
        if counts_across_chips():
            from spark_bam_tpu.parallel.mesh import local_mesh
            from spark_bam_tpu.parallel.stream_mesh import count_reads_sharded

            n = count_reads_sharded(path, config, mesh=local_mesh())
        else:
            from spark_bam_tpu.tpu.stream_check import StreamChecker

            n = StreamChecker(path, config).count_reads()
    obs.count("load.records", n)
    return n


def check_bam_tpu(
    path, config: Config = Config(), records_path=None, metas=None,
    progress=None,
) -> dict:
    """check-bam on the device: the checker's verdict at EVERY uncompressed
    position of ``path`` (header bytes included, as upstream's check-bam
    has it) against the ``.records`` truth (``records_path``, by default
    the sidecar beside the file), in O(window) host memory. The rows of a
    step are inflated on the host, checked on the chips this process sees
    (one or several: the same step, ``jit_confusion_step``) and compared
    with the truth there (``parallel/stream_mesh.check_bam_sharded``).

    Returns the confusion matrix (``true_positives``, ``false_positives``,
    ``false_negatives``, ``true_negatives``, ``positions``, ``devices``)
    and WHERE the two disagree: ``false_positive_positions`` and
    ``false_negative_positions``, sorted absolute flat offsets (int64),
    complete. A caller that has scanned the block table hands it on
    (``metas``), and one that wants to hear of every step gives a
    ``progress(steps done, positions done, positions in all)``:
    ``check_bam_sharded``'s. A call is one pass, as ``count_reads_tpu``'s
    is."""
    from spark_bam_tpu.parallel.mesh import local_mesh
    from spark_bam_tpu.parallel.stream_mesh import check_bam_sharded

    with obs.pass_span("load.check_bam", path=str(path)):
        out = check_bam_sharded(
            path, config, mesh=local_mesh(), records_path=records_path,
            metas=metas, progress=progress)
    obs.count("checkbam.passes")
    return out


def load_reads_columnar(
    path,
    loci: LociSet | str | None = None,
    flags_required: int = 0,
    flags_forbidden: int = 0,
    config: Config = Config(),
) -> ReadBatch:
    """All records of a BAM as columnar arrays; filters applied on device."""
    result = record_starts(path, config)
    with obs.span("load.parse", records=len(result.starts)):
        batch = parse_flat_records(result.view.data, result.starts)
    if loci is None and not flags_required and not flags_forbidden:
        return batch
    return _apply_filter(
        batch, result.header, loci, flags_required, flags_forbidden
    )
