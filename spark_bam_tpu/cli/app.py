"""Shared context for the checker commands.

The reference's ``CheckerApp`` (cli/.../check/CheckerApp.scala:31-223) built
around Spark broadcasts/accumulators; here one ``CheckerContext`` inflates
the file into a flat view once, evaluates whichever vectorized engines a
command needs, and renders the shared report blocks (position totals,
confusion matrix, annotated false positives).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property

import numpy as np

from spark_bam_tpu.bam.header import read_header
from spark_bam_tpu.bam.index_records import read_records_index
from spark_bam_tpu.bam.record import BamRecord
from spark_bam_tpu.bgzf.flat import FlatView, flatten_file
from spark_bam_tpu.check.flags import Flags
from spark_bam_tpu.check.seqdoop import seqdoop_check_flat
from spark_bam_tpu.check.vectorized import ChainResult, check_flat
from spark_bam_tpu.cli.output import Printer
from spark_bam_tpu.core.channel import path_exists, path_size
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.core.pos import Pos
from spark_bam_tpu.core.stats import format_bytes_binary


def render_record(rec: BamRecord, contigs) -> str:
    """HTSJDK-style record rendering + the reference's location suffix
    (check/.../PosMetadata.scala:35-55)."""
    pair = ""
    if rec.flag & 0x1:
        pair = " 2/2" if rec.flag & 0x80 else " 1/2"
    kind = "unmapped" if rec.is_unmapped else "aligned"
    s = f"{rec.read_name}{pair} {rec.read_length}b {kind} read"
    num_contigs = len(contigs)
    if rec.is_unmapped and rec.pos >= 0 and 0 <= rec.ref_id < num_contigs:
        s += f" (placed at {contigs.name(rec.ref_id)}:{rec.pos + 1})"
    elif not rec.is_unmapped:
        s += f" @ {contigs.name(rec.ref_id)}:{rec.pos + 1}"
    return s


@dataclass
class PosAnnotation:
    pos: Pos
    delta: int | None
    record_str: str | None
    flags: Flags

    def __str__(self) -> str:
        rec = (
            f"{self.delta} before {self.record_str}"
            if self.record_str is not None
            else "no next record"
        )
        return f"{self.pos}:\t{rec}. Failing checks: {self.flags}"


def print_report_header(p, total: int, compressed: int, num_reads: int):
    """The golden report's four-line header (positions / compressed size /
    ratio / reads) — one renderer for the in-memory and sharded paths."""
    p.echo(
        f"{total} uncompressed positions",
        f"{format_bytes_binary(compressed)} compressed",
        "Compression ratio: %.2f" % (total / compressed),
        f"{num_reads} reads",
    )


def funnel_status_line(
    config: Config,
    stats: dict | None = None,
    device: bool = True,
    full_masks: bool = False,
) -> str:
    """One ``funnel: …`` line for the check commands (sibling of
    ``sbi.store.cache_status_line``): the configured mode, whether the
    two-stage prefilter actually ran on this path, and — when the engine
    recorded ``funnel_stats`` — the measured reduction."""
    mode = config.funnel
    if not device or not config.funnel_enabled(full_masks):
        if mode == "off":
            why = "disabled"
        elif not device:
            why = "host engine, no device hot path"
        else:
            why = "full per-position flag masks requested"
        return f"funnel: off ({mode}: {why})"
    if stats and stats.get("screened"):
        screened = int(stats["screened"])
        survivors = int(stats["survivors"])
        reduction = screened / max(survivors, 1)
        return (
            f"funnel: on ({mode}): {screened} positions -> "
            f"{survivors} survivors, {reduction:.1f}x reduction"
        )
    return f"funnel: on ({mode})"


#: Uncompressed bytes from which ``auto`` takes the device engine (one
#: kernel window): below it the NumPy engine resolves a file faster than a
#: kernel compiles and launches.
DEVICE_FROM_BYTES = 32 << 20


def device_engine(backend: str, uncompressed_bytes: int) -> bool:
    """Whether the eager checker of an input this large runs on the device:
    a backend that names it, or ``auto`` on a TPU once the input outweighs a
    kernel's compile and launch (small files resolve faster in the NumPy
    engine). The one place that decides it: ``CheckerContext`` asks with
    its view's size, ``check-bam -s`` with the block table's sum."""
    if backend == "tpu":
        return True
    if backend != "auto" or uncompressed_bytes < DEVICE_FROM_BYTES:
        return False
    import jax

    return jax.default_backend() == "tpu"


class CheckerContext:
    def __init__(
        self,
        path,
        config: Config = Config(),
        printer: Printer | None = None,
        ranges=None,
    ):
        self.path = str(path)
        self.config = config
        self.printer = printer or Printer()
        self.ranges = ranges  # RangeSet of compressed byte ranges, or None

    @cached_property
    def position_mask(self) -> np.ndarray | None:
        """Mask of flat positions whose *block start* is inside the byte
        ranges (reference Blocks.Args --intervals, Blocks.scala:33-41)."""
        if self.ranges is None:
            return None
        mask = np.zeros(self.view.size, dtype=bool)
        starts = self.view.block_starts
        flats = self.view.block_flat
        for i, start in enumerate(starts):
            if int(start) in self.ranges:
                end = self.view.size if i + 1 == len(flats) else int(flats[i + 1])
                mask[int(flats[i]): end] = True
        return mask

    @cached_property
    def header(self):
        return read_header(self.path)

    @cached_property
    def contigs(self):
        return self.header.contig_lengths

    @cached_property
    def lengths(self) -> np.ndarray:
        return np.array(self.contigs.lengths_list(), dtype=np.int32)

    @cached_property
    def view(self) -> FlatView:
        return flatten_file(self.path)

    @cached_property
    def compressed_size(self) -> int:
        return path_size(self.path)

    @cached_property
    def selected_compressed_size(self) -> int:
        """Sum of the checked blocks' compressed sizes (the reference's
        compressedSizeAccumulator: per-block, honors --intervals, excludes
        the EOF sentinel)."""
        from spark_bam_tpu.bgzf.index_blocks import blocks_metadata

        return sum(
            m.compressed_size
            for m in blocks_metadata(self.path)
            if self.ranges is None or m.start in self.ranges
        )

    # ------------------------------------------------------------- engines
    @cached_property
    def eager_result(self) -> ChainResult:
        if self._use_tpu_backend():
            from spark_bam_tpu.tpu.checker import TpuChecker

            want = min(self.config.window_size, max(self.view.size, 1))
            window = 1 << max(20, (want - 1).bit_length())
            checker = TpuChecker(
                self.lengths,
                window=window,
                halo=min(self.config.halo_size, window // 4),
                reads_to_check=self.config.reads_to_check,
            )
            res = checker.check_buffer(self.view.data, at_eof=True)
            return ChainResult(
                verdict=res.verdict,
                reads_parsed=res.reads_parsed,
                fail_mask=res.fail_mask,
                reads_before=res.reads_before,
                exact=res.exact,
                escaped=res.escaped,
            )
        return check_flat(
            self.view.data,
            self.lengths,
            at_eof=True,
            reads_to_check=self.config.reads_to_check,
        )

    def _use_tpu_backend(self) -> bool:
        if not device_engine(self.config.backend, self.view.size):
            return False
        if self.config.backend == "auto":
            from spark_bam_tpu.core.platform import enable_compile_cache

            enable_compile_cache()
        return True

    @cached_property
    def eager_verdict(self) -> np.ndarray:
        return self.eager_result.verdict

    @cached_property
    def seqdoop_verdict(self) -> np.ndarray:
        return seqdoop_check_flat(self.view, len(self.contigs))

    @cached_property
    def truth(self) -> np.ndarray:
        truth = np.zeros(self.view.size, dtype=bool)
        for pos in read_records_index(self.records_path):
            truth[self.view.flat_of_pos(pos.block_pos, pos.offset)] = True
        return truth

    @property
    def records_path(self) -> str:
        return self.path + ".records"

    @property
    def has_records_index(self) -> bool:
        return path_exists(self.records_path)

    def verdict_for(self, name: str) -> np.ndarray:
        if name == "eager":
            return self.eager_verdict
        if name == "seqdoop":
            return self.seqdoop_verdict
        if name == "indexed":
            return self.truth
        raise KeyError(name)

    # --------------------------------------------------------- annotations
    def annotate(self, flat_idx: int) -> PosAnnotation:
        """Next-record metadata + full-checker flags for one position
        (reference PosMetadata.apply)."""
        pos = Pos(*self.view.pos_of_flat(flat_idx))
        mask = int(self.eager_result.fail_mask[flat_idx])
        flags = Flags.from_mask(mask, int(self.eager_result.reads_before[flat_idx]))
        true_flat = self.true_flat_eager
        j = int(np.searchsorted(true_flat, flat_idx))
        if j < len(true_flat) and true_flat[j] - flat_idx < self.config.max_read_size:
            nxt = int(true_flat[j])
            rec, _ = BamRecord.decode(self.view.data, nxt)
            return PosAnnotation(
                pos, nxt - flat_idx, render_record(rec, self.contigs), flags
            )
        return PosAnnotation(pos, None, None, flags)

    @cached_property
    def true_flat_eager(self) -> np.ndarray:
        return np.flatnonzero(self.eager_verdict)

    # ------------------------------------------------------------- reports
    def print_header_and_confusion(
        self, expected: np.ndarray, actual: np.ndarray
    ) -> None:
        """The shared check-bam/full-check report (CheckerApp.scala:64-222)."""
        p = self.printer
        sel = self.position_mask
        if sel is not None:
            expected = expected & sel
            actual = actual & sel
            in_scope = int(sel.sum())
        else:
            in_scope = self.view.size
        tp = int((expected & actual).sum())
        fp_idx = np.flatnonzero(~expected & actual)
        fn_idx = np.flatnonzero(expected & ~actual)
        num_reads = tp + len(fn_idx)
        tn = in_scope - num_reads - len(fp_idx)
        total = in_scope
        print_report_header(p, total, self.selected_compressed_size, num_reads)

        if not len(fp_idx) and not len(fn_idx):
            p.echo("All calls matched!")
            return

        p.echo(f"{len(fp_idx)} false positives, {len(fn_idx)} false negatives", "")

        if len(fp_idx):
            annotations = [self.annotate(int(i)) for i in fp_idx]
            hist: dict[str, int] = {}
            for a in annotations:
                key = str(a.flags)
                hist[key] = hist.get(key, 0) + 1
            rows = [
                f"{count}:\t{flags}"
                for flags, count in sorted(hist.items(), key=lambda kv: -kv[1])
            ]
            p.print_limited(
                rows,
                header="False-positive-site flags histogram:",
                truncated_header=lambda n: "False-positive-site flags histogram:",
            )
            p.echo("")
            p.print_limited(
                [str(a) for a in annotations],
                header="False positives with succeeding read info:",
                truncated_header=lambda n: (
                    f"{n} of {len(fp_idx)} false positives with succeeding read info::"
                ),
            )

        if len(fn_idx):
            p.print_limited(
                [str(Pos(*self.view.pos_of_flat(int(i)))) for i in fn_idx],
                header=f"{len(fn_idx)} false negatives:",
                truncated_header=lambda n: f"{n} of {len(fn_idx)} false negatives:",
            )
