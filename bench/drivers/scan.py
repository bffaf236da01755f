"""Whole-file passes of the headline command until the window has passed.

``count_reads_tpu(path, Config())`` over the cell's file, again and again; a
pass in flight when the seconds run out is finished, and every pass's count
is compared with the generator's index. The rate is taken to the end of the
last completed pass, over every byte of every pass, and reported under the
name the cell's own end-to-end entry gives it (``scan_rate``, or
``scan_rate.longread`` with a bound of its own).
"""

from __future__ import annotations

import time

from bench import oracle


def count_pass(path) -> int:
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.load.tpu_load import count_reads_tpu

    return count_reads_tpu(path, Config())


class Driver:
    def __init__(self, ctx, checks):
        self.ctx = ctx
        self.checks = checks
        self.expected = oracle.whole_file_count(ctx.index)
        (self.rate,) = (m["name"] for m in ctx.end_to_end
                        if m["name"] != "setup_s")

    def warm_up(self) -> None:
        self.checks.equal("warm_up.count", count_pass(self.ctx.path),
                          self.expected)

    def window(self, seconds: float) -> dict:
        ctx = self.ctx
        ends, failed = [], 0
        t0 = time.perf_counter()
        while not ends or ends[-1] < seconds:
            if len(ends) == ctx.traffic["profiled_pass"]:
                ctx.slice_begin()
            n = count_pass(ctx.path)
            ends.append(time.perf_counter() - t0)
            ctx.slice_end()
            if not self.checks.equal(f"pass_{len(ends)}.count", n,
                                     self.expected, seconds=ends[-1]):
                failed += 1
        size = int(ctx.index["uncompressed_bytes"])
        return {
            "attempted": len(ends), "failed": failed,
            "metrics": {self.rate: len(ends) * size / 1e6 / ends[-1]},
            "detail": {"passes": len(ends), "pass_ends_s": ends,
                       "uncompressed_bytes": size},
        }

    def close(self) -> None:
        pass
