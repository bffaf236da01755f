"""Whole-file check-bam passes against a ``.records`` truth that is wrong in
known places, until the window has passed.

``check_bam_tpu(path, Config())`` over the cell's file, again and again, one
caller, one pass in flight; a pass in flight when the seconds run out is
finished. The truth is a sidecar written once in set-up
(``oracle_checkbam``); every pass, the warm-up included, is compared with
the oracle: the four counts, ``positions`` and both position lists, element
for element. The rate is taken to the end of the last completed pass, over
every byte of every pass.

The two host re-derivations (a dirty step, a row over its list) are counters,
and counters exist only under a live registry. ``run.py`` keeps one through
the warm-up of every run and through the window of a traced run: there the
driver holds both at 0, and a registry that is NOT live there fails the
check, it does not skip it. An untraced window runs with no registry (the
harness's rule: no instrumentation in a timed window); its passes are the
warm-up's pass again, the same file against the same sidecar through the
same compiled step, so what the warm-up held holds for them.
"""

from __future__ import annotations

import time
from pathlib import Path

from bench import oracle_checkbam
from bench.readers import counter_sum

COUNTS = ("true_positives", "false_positives", "false_negatives",
          "true_negatives", "positions")
LISTS = ("false_positive_positions", "false_negative_positions")
#: Zero in every pass: a step with escaped chains, or a row with more
#: mismatches than its list holds, is re-derived on the host.
HOST_REDERIVED = ("mesh.dirty_steps", "checkbam.list_overflows")


class Driver:
    def __init__(self, ctx, checks):
        # A program without the entry ends the cell here, before any pass.
        from spark_bam_tpu.load.tpu_load import check_bam_tpu

        self.check_bam = check_bam_tpu
        self.ctx = ctx
        self.checks = checks
        traffic = ctx.traffic
        dropped, added = oracle_checkbam.perturb(
            ctx.index, ctx.seed, traffic["truth_dropped"],
            traffic["truth_added"], traffic["seam_drops"],
            int(ctx.config["shapes"]["row_owned_bytes"]))
        self.expected = oracle_checkbam.expected(ctx.index, dropped, added)
        self.sidecar = Path(str(ctx.path) + ".records")
        self.sidecar.write_text(oracle_checkbam.sidecar_text(
            ctx.index, oracle_checkbam.truth(ctx.index, dropped, added)))

    def one_pass(self):
        from spark_bam_tpu.core.config import Config

        return self.check_bam(self.ctx.path, Config())

    def compare(self, where: str, got: dict, counters: bool, **more) -> bool:
        from spark_bam_tpu import obs

        ok = True
        for key in COUNTS:
            ok &= self.checks.equal(f"{where}.{key}", int(got[key]),
                                    self.expected[key], **more)
        for key in LISTS:
            ok &= self.checks.equal(f"{where}.{key}", got[key].tolist(),
                                    self.expected[key])
        if counters:
            live = obs.enabled()
            ok &= self.checks.equal(f"{where}.registry_live", live, True)
            if live:
                snapshot = obs.registry().snapshot()
                for name in HOST_REDERIVED:
                    ok &= self.checks.equal(
                        f"{where}.{name}", counter_sum(snapshot, name), 0)
        return ok

    def warm_up(self) -> None:
        self.compare("warm_up", self.one_pass(), counters=True)

    def window(self, seconds: float) -> dict:
        ctx = self.ctx
        ends, failed = [], 0
        t0 = time.perf_counter()
        while not ends or ends[-1] < seconds:
            if len(ends) == ctx.traffic["profiled_pass"]:
                ctx.slice_begin()
            got = self.one_pass()
            ends.append(time.perf_counter() - t0)
            ctx.slice_end()
            failed += not self.compare(f"pass_{len(ends)}", got,
                                       counters=ctx.trace, seconds=ends[-1])
        size = int(ctx.index["uncompressed_bytes"])
        return {
            "attempted": len(ends), "failed": failed,
            "metrics": {"scan_rate": len(ends) * size / 1e6 / ends[-1]},
            "detail": {"passes": len(ends), "pass_ends_s": ends,
                       "uncompressed_bytes": size},
        }

    def close(self) -> None:
        self.sidecar.unlink(missing_ok=True)
