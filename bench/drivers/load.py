"""Whole-file passes of the streaming load until the window has passed.

``stream_read_batches(path, Config(), loci=..., flags_forbidden=...)`` over
the cell's file, again and again, one caller; a pass in flight when the
seconds run out is finished. Every pass's rows are compared with
``bench/oracle_load.py``: how many, their flat starts, every fixed column
element for element, and (in the warm-up and in the last pass) the CRC32 of
each row's record bytes. The rows are read through what a ``ReadBatch`` has
(``batch[key]``, ``starts``, ``buf``, ``columns["valid"]``). The rate is
``scan_rate``'s own: uncompressed bytes of the completed passes over the
seconds to the end of the last.

The configuration's ``readback_sized_by_rows`` is held by the program's own
account of a pass (``load.d2h_bytes`` over ``load.passes``). A program that
keeps no such account cannot be held to it, and so cannot run this
deployment: the driver ends before the warm-up, not zero, with nothing left
behind.
"""

from __future__ import annotations

import time
import zlib

import numpy as np

from bench import oracle_load
from bench.readers import counter_sum

#: The load's own counters that must read zero in this cell: a row finished
#: on the host, or a record decoded from the seekable stream.
ZERO = ("load.cigar_host_fixups", "load.spilled_records")
#: The load's account of a pass, by which ``readback_sized_by_rows`` is held.
ACCOUNT = ("load.passes", "load.d2h_bytes")


def load_pass(path, traffic: dict, with_bytes: bool) -> dict:
    """One pass: the rows handed back, as ``oracle_load.expected_rows``
    names them (``crc`` only ``with_bytes``, and beside it ``crc_ms``, the
    milliseconds the CRCs took)."""
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.load.tpu_load import stream_read_batches

    parts: dict = {name: [] for name in ("starts", *oracle_load.COLUMNS)}
    crcs, crc_s = [], 0.0
    for base, batch in stream_read_batches(
            path, Config(), loci=traffic["loci"],
            flags_required=traffic["flags_required"],
            flags_forbidden=traffic["flags_forbidden"]):
        starts = np.asarray(batch.starts)[batch.columns["valid"]]
        # A batch decoded off the stream (base -1) has no flat offsets: its
        # rows fail the comparison of starts, as they should in this cell.
        parts["starts"].append(base + starts.astype(np.int64))
        for name in oracle_load.COLUMNS:
            parts[name].append(batch[name].astype(np.int64))
        if with_bytes:
            t0 = time.perf_counter()
            buf = memoryview(np.ascontiguousarray(batch.buf))
            sizes = 4 + batch["block_size"].astype(np.int64)
            crcs.extend(zlib.crc32(buf[s: s + n]) for s, n in
                        zip(starts.tolist(), sizes.tolist()))
            crc_s += time.perf_counter() - t0
    rows = {name: np.concatenate(p) if p else np.empty(0, np.int64)
            for name, p in parts.items()}
    if with_bytes:
        rows["crc"] = np.array(crcs, dtype=np.int64)
        rows["crc_ms"] = crc_s * 1e3
    return rows


class Driver:
    def __init__(self, ctx, checks):
        from spark_bam_tpu.obs import names

        missing = [n for n in ACCOUNT if not names.is_registered(n)]
        if missing:
            ctx.path.unlink(missing_ok=True)
            raise SystemExit(
                f"{ctx.cell['name']}: this program's streaming load keeps no "
                f"account of what a pass reads back ({', '.join(missing)} are "
                "not in obs.names), so it cannot be held to the "
                f"configuration's readback_sized_by_rows: {ctx.cell['config']}"
                " is not a deployment it runs; not measured")
        self.ctx = ctx
        self.checks = checks
        t = ctx.traffic
        self.expected = oracle_load.expected_rows(
            ctx.index, oracle_load.interval_of(t["loci"]),
            t["flags_required"], t["flags_forbidden"])
        (self.rate,) = (m["name"] for m in ctx.end_to_end
                        if m["name"] != "setup_s")
        self.crc_ms = None  # what the last pass with CRCs spent on them
        shapes = ctx.config["shapes"]
        # The most a pass may read back: by the rows, and a little a window.
        windows = -(-int(ctx.index["uncompressed_bytes"])
                    // shapes["window_bytes"])
        self.readback = (
            shapes["readback_bytes_a_row"] * len(self.expected["starts"])
            + shapes["readback_bytes_a_window"] * windows)

    def compare(self, where: str, rows: dict, **more) -> bool:
        """Every comparison of a pass; True when all hold."""
        want = self.expected
        self.crc_ms = rows.pop("crc_ms", self.crc_ms)
        ok = self.checks.equal(f"{where}.rows", len(rows["starts"]),
                               len(want["starts"]), **more)
        for name in rows:
            same = np.array_equal(rows[name], want[name])
            ok &= self.checks.equal(f"{where}.rows_differing.{name}",
                                    0 if same else 1, 0)
        return ok

    def account(self, where: str, passes: int) -> None:
        """The registry's counters since it was configured, over ``passes``
        passes: the two that read zero, and what came back from the device
        against the most the rows allow."""
        from spark_bam_tpu import obs

        snapshot = obs.registry().snapshot()
        for name in ZERO:
            self.checks.equal(f"{where}.{name}",
                              counter_sum(snapshot, name), 0)
        self.checks.equal(f"{where}.load.passes",
                          counter_sum(snapshot, "load.passes"), passes)
        over = counter_sum(snapshot, "load.d2h_bytes") - passes * self.readback
        self.checks.equal(f"{where}.load.d2h_bytes_over", max(over, 0), 0)

    def warm_up(self) -> None:
        # Twice: the second pass runs what the first compiled.
        for where in ("warm_up", "warm_up_again"):
            self.compare(where, load_pass(
                self.ctx.path, self.ctx.traffic, with_bytes=True))
        self.account("warm_up", 2)

    def window(self, seconds: float) -> dict:
        ctx = self.ctx
        ends, failed = [], 0
        t0 = time.perf_counter()
        while not ends or ends[-1] < seconds:
            if len(ends) == ctx.traffic["profiled_pass"]:
                ctx.slice_begin()
            rows = load_pass(ctx.path, ctx.traffic, with_bytes=False)
            ends.append(time.perf_counter() - t0)
            ctx.slice_end()
            if not self.compare(f"pass_{len(ends)}", rows, seconds=ends[-1]):
                failed += 1
        # One pass more, outside the rate: the rows' bytes at the window's
        # end are what they were at its start.
        if not self.compare("last_pass", load_pass(
                ctx.path, ctx.traffic, with_bytes=True)):
            failed += 1
        if ctx.trace:
            self.account("window", len(ends) + 1)
        size = int(ctx.index["uncompressed_bytes"])
        return {
            "attempted": len(ends) + 1, "failed": failed,
            "metrics": {self.rate: len(ends) * size / 1e6 / ends[-1]},
            "detail": {"passes": len(ends), "pass_ends_s": ends,
                       "uncompressed_bytes": size,
                       "rows_a_pass": len(self.expected["starts"]),
                       "crc_ms": self.crc_ms},
        }

    def close(self) -> None:
        pass
