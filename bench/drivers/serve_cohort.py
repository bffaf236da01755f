"""``serve_closed``'s closed loop over a cohort of files.

The same in-process service, clients and clock as ``serve_closed.Driver``
(whose window this is); a request draws its file Zipf over the set the
``cohort`` generator wrote (which file has which rank is a permutation drawn
from the seed) and its start uniformly among that file's members. Every
answer is compared with ``ranged_count`` of its own file's index. After the
window the service's own account of resident flat bytes is held to the
configuration's guarantee.
"""

from __future__ import annotations

import time
from pathlib import Path

import numpy as np

from bench import oracle
from bench.drivers import serve_closed


class Driver(serve_closed.Driver):
    def __init__(self, ctx, checks):
        super().__init__(ctx, checks)
        self.files = ctx.index["files"]
        smallest = min(int(f["compressed_bytes"]) for f in self.files)
        self.span = min(int(ctx.traffic["range_bytes"]), smallest // 2)
        self.starts = [
            f["block_starts"][f["block_starts"] + self.span
                              <= int(f["compressed_bytes"])]
            for f in self.files
        ]
        n = len(self.files)
        rng = np.random.default_rng([int(ctx.seed), 0x21BF])
        self.by_rank = rng.permutation(n)  # the file of each rank
        weight = 1.0 / np.arange(1, n + 1) ** float(
            ctx.traffic["zipf_exponent"])
        self.share = weight / weight.sum()

    def _ask(self, client, where: tuple) -> tuple:
        """``(latency ms, got, expected)`` of one ranged count of file
        ``where[0]`` from ``where[1]``."""
        f, start = self.files[where[0]], where[1]
        end = start + self.span
        t0 = time.perf_counter()
        got = client.request("count", path=f["path"], start=start,
                             end=end)["count"]
        ms = (time.perf_counter() - t0) * 1e3
        return ms, got, oracle.ranged_count(f, start, end)

    def warm_up(self) -> None:
        """One whole-file count of file 0 (the serve step) and one ranged
        count of every file (each opened, each compared)."""
        with self._client() as client:
            got = client.request(
                "count", path=self.files[0]["path"])["count"]
            self.checks.equal("warm_up.count", got,
                              oracle.whole_file_count(self.files[0]))
            for k in range(len(self.files)):
                _ms, got, want = self._ask(
                    client, (k, int(self.starts[k][0])))
                self.checks.equal(f"warm_up.ranged_count.file_{k}", got, want)

    def _loop(self, k: int, deadline: float, out: list) -> None:
        from spark_bam_tpu.serve.client import ServeClientError

        rng = np.random.default_rng([int(self.ctx.seed), 0xC11E, k])
        with self._client() as client:
            while time.perf_counter() < deadline:
                f = int(self.by_rank[rng.choice(len(self.share),
                                                p=self.share)])
                where = (f, int(self.starts[f][
                    rng.integers(len(self.starts[f]))]))
                try:
                    row = self._ask(client, where)
                except (ServeClientError, OSError) as exc:
                    row = (None, repr(exc), None)
                with self.lock:
                    out.append((k, "%d_%d" % where, *row))

    def window(self, seconds: float) -> dict:
        out = super().window(seconds)
        stats = self.service.stats()
        peak = stats.get("flat_resident_peak_bytes")  # None: no account kept
        most = int(self.ctx.config["guarantees"]["resident_flat_bytes_at_most"])
        self.checks.equal(
            "window.resident_flat_bytes_over",
            None if peak is None else max(0, int(peak) - most), 0,
            peak=peak, at_most=most)
        out["detail"].update(
            files_by_rank=self.by_rank.tolist(),
            flat_resident_peak_bytes=peak,
            flat_resident_bytes=stats.get("flat_resident_bytes"),
            files_open=stats.get("files_resident"))
        return out

    def close(self) -> None:
        super().close()
        for f in self.files[1:]:  # file 0 is the run's own to delete
            Path(f["path"]).unlink(missing_ok=True)
