"""A closed loop of clients against one in-process service.

One ``SplitService(Config(), mesh=local_mesh())`` behind a ``ServerThread``
in this process; ``clients`` threads, each with its own ``ServeClient``, send
``count`` over a block-aligned compressed range of ``range_bytes`` drawn
uniformly from the file, the next request when the reply is in. Latency is
the client's clock, send to full reply. A request that fails, is refused or
answers wrongly has no latency and counts in ``failed``. Requests in flight
when the seconds run out are finished and count.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from bench import oracle


def percentile(values: list, q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


class Driver:
    def __init__(self, ctx, checks):
        from spark_bam_tpu.core.config import Config
        from spark_bam_tpu.parallel.mesh import local_mesh
        from spark_bam_tpu.serve import ServerThread, SplitService

        self.ctx = ctx
        self.checks = checks
        self.service = SplitService(Config(), mesh=local_mesh())
        self.server = ServerThread(self.service).start()
        self.lock = threading.Lock()
        span = int(ctx.traffic["range_bytes"])
        starts = ctx.index["block_starts"]
        self.span = min(span, int(ctx.index["compressed_bytes"]) // 2)
        self.starts = starts[starts + self.span
                             <= int(ctx.index["compressed_bytes"])]

    def _client(self):
        from spark_bam_tpu.serve import ServeClient

        return ServeClient(self.server.address,
                           timeout=float(self.ctx.traffic["timeout_s"]))

    def _ask(self, client, start: int) -> tuple:
        """``(latency ms, got, expected)`` of one ranged count."""
        end = start + self.span
        t0 = time.perf_counter()
        got = client.request("count", path=str(self.ctx.path), start=start,
                             end=end)["count"]
        ms = (time.perf_counter() - t0) * 1e3
        return ms, got, oracle.ranged_count(self.ctx.index, start, end)

    def warm_up(self) -> None:
        """Warms the file (one whole-file count: flat view, serve step) and
        one ranged request, each compared with the index."""
        with self._client() as client:
            got = client.request("count", path=str(self.ctx.path))["count"]
            self.checks.equal("warm_up.count", got,
                              oracle.whole_file_count(self.ctx.index))
            _ms, got, want = self._ask(client, int(self.starts[0]))
            self.checks.equal("warm_up.ranged_count", got, want)

    def _loop(self, k: int, deadline: float, out: list) -> None:
        from spark_bam_tpu.serve.client import ServeClientError

        rng = np.random.default_rng([int(self.ctx.seed), 0xC11E, k])
        with self._client() as client:
            while time.perf_counter() < deadline:
                start = int(self.starts[rng.integers(len(self.starts))])
                try:
                    row = self._ask(client, start)
                except (ServeClientError, OSError) as exc:
                    row = (None, repr(exc), None)
                with self.lock:
                    out.append((k, start, *row))

    def window(self, seconds: float) -> dict:
        ctx = self.ctx
        rows: list = []
        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=self._loop, name=f"client-{k}",
                             args=(k, t0 + seconds, rows))
            for k in range(int(ctx.traffic["clients"]))
        ]
        for t in threads:
            t.start()
        # The profiled slice: some seconds of steady serving, after a lead.
        lead = min(float(ctx.traffic["profile_lead_s"]), seconds / 4)
        time.sleep(lead)
        ctx.slice_begin()
        time.sleep(min(float(ctx.traffic["profile_slice_s"]), seconds / 2))
        ctx.slice_end()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0

        wrong = [r for r in rows if r[2] is None or r[3] != r[4]]
        for k, start, _ms, got, want in wrong[:8]:
            self.checks.equal(f"request.client_{k}.start_{start}", got, want)
        self.checks.equal("requests.wrong_or_failed", len(wrong), 0,
                          attempted=len(rows))
        good = [r[2] for r in rows if r[2] is not None and r[3] == r[4]]
        return {
            "attempted": len(rows), "failed": len(wrong),
            "metrics": {
                "request_p50_ms": percentile(good, 50) if good else None,
                "request_p80_ms": percentile(good, 80) if good else None,
            },
            "detail": {"requests": len(rows), "wall_s": wall,
                       "requests_per_s": len(rows) / wall,
                       "max_ms": max(good) if good else None},
        }

    def close(self) -> None:
        self.server.stop()
        self.service.close()
