#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at ``Config()`` defaults (24 MiB windows + 4 MiB halo → the 32 MiB
kernel) on a generated short-read BAM:

- ``generate``: ``benchmarks.synth.synth_bam`` (192 MiB by default) into
  ``.smoke_data/`` inside the checkout; the manifest's ``reads`` (the
  generator's own length-prefix walk) is the oracle.
- ``count``: ``load.tpu_load.count_reads_tpu(path, Config())`` twice; the
  second run must report zero compilations.
- ``cli``: ``cli.main.main`` — index-blocks, index-records, then
  ``count-reads --sharded`` and ``check-bam --sharded`` on a 128 MiB file.
- ``serve``: a ``SplitService`` behind a ``ServerThread``; a ``ServeClient``
  asks ``plan``, ``count``, ``batch`` and ``aggregate`` and each answer is
  compared with the direct one.
- ``--chips 4`` (instead of the device phases above): ``count_reads_sharded``
  and ``check_bam_sharded`` over a mesh of every device against the same
  calls on a one-device mesh, with the windows each device was given.

One JSON line per phase on stdout; the LAST line is
``{"ok": ..., "device": {"platform", "kind", "count"}}``. Exit 0 only when
every phase agreed with its oracle on a TPU with no demotion counted.
``--allow-cpu`` is for rehearsals: it lets the phases run on the CPU backend
and changes nothing else — ``"ok"`` stays false and the exit code non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
DATA = ROOT / ".smoke_data"

#: Counters that must stay at zero: each one is a path that quietly left
#: the device, or (escape retries) a kernel that misjudged a short read.
ZERO_COUNTERS = (
    "check.fused_demotions", "check.count_escape_retries",
    "agg.host_fallbacks", "mesh.escapes",
)
EVIDENCE_COUNTERS = (
    "check.windows", "inflate.windows",
    "mesh.steps", "serve.batches", "serve.batch_rows", "funnel.positions",
    "funnel.survivors", "funnel.lanes",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


class Compiles:
    """Counts what jax hands its backend compiler (a persistent-cache hit
    still passes through, and is counted apart)."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = self.hits = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1
            self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.hits += 1

    def mark(self) -> tuple:
        return self.n, self.hits, self.seconds

    def since(self, mark: tuple) -> dict:
        n, hits, seconds = mark
        return {
            "compiles": self.n - n,
            "cache_hits": self.hits - hits,
            "compile_seconds": round(self.seconds - seconds, 3),
        }


def counters() -> dict:
    """Name → summed value of the live obs registry's counters."""
    from spark_bam_tpu import obs

    out: dict = {}
    for c in obs.registry().snapshot()["counters"]:
        out[c["name"]] = out.get(c["name"], 0) + c["value"]
    return out


class Phase:
    """One phase: fresh obs registry in, one JSON line out. ``check`` records
    a comparison; the phase (and so the run) fails on any that differ, on any
    non-zero demotion counter, and on any exception — which propagates."""

    def __init__(self, name: str, compiles: Compiles):
        self.name = name
        self.compiles = compiles
        self.fields: dict = {}
        self.ok = True

    def __enter__(self) -> "Phase":
        from spark_bam_tpu import obs

        obs.shutdown()
        obs.configure()
        self.mark = self.compiles.mark()
        self.t0 = time.perf_counter()
        return self

    def check(self, what: str, got, expected, show: bool = True) -> None:
        """Compare an answer with its oracle; ``show=False`` keeps a long
        answer out of the phase line and records only the verdict."""
        same = got == expected
        self.fields[what] = (
            {"got": got, "expected": expected, "equal": same} if show
            else {"equal": same}
        )
        self.ok &= same

    def __exit__(self, exc_type, exc, tb) -> bool:
        from spark_bam_tpu import obs

        seconds = time.perf_counter() - self.t0
        seen = counters()
        obs.shutdown()
        demotions = {k: seen.get(k, 0) for k in ZERO_COUNTERS}
        if exc_type is not None or any(demotions.values()):
            self.ok = False
        emit({
            "phase": self.name, "ok": self.ok, "seconds": round(seconds, 3),
            **self.compiles.since(self.mark), **self.fields,
            "demotions": demotions,
            "evidence": {k: seen[k] for k in EVIDENCE_COUNTERS if k in seen},
            **({"error": repr(exc)} if exc_type is not None else {}),
        })
        return False  # an exception ends the run; nothing is swallowed


def engines(config) -> dict:
    """Which engines ``auto`` resolves to in this process."""
    return {"funnel": config.funnel_enabled()}


def generate(compiles: Compiles, sizes: dict) -> dict:
    """``{label: (path, manifest)}`` for each distinct size asked for."""
    from spark_bam_tpu.benchmarks.synth import synth_bam, synthetic_fixture

    made: dict = {}
    with Phase("generate", compiles) as ph:
        DATA.mkdir(exist_ok=True)
        seed = synthetic_fixture(cache_dir=DATA)
        by_size: dict = {}
        for label, size in sizes.items():
            if size not in by_size:
                path = DATA / f"smoke_{size}.bam"
                by_size[size] = (path, synth_bam(path, size, fixture=seed))
            made[label] = by_size[size]
        ph.fields["seed"] = str(seed)
        ph.fields["files"] = {
            label: {"path": str(p), **{k: m[k] for k in (
                "reads", "reps", "compressed_bytes", "uncompressed_bytes")}}
            for label, (p, m) in made.items()
        }
    return made


def phase_count(compiles: Compiles, path: Path, reads: int) -> bool:
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.load.tpu_load import count_reads_tpu

    ok = True
    for run in ("count", "count_again"):
        with Phase(run, compiles) as ph:
            ph.fields["engines"] = engines(Config())
            ph.check("reads", count_reads_tpu(path, Config()), reads)
            ph.fields["windows"] = counters().get("check.windows", 0)
            if run == "count_again":
                ph.check("compiles_in_warm_run",
                         compiles.since(ph.mark)["compiles"], 0)
        ok &= ph.ok
    return ok


def cli(argv: list) -> None:
    from spark_bam_tpu.cli.main import main

    rc = main(argv)
    if rc != 0:
        raise RuntimeError(f"cli {' '.join(argv)} exited {rc}")


def phase_cli(compiles: Compiles, path: Path, reads: int) -> bool:
    import re

    from spark_bam_tpu.core.config import Config

    with Phase("cli", compiles) as ph:
        ph.fields["engines"] = engines(Config())
        cli(["index-blocks", str(path)])
        cli(["index-records", str(path)])
        out = DATA / "cli_count.txt"
        cli(["count-reads", "--sharded", "-o", str(out), str(path)])
        m = re.search(r"Read count: (\d+)", out.read_text())
        ph.check("count_reads_sharded", m and int(m.group(1)), reads)
        out = DATA / "cli_check.txt"
        cli(["check-bam", "--sharded", "-o", str(out), str(path)])
        text = out.read_text()
        m = re.search(r"^(\d+) reads$", text, re.M)
        ph.check("check_bam_reads", m and int(m.group(1)), reads)
        ph.check("check_bam_all_matched", "All calls matched!" in text, True)
    return ph.ok


def seed_aggregate(seed: Path, reps: int, plan, nc: int) -> dict:
    """The aggregate oracle, by ``agg/host.py`` alone: every metric is a sum
    over records, and the file is the seed's records ``reps`` times."""
    from spark_bam_tpu.agg.host import columns_from_records, host_aggregate
    from spark_bam_tpu.load.api import load_bam

    once = host_aggregate(
        columns_from_records(load_bam(str(seed)).collect()), plan, nc
    )
    return {k: (v * reps).tolist() for k, v in once.items()}


def phase_serve(compiles: Compiles, path: Path, manifest: dict) -> bool:
    from spark_bam_tpu.agg.plan import AggConfig, decode_result
    from spark_bam_tpu.bam.header import read_header
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.load.api import export, split_starts
    from spark_bam_tpu.parallel.mesh import local_mesh
    from spark_bam_tpu.serve import ServeClient, ServerThread, SplitService

    columns = "flag,pos,mapq"
    with Phase("serve", compiles) as ph:
        ph.fields["engines"] = engines(Config())
        svc = SplitService(Config(), mesh=local_mesh())
        try:
            with ServerThread(svc) as srv, ServeClient(
                srv.address, timeout=900.0
            ) as client:
                plan = client.request("plan", path=str(path))
                counts = [
                    client.request("count", path=str(path))["count"]
                    for _ in range(2)
                ]
                batch = client.request(
                    "batch", path=str(path), columns=columns
                )
                agg = client.request("aggregate", path=str(path))
        finally:
            svc.close()
        # Direct answers, after the requests (outside any timing).
        direct = split_starts(str(path), config=Config())
        ph.check(
            "plan",
            [(s["start"], s["end"], s["pos"]) for s in plan["splits"]],
            [(s.start, s.end, None if p is None else [p.block_pos, p.offset])
             for s, p in direct],
            show=False,
        )
        ph.fields["plan"]["splits"] = len(direct)
        ph.check("count", counts, [manifest["reads"]] * 2)
        ph.check("batch_rows", batch["rows"], manifest["reads"])
        sink = DATA / "serve_batch.sbcr"
        export(str(path), str(sink), fmt="native", columns=columns)
        got = b"".join(batch["_binary"])
        ph.check("batch_bytes_equal_export", got, sink.read_bytes(),
                 show=False)
        ph.fields["batch_bytes"] = len(got)
        agg_plan = AggConfig.parse("")
        nc = len(read_header(str(path)).contig_lengths.lengths_list())
        got = decode_result(agg["result"], agg["_binary"][0])
        ph.check("aggregate_rows", agg["rows"], manifest["reads"])
        want = seed_aggregate(
            Path(manifest["fixture"]), manifest["reps"], agg_plan, nc
        )
        same = {k: got[k].reshape(-1).tolist() == want[k] for k in want}
        ph.check("aggregate", same, {k: True for k in want})
    return ph.ok


def windows_per_device(path: Path, mesh) -> dict:
    """Device id → windows (rows) the sharded count places there: the
    stream's own plan (``row_slots``), nothing inflated."""
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.parallel.stream_mesh import _ShardedStream

    st = _ShardedStream(path, Config(), mesh, None, None, None)
    devices = list(mesh.devices.flat)
    placed = {int(d.id): 0 for d in devices}
    for c0 in range(0, st.per_proc, st.step_rows_local):
        for _g, d, _slot in st.row_slots(c0):
            placed[int(devices[d].id)] += 1
    return placed


def phase_mesh(compiles: Compiles, path: Path, reads: int) -> bool:
    """The four-chip path and what it is compared with — nothing else."""
    import jax
    import numpy as np

    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.parallel.mesh import make_mesh
    from spark_bam_tpu.parallel.stream_mesh import (
        check_bam_sharded, count_reads_sharded,
    )

    with Phase("index", compiles):
        cli(["index-blocks", str(path)])
        cli(["index-records", str(path)])
    answers = {}
    ok = True
    for label, devices in (("all", jax.devices()), ("one", jax.devices()[:1])):
        with Phase(f"mesh_{label}", compiles) as ph:
            ph.fields["engines"] = engines(Config())
            mesh = make_mesh(devices)
            stats: dict = {}
            n = count_reads_sharded(path, Config(), mesh=mesh, stats_out=stats)
            ph.check("count_reads_sharded", n, reads)
            conf = check_bam_sharded(path, Config(), mesh=mesh)
            ph.check(
                "check_bam_sharded",
                [conf["false_positives"], conf["false_negatives"],
                 conf["true_positives"]],
                [0, 0, reads],
            )
            ph.fields["devices"] = conf["devices"]
            ph.fields["stats"] = stats
            placed = windows_per_device(path, mesh)
            ph.fields["windows_per_device"] = placed
            ph.check("every_device_has_windows", all(placed.values()),
                     stats["rows"] >= len(devices))
            answers[label] = (n, conf)
        ok &= ph.ok
    same = answers["all"][0] == answers["one"][0] and all(
        np.array_equal(answers["all"][1][k], answers["one"][1][k])
        for k in answers["all"][1] if k != "devices"
    )
    emit({"phase": "mesh_compare", "ok": same,
          "devices": [answers["all"][1]["devices"],
                      answers["one"][1]["devices"]]})
    return ok and same


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bytes", default="192MB",
                    help="compressed size of the count phase's BAM")
    ap.add_argument("--small-bytes", default=None,
                    help="compressed size of the cli/serve/mesh BAM "
                         "(default: 128MB, or --bytes when that is smaller)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded path on all devices vs one")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal: run the phases on the CPU backend; "
                         "the result is still a failure")
    args = ap.parse_args(argv)

    os.environ.pop("SPARK_BAM_METRICS_OUT", None)  # cli.main would export
    device = None
    ok = False
    try:
        import jax

        from spark_bam_tpu.core.config import parse_bytes
        from spark_bam_tpu.core.platform import enable_compile_cache
        from spark_bam_tpu.native.build import native_info, require_native

        cache_dir = enable_compile_cache()
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
        on_chip = dev.platform == "tpu" and len(jax.devices()) == args.chips
        if not on_chip and not args.allow_cpu:
            raise RuntimeError(
                f"need {args.chips} tpu device(s), found "
                f"{device['count']} x {device['platform']}"
            )
        require_native("chip_smoke.py")
        emit({"phase": "start", "ok": True, "device": device,
              "cache_dir": cache_dir, "native": native_info(),
              "jax": jax.__version__})

        compiles = Compiles()
        big = parse_bytes(args.bytes)
        small = (parse_bytes(args.small_bytes) if args.small_bytes
                 else min(128 << 20, big))
        if args.chips == 4:
            files = generate(compiles, {"small": small})
            path, manifest = files["small"]
            ok = phase_mesh(compiles, path, manifest["reads"])
        else:
            files = generate(compiles, {"big": big, "small": small})
            path, manifest = files["big"]
            ok = phase_count(compiles, path, manifest["reads"])
            path, manifest = files["small"]
            ok &= phase_cli(compiles, path, manifest["reads"])
            ok &= phase_serve(compiles, path, manifest)
        ok = bool(ok and on_chip)
    except Exception as exc:  # reported, and the last line is printed
        import traceback

        ok = False
        emit({"phase": "error", "ok": False, "error": repr(exc)})
        traceback.print_exc()
    if device is None:
        return 1  # no jax, or nothing of the repo beside this file
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
