#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file found by the name ``BENCHMARK.json`` gives (see ``bench/README.md``).
Set-up (all of it inside ``setup_s``): the native library, the cell's file
from ``--seed``, one warm-up that executes every shape of the window under a
live obs registry and compares its answers with the generator's index. Then
the window, in which nothing may compile. The last line of stdout is the
result object; earlier lines are JSON too, one per comparison.

There is no fallback to the CPU: without the cell's TPU devices the command
exits 3 and prints no result. ``--rehearse`` runs the configuration's
``rehearsal`` size on whatever backend is there, for the builder's own
rehearsals, and always prints ``"correct": false``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python can see it

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

BENCH = ROOT / "bench"
DATA = ROOT / ".smoke_data" / "bench"

#: Counters that must read zero: each is a path that left the device, which
#: is a different result and not a slower one.
ZERO_COUNTERS = ("check.fused_demotions", "agg.host_fallbacks")
ESCAPE_COUNTER = "check.count_escape_retries"
#: ``pass_17.count`` and ``pass_18.count`` are one kind of comparison.
_NUMBERED = re.compile(r"_\d+(?=\.|$)")


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def plugin(kind: str, name: str):
    """``bench/<kind>/<name>.py``, found by name: no registry to edit."""
    return importlib.import_module(f"bench.{kind}.{name}")


def resolve_cell(workload: str) -> dict:
    """The cell, its configuration and traffic files and the metrics it
    reports, all from the names in ``BENCHMARK.json``."""
    bm = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bm["configs"] if c["name"] == cell["config"])

    def mine(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell,
        "config": load_json(ROOT / cfg_entry["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in bm["end_to_end"] if mine(m)],
        "per_layer": [m for m in bm["per_layer"] if mine(m)],
    }


class Checks:
    """Every number compared, printed beside its limit; ``ok`` is their
    conjunction. Exactness is the system's guarantee, so a count's limit is
    its expected value and a counter's limit is 0."""

    def __init__(self):
        self.ok = True
        self.compared: dict = {}

    def equal(self, what: str, got, expected, **more) -> bool:
        same = got == expected
        self.ok &= same
        # One row a kind of comparison: how many were made, and the first
        # that failed, else the last.
        row = self.compared.setdefault(
            _NUMBERED.sub("", what), {"n": 0, "ok": True})
        row["n"] += 1
        if row["ok"]:
            row.update(got=got, limit=expected, ok=same)
        emit({"check": what, "got": got, "limit": expected,
              "rule": "equal", "ok": same, **more})
        return same

    def zero_counters(self, where: str, snapshot: dict, config: dict) -> None:
        from bench.readers import counter_sum

        names = list(ZERO_COUNTERS)
        if config["guarantees"].get("no_record_exceeds_halo"):
            names.append(ESCAPE_COUNTER)
        for name in names:
            self.equal(f"{where}.{name}", counter_sum(snapshot, name), 0)


class Context:
    """What a driver and the readers are handed."""

    def __init__(self, resolved: dict, seed: int, trace: bool, path: Path,
                 index: dict, profile_dir: Path):
        self.cell = resolved["cell"]
        self.config = resolved["config"]
        self.traffic = resolved["traffic"]
        self.end_to_end = resolved["end_to_end"]
        self.seed = seed
        self.trace = trace
        self.path = path
        self.index = index
        self.profile_dir = profile_dir
        self.profile = None  # the reduced device trace, once taken
        self._t_slice = None

    # A driver brackets the part of its window that is profiled: one steady
    # pass, or some seconds of serving. No-ops in an untraced run.
    def slice_begin(self) -> None:
        if not self.trace or self.profile is not None:
            return
        import jax

        shutil.rmtree(self.profile_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.profile_dir))
        self._t_slice = time.perf_counter()

    def slice_end(self) -> None:
        if self._t_slice is None:
            return
        import jax

        from bench import trace_reduce

        window_s = time.perf_counter() - self._t_slice
        self._t_slice = None
        jax.profiler.stop_trace()
        self.profile = trace_reduce.reduce_dir(self.profile_dir)
        self.profile["window_s"] = window_s
        emit({"phase": "profile", "window_s": window_s,
              **{k: self.profile[k] for k in
                 ("busy_s", "devices", "file_bytes", "inventory")}})


def device_info() -> dict:
    """The device as JAX reports it. The peak is what the fullest chip held:
    live arrays (``peak_bytes_in_use``) plus what the runtime set aside for
    the programs' temporaries (``peak_bytes_reserved``), which on a TPU is
    most of it and which ``peak_bytes_in_use`` leaves out."""
    import jax

    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in stats]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False) -> dict:
    """The whole run; returns the result object. ``rehearse`` skips the look
    for a chip and takes the configuration's rehearsal size, and changes
    nothing else: ``correct`` here is what the comparisons gave (``main``
    prints it as false), so a test can see a broken path fail them."""
    resolved = resolve_cell(workload)
    cell, config = resolved["cell"], resolved["config"]

    import jax

    from bench.compiles import Compiles
    from spark_bam_tpu import obs
    from spark_bam_tpu.core.platform import enable_compile_cache
    from spark_bam_tpu.native.build import native_info, require_native

    os.environ.pop("SPARK_BAM_METRICS_OUT", None)
    cache_dir = enable_compile_cache()
    found = device_info()
    on_chip = found["platform"] == "tpu" and found["count"] == cell["chips"]
    if not on_chip and not rehearse:
        print(f"need {cell['chips']} tpu device(s), found {found['count']} x "
              f"{found['platform']}: not measuring", file=sys.stderr)
        raise SystemExit(3)
    peaks = load_json(BENCH / "peaks.json")
    if on_chip and found["kind"] not in peaks:
        raise SystemExit(f"device kind {found['kind']!r} is not in peaks.json")
    require_native("bench/run.py")
    compiles = Compiles()
    emit({"phase": "start", "workload": workload, "seed": seed,
          "device": found, "cache_dir": cache_dir, "native": native_info(),
          "jax": jax.__version__, "rehearse": rehearse})

    # The cell's file, from the seed.
    t0 = time.perf_counter()
    size = int(config["rehearsal" if rehearse else "scale"]
               ["uncompressed_bytes"])
    DATA.mkdir(parents=True, exist_ok=True)
    path = DATA / f"{cell['config']}-{seed}.bam"
    index = plugin("generators", config["generator"]).generate(
        config["params"], seed, size, path)
    emit({"phase": "generate", "seconds": time.perf_counter() - t0,
          "path": str(path), "records": len(index["record_starts"]),
          **{k: index[k] for k in ("uncompressed_bytes", "compressed_bytes",
                                   "ratio", "record_bytes_mean")}})

    ctx = Context(resolved, seed, trace, path, index,
                  DATA / f"profile-{workload}")
    checks = Checks()
    driver = plugin("drivers", ctx.traffic["driver"]).Driver(ctx, checks)
    try:
        # Warm-up under a live registry: the counters are no-ops without one.
        obs.shutdown()
        obs.configure()
        t0 = time.perf_counter()
        mark = compiles.mark()
        driver.warm_up()
        checks.zero_counters("warm_up", obs.registry().snapshot(), config)
        emit({"phase": "warm_up", "seconds": time.perf_counter() - t0,
              **compiles.since(mark)})
        obs.shutdown()
        if trace:
            obs.configure()  # a fresh one: the window's readings alone

        mark = compiles.mark()
        setup_s = time.perf_counter() - T_START
        measured = driver.window(float(seconds))
        ctx.slice_end()  # if the driver left the slice open
        in_window = compiles.since(mark)
        checks.equal("compiles_in_window", in_window["compiles"], 0)
        snapshot = None
        if trace:
            snapshot = obs.registry().snapshot()
            checks.zero_counters("window", snapshot, config)
    finally:
        driver.close()
        obs.shutdown()
        path.unlink(missing_ok=True)

    device = device_info()
    measured["metrics"]["setup_s"] = setup_s
    emit({"phase": "window", "end_to_end": measured["metrics"],
          "detail": measured.get("detail"), **in_window,
          "memory_stats": jax.devices()[0].memory_stats()})
    if trace:
        sources = {
            "snapshot": snapshot, "profile": ctx.profile, "device": device,
            "config": config, "peaks": peaks.get(device["kind"]),
        }
        metrics = {}
        for m in resolved["per_layer"]:
            spec = load_json(BENCH / "layer_metrics" / f"{m['name']}.json")
            value = plugin("readers", spec["reader"]).read(
                spec.get("args", {}), sources)
            if value is not None:  # nothing to read: left out of the line
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if ctx.profile is not None:
            device["busy_s"] = ctx.profile["busy_s"]
            device["window_s"] = ctx.profile["window_s"]
    else:
        metrics = {
            m["name"]: {"value": measured["metrics"][m["name"]],
                        "unit": m["unit"]}
            for m in resolved["end_to_end"]
        }
    result = {
        "correct": bool(checks.ok and (on_chip or rehearse)),
        "attempted": measured["attempted"], "failed": measured["failed"],
        "metrics": metrics, "device": device,
    }
    if trace and ctx.profile is not None:
        result["breakdown"] = ctx.profile["breakdown"]
    result["compared"] = checks.compared  # last: each number and its limit
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="rehearsal size, any backend; correct stays false")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), rehearse=args.rehearse)
    if args.rehearse:
        result["correct"] = False  # a rehearsal measures nothing
    for what, row in result["compared"].items():
        print(json.dumps({"compared": what, **row}, default=str),
              file=sys.stderr)
    sys.stderr.flush()
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
