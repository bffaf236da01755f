"""Tests of the benchmark's own code. Run from the repo's root:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture(scope="session")
def benchmark_json() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def config_of(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def generate(name: str, seed: int, path, size: int | None = None):
    """``(index, config)`` of configuration ``name`` written to ``path`` at
    ``size`` bytes (its rehearsal size by default)."""
    import importlib

    config = config_of(name)
    gen = importlib.import_module(f"bench.generators.{config['generator']}")
    size = size or config["rehearsal"]["uncompressed_bytes"]
    return gen.generate(config["params"], seed, size, path), config


def held_entry(bm: dict, cell_name: str, config: str, traffic: str,
               chips: int, rate: str = "scan_rate") -> set:
    """What must hold of a cell's entry whatever later PRs add to the
    benchmark: its configuration, traffic and chips; at most half the cells
    on four chips; its rate (``scan_rate``, or the long-read cells'
    ``scan_rate.longread``) reported; every per-layer metric that lists
    the cell has a file that names the cell and moves a metric the cell
    reports. Returns those metrics' names."""
    cell = next(w for w in bm["workloads"] if w["name"] == cell_name)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        config, traffic, chips)
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(bm["workloads"]) // 2)
    reported = {m["name"] for m in bm["end_to_end"]
                if cell_name in m.get("workloads", [cell_name])}
    assert {rate, "setup_s"} <= reported
    mine = set()
    for m in bm["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        spec = json.loads((ROOT / "bench" / "layer_metrics"
                           / f"{m['name']}.json").read_text())
        assert cell_name in spec["cells"], m["name"]
        assert m["moves"] in reported, m["name"]
        mine.add(m["name"])
    return mine
