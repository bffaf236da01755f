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
