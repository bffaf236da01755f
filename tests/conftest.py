"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware; set the XLA flags before jax is imported
anywhere.
"""

import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

# Force, not setdefault: tests want the deterministic CPU backend with 8
# virtual devices so multi-chip sharding is exercised whatever the
# environment names. The chip is reached only through chip_smoke.py.
from spark_bam_tpu.core.platform import (  # noqa: E402
    enable_compile_cache,
    force_cpu_devices,
)

force_cpu_devices(8)
# Persistent XLA compile cache: repeat test sessions skip kernel recompiles.
enable_compile_cache()

import pytest  # noqa: E402

# Reference test fixtures (small real BAMs + golden sidecars). Read-only.
FIXTURES = Path("/root/reference/test_bams/src/main/resources")


def fixture(name: str) -> Path:
    return FIXTURES / name


@pytest.fixture(scope="session")
def bam1():
    p = fixture("1.bam")
    if not p.exists():
        pytest.skip("reference fixtures unavailable")
    return p


@pytest.fixture(scope="session")
def bam2():
    p = fixture("2.bam")
    if not p.exists():
        pytest.skip("reference fixtures unavailable")
    return p


@pytest.fixture(scope="session")
def sam2():
    p = fixture("2.sam")
    if not p.exists():
        pytest.skip("reference fixtures unavailable")
    return p


@pytest.fixture(scope="session")
def bam5k():
    p = fixture("5k.bam")
    if not p.exists():
        pytest.skip("reference fixtures unavailable")
    return p
