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


class BamCase:
    """A small BAM with its ``.records`` sidecar and what is known of it
    beforehand: the reference's ``2.bam`` with its golden numbers, or a
    seeded generated file (``tests/bam_factories.random_bam``), whose
    numbers the tests take from the sequential codec. ``contig`` is the
    first contig's name."""

    def __init__(self, path, contig: str, records=None, on_contig=None):
        self.path = path
        self.contig = contig
        self.records = records      # golden record count, or None
        self.on_contig = on_contig  # golden rows on ``contig``:0-100000


@pytest.fixture(scope="session", params=["reference", "generated"])
def bam2_like(request, tmp_path_factory):
    """The cases that were written against ``2.bam`` run on it where the
    reference's fixtures are installed (elsewhere that parameter skips) and
    on a generated file everywhere."""
    if request.param == "reference":
        if not fixture("2.bam").exists():
            pytest.skip("reference fixtures unavailable")
        return BamCase(fixture("2.bam"), "1", 2500, 2450)
    from tests.bam_factories import random_bam

    path = tmp_path_factory.mktemp("bam2_like") / "generated.bam"
    random_bam(path, 2, contigs=(("chr1", 5_000_000), ("chr2", 3_000_000)),
               n_records=(600, 700), read_len=(30, 400), dup_rate=0.05,
               index=True)
    return BamCase(path, "chr1")
