import pytest

from spark_bam_tpu.core.config import Config, format_bytes, parse_bytes
from spark_bam_tpu.core.pos import Pos, parse_pos
from spark_bam_tpu.core.ranges import ByteRange, RangeSet, parse_range, parse_ranges


def test_pos_htsjdk_roundtrip():
    p = Pos(239479, 311)
    assert Pos.from_htsjdk(p.to_htsjdk()) == p
    assert p.to_htsjdk() == (239479 << 16) | 311
    assert str(p) == "239479:311"
    assert parse_pos("239479:311") == p
    assert parse_pos("100") == Pos(100, 0)


def test_pos_distance():
    # Intra-block offsets scale by the estimated compression ratio (default 3.0).
    assert Pos(1000, 300).distance(Pos(1000, 0)) == 100
    assert Pos(0, 0).distance(Pos(1000, 0)) == 0  # clamped at 0


def test_parse_bytes():
    assert parse_bytes("2MB") == 2 << 20
    assert parse_bytes("32m") == 32 << 20
    assert parse_bytes("100KB") == 100 << 10
    assert parse_bytes("1g") == 1 << 30
    assert parse_bytes(12345) == 12345
    assert parse_bytes("7") == 7
    assert format_bytes(2 << 20) == "2MB"


def test_ranges_grammar():
    assert parse_range("10-20") == ByteRange(10, 20)
    assert parse_range("10+5") == ByteRange(10, 15)
    assert parse_range("7") == ByteRange(7, 8)
    assert parse_range("1k-2k") == ByteRange(1024, 2048)
    rs = parse_ranges("0-10,20+5,100")
    assert 5 in rs and 22 in rs and 100 in rs
    assert 15 not in rs and 101 not in rs
    assert rs.overlaps(8, 12) and not rs.overlaps(12, 18)
    # Adjacent/overlapping ranges merge.
    merged = RangeSet([ByteRange(0, 10), ByteRange(5, 15)])
    assert merged.ranges == (ByteRange(0, 15),)
    assert parse_ranges(None) is None and parse_ranges("  ") is None


def test_config_surface():
    c = Config()
    assert c.bgzf_blocks_to_check == 5
    assert c.reads_to_check == 10
    assert c.max_read_size == 10_000_000
    assert c.estimated_compression_ratio == 3.0
    c2 = Config.from_dict({"spark.bam.reads_to_check": 3, "split_size": "4MB"})
    assert c2.reads_to_check == 3
    assert c2.split_size == 4 << 20
    c3 = Config.from_env({"SPARK_BAM_CHECKER": "full"})
    assert c3.checker == "full"


_CACHE_PROBE = (
    "import jax\n"
    "from spark_bam_tpu.core.platform import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _cache_probe(env_dir):
    """(returned dir, jax's configured dir) from a fresh process."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, text=True,
        capture_output=True, timeout=120, check=True,
        cwd=Path(__file__).resolve().parent.parent,
    )
    return out.stdout.strip().splitlines()[-2:]


def test_compile_cache_env_var_wins_and_nothing_is_set_in_code(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is jax's own: with it set the program
    sets no directory, and jax's config reads the variable."""
    returned, configured = _cache_probe(tmp_path / "x")
    assert returned == configured == str(tmp_path / "x")
    assert not (tmp_path / "x").exists()  # placed by jax on first write


def test_compile_cache_default_is_fixed_inside_the_checkout():
    """Unset, every process lands on the same in-checkout directory (the
    path is part of the cache key, so it must not move)."""
    from pathlib import Path

    from spark_bam_tpu.core.platform import CHECKOUT_JAX_CACHE

    first, second = _cache_probe(None), _cache_probe(None)
    assert first == second == [str(CHECKOUT_JAX_CACHE)] * 2
    repo = Path(__file__).resolve().parent.parent
    assert CHECKOUT_JAX_CACHE == repo / ".jax_cache"


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_count_without_native_library_inflates_with_zlib(
        backend, monkeypatch, tmp_path):
    """What a process sees when the native library did not build: the
    count's windows are inflated by zlib, on a TPU as on the CPU, and the
    count is the NumPy engine's."""
    import jax

    from bam_factories import random_bam
    from spark_bam_tpu import obs
    from spark_bam_tpu.native import build
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    path = tmp_path / "small.bam"
    random_bam(path, seed=7)
    want = StreamChecker(path, Config(), use_device=False).count_reads()
    monkeypatch.setattr(build, "_LIB_CACHE", [None])
    monkeypatch.setattr(build, "_LOAD_INFO", {"error": "g++ rc=1: boom"})
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    obs.shutdown()
    reg = obs.configure()
    try:
        got = StreamChecker(path, Config()).count_reads()
        engines = {e["attrs"]["engine"] for e in reg.events()
                   if e["name"] == "inflate.window"}
    finally:
        obs.shutdown()
    assert got == want > 0 and engines == {"zlib"}


def test_config_env_skips_cloud_namespaces(monkeypatch):
    """SPARK_BAM_GS_* / SPARK_BAM_S3_* / SPARK_BAM_PROFILE_* are backend
    and profiler namespaces, not Config knobs — from_env must skip them
    instead of raising (a set SPARK_BAM_PROFILE_DIR used to break every
    CLI invocation that called Config.from_env)."""
    from spark_bam_tpu.core.config import Config

    monkeypatch.setenv("SPARK_BAM_GS_ENDPOINT", "http://localhost:1")
    monkeypatch.setenv("SPARK_BAM_GS_TOKEN", "tok")
    monkeypatch.setenv("SPARK_BAM_S3_ENDPOINT", "http://localhost:2")
    monkeypatch.setenv("SPARK_BAM_PROFILE_DIR", "/tmp/prof")
    monkeypatch.setenv("SPARK_BAM_READS_TO_CHECK", "7")
    cfg = Config.from_env()
    assert cfg.reads_to_check == 7  # real knobs still apply


def test_config_unknown_key_still_rejected():
    import pytest

    from spark_bam_tpu.core.config import Config

    with pytest.raises(KeyError):
        Config.from_dict({"spark.bam.not.a.knob": 1})
