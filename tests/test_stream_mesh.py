"""Mesh-sharded streaming count-reads (parallel/stream_mesh.py) on the
virtual 8-device CPU mesh: the single-host multi-chip production path must
agree with the single-device streaming engine and the pinned fixture
counts (2.bam = 2500 reads, 1.bam = 4917 — reference
docs/command-line.md:46-53, cli golden output/check-bam/1.bam)."""

import jax
import numpy as np

from spark_bam_tpu.core.config import Config
from spark_bam_tpu.parallel.mesh import make_mesh
from spark_bam_tpu.parallel.stream_mesh import count_reads_sharded
from spark_bam_tpu.tpu.stream_check import StreamChecker

from conftest import FIXTURES

BAM1 = FIXTURES / "1.bam"
BAM2 = FIXTURES / "2.bam"


def _mesh():
    return make_mesh(jax.devices("cpu")[:8])


def test_sharded_count_matches_fixture_and_single_device():
    mesh = _mesh()
    # 128 KiB windows over the ~1.6 MB flat stream: ≥2 sharded steps with a
    # partial final batch, plus carry/halo seams between every row.
    got = count_reads_sharded(
        BAM2, Config(), mesh=mesh,
        window_uncompressed=128 << 10, halo=32 << 10,
    )
    assert got == 2500
    single = StreamChecker(
        BAM2, Config(), window_uncompressed=128 << 10, halo=32 << 10,
    ).count_reads()
    assert got == single


def test_sharded_count_bam1():
    got = count_reads_sharded(
        BAM1, Config(), mesh=_mesh(),
        window_uncompressed=256 << 10, halo=64 << 10,
    )
    assert got == 4917


def test_sharded_count_single_batch_small_file():
    # Whole file fits one window: one step, one live row, 7 zero rows.
    got = count_reads_sharded(
        BAM2, Config(), mesh=_mesh(),
        window_uncompressed=4 << 20, halo=256 << 10,
    )
    assert got == 2500


import pytest


@pytest.fixture(scope="module")
def longread_bam(tmp_path_factory):
    """A small long-read BAM whose ultra records (~2.25 MB encoded) outrun
    any sub-MB halo even after the engine's block-granular halo extension —
    the escape-forcing input (2.bam's ~150 B records can't force escapes
    any more: one 64 KiB halo block always covers their chains)."""
    from spark_bam_tpu.bam.index_records import index_records
    from spark_bam_tpu.benchmarks.synth import synth_longread_bam

    p = tmp_path_factory.mktemp("lr") / "lr.bam"
    manifest = synth_longread_bam(
        p, 2 << 20, read_lens=(30_000, 60_000), reads_per_rep=6,
        ultra_seq_len=1_500_000,
    )
    index_records(p)
    return str(p), manifest


def test_sharded_count_escape_resolves_exact(longread_bam):
    # A 256 KiB halo is far shorter than an ultra record's span, so owned
    # positions near every seam escape; escaped steps re-derive exactly
    # on host (the escape-localized patch) — or, without the native
    # library, through the whole-file fallback — and the count must land
    # exactly either way.
    path, manifest = longread_bam
    stats = {}
    got = count_reads_sharded(
        path, Config(), mesh=_mesh(),
        window_uncompressed=1 << 20, halo=256 << 10, stats_out=stats,
    )
    assert got == manifest["reads"]
    assert stats["escapes"] > 0
    assert stats["fallback"] or stats["patched_steps"] > 0


def test_check_bam_sharded_bam2_all_match():
    # Reference: eager vs indexed on 2.bam has no miscalls; 1,606,522
    # uncompressed positions, 2,500 records (docs/command-line.md:46-53).
    from spark_bam_tpu.parallel.stream_mesh import check_bam_sharded

    stats = check_bam_sharded(
        BAM2, Config(), mesh=_mesh(),
        window_uncompressed=128 << 10, halo=32 << 10,
    )
    assert not len(stats.pop("false_positive_positions"))
    assert not len(stats.pop("false_negative_positions"))
    assert stats == {
        "true_positives": 2500,
        "false_positives": 0,
        "false_negatives": 0,
        "true_negatives": 1_606_522 - 2500,
        "positions": 1_606_522,
        "devices": 8,
    }


def test_check_bam_sharded_bam1():
    # 1.bam: 1,608,257 positions, 4,917 reads, and the eager checker has
    # no known miscalls vs the indexed truth (the 5 documented FPs are
    # hadoop-bam's, not ours — cli golden output/check-bam/1.bam).
    from spark_bam_tpu.parallel.stream_mesh import check_bam_sharded

    stats = check_bam_sharded(
        BAM1, Config(), mesh=_mesh(),
        window_uncompressed=256 << 10, halo=64 << 10,
    )
    assert stats["true_positives"] == 4917
    assert stats["false_positives"] == 0
    assert stats["false_negatives"] == 0
    assert stats["positions"] == 1_608_257


def test_check_bam_sharded_escape_patch_matches_device_pass(longread_bam):
    # A halo too small for the ultra records forces escapes; the
    # escape-localized host patch (or, without the native library, the
    # whole-file set-arithmetic fallback) must produce the same matrix
    # the device pass produces with a halo that covers every chain.
    from spark_bam_tpu.native.build import load_native
    from spark_bam_tpu.parallel.stream_mesh import check_bam_sharded

    path, _ = longread_bam
    via_escape = check_bam_sharded(
        path, Config(), mesh=_mesh(),
        window_uncompressed=1 << 20, halo=256 << 10,
    )
    via_device = check_bam_sharded(
        path, Config(), mesh=_mesh(),
        window_uncompressed=8 << 20, halo=4 << 20,
    )
    # With the native library the escaped steps patch on-mesh (devices
    # stays 8); without it the whole-file single-device fallback runs.
    expected_devices = 8 if load_native() is not None else 1
    assert via_escape.pop("devices") == expected_devices
    assert via_device.pop("devices") == 8
    for key in ("false_positive_positions", "false_negative_positions"):
        assert np.array_equal(via_escape.pop(key), via_device.pop(key))
    assert via_escape == via_device


def test_progress_callback_fires():
    seen = []
    count_reads_sharded(
        BAM2, Config(), mesh=_mesh(),
        window_uncompressed=128 << 10, halo=32 << 10,
        progress=lambda s, d, t: seen.append((s, d, t)),
    )
    assert seen and seen[-1][0] == len(seen)
    assert seen[-1][2] == seen[-1][1]  # final flush covers the whole file


def test_stats_out_reports_fallback():
    stats = {}
    count_reads_sharded(
        BAM2, Config(), mesh=_mesh(),
        window_uncompressed=128 << 10, halo=32 << 10, stats_out=stats,
    )
    assert stats["fallback"] is False and stats["steps"] > 0
    assert stats["rows"] > 1  # multiple block groups actually sharded


def test_process_slicing_covers_every_group_once():
    """The multi-host row split: across processes, each global row index
    maps to exactly one process's slice, padding rows own nothing, and the
    per-process step counts are identical — the collective's shape
    contract. (The cross-process psum itself is proven by
    tests/test_multihost.py's 2-process run through this same engine.)"""
    from spark_bam_tpu.parallel.stream_mesh import _ShardedStream

    st_all = _ShardedStream(
        BAM2, Config(), _mesh(), 128 << 10, 32 << 10, None
    )
    owned = []
    for pid in range(2):
        st = _ShardedStream(
            BAM2, Config(), _mesh(), 128 << 10, 32 << 10, None,
            num_processes=2, process_id=pid,
        )
        assert st.per_proc == st_all.per_proc // 2 or st.per_proc * 2 == -(
            -len(st.groups) // st.n_global
        ) * st.n_global
        for local in range(st.per_proc):
            g = pid * st.per_proc + local
            if g < len(st.groups):
                owned.append(g)
    assert sorted(owned) == list(range(len(st_all.groups)))


def test_host_shard_plan_partitions_exactly():
    """The scheduler-facing locality surface: owned group ranges partition
    the file, compressed ranges tile it with only halo-sized seam overlap,
    and the arithmetic matches the engine's own row slicing."""
    from spark_bam_tpu.parallel.stream_mesh import (
        _ShardedStream,
        host_shard_plan,
    )
    from spark_bam_tpu.core.channel import path_size

    plan = host_shard_plan(
        BAM2, num_hosts=2, devices_per_host=4,
        window_uncompressed=128 << 10, halo=32 << 10,
    )
    assert [p["host"] for p in plan] == [0, 1]
    st = _ShardedStream(
        BAM2, Config(), _mesh(), 128 << 10, 32 << 10, None,
        num_processes=2, process_id=0,
    )
    # Group ranges: contiguous, non-overlapping, covering every group.
    assert plan[0]["groups"][0] == 0
    assert plan[0]["groups"][1] == plan[1]["groups"][0] == st.per_proc
    assert plan[1]["groups"][1] == len(st.groups)
    assert sum(p["uncompressed"] for p in plan) == st.total
    # Compressed ranges: within the file; host 0's halo overlap reaches
    # into host 1's range but no further than halo + one block.
    size = path_size(BAM2)
    for p in plan:
        lo, hi = p["compressed_range"]
        assert 0 <= lo < hi <= size
    assert plan[0]["compressed_range"][1] > plan[1]["compressed_range"][0]


def test_locality_provider_hook():
    """SplitRDD.preferredLocations analog: a registered provider surfaces
    hosts per split; unregistered means 'anywhere'."""
    from spark_bam_tpu.load.splits import (
        file_splits,
        preferred_hosts,
        set_locality_provider,
    )

    splits = file_splits(BAM2, 256 << 10)
    assert preferred_hosts(splits[0]) == []
    try:
        set_locality_provider(
            lambda path, start, end: [f"host{start // (256 << 10) % 2}"]
        )
        assert preferred_hosts(splits[0]) == ["host0"]
        assert preferred_hosts(splits[1]) == ["host1"]
    finally:
        set_locality_provider(None)
    assert preferred_hosts(splits[0]) == []


def test_full_check_sharded_matches_streaming():
    """The third mesh workload: full-check aggregations across the mesh
    must equal the single-device streaming summary exactly — per-flag
    totals, considered count, and every critical/two-check site+mask."""
    import numpy as np

    from spark_bam_tpu.parallel.stream_mesh import full_check_summary_sharded
    from spark_bam_tpu.tpu.stream_check import full_check_summary_streaming

    a = full_check_summary_sharded(
        BAM2, Config(), mesh=_mesh(),
        window_uncompressed=256 << 10, halo=64 << 10,
    )
    b = full_check_summary_streaming(
        BAM2, Config(), window_uncompressed=256 << 10, halo=64 << 10,
    )
    assert a.pop("devices") == 8
    assert a["per_flag"] == b["per_flag"]
    assert a["considered"] == b["considered"]
    assert a["positions"] == b["positions"]
    for key in (
        "critical_positions", "critical_masks",
        "two_check_positions", "two_check_masks",
    ):
        np.testing.assert_array_equal(a[key], b[key])


def test_full_check_sharded_defer_patches_exact(longread_bam):
    """Ultra records force deferred lanes: the deferred steps' rows
    re-derive exactly on host (escape-localized patch — the mesh pass
    stays on 8 devices) and every aggregation still matches a direct
    streaming run, sites and masks included."""
    import numpy as np

    from spark_bam_tpu.parallel.stream_mesh import full_check_summary_sharded
    from spark_bam_tpu.tpu.stream_check import full_check_summary_streaming

    path, _ = longread_bam
    stats = {}
    a = full_check_summary_sharded(
        path, Config(), mesh=_mesh(),
        window_uncompressed=1 << 20, halo=256 << 10, stats_out=stats,
    )
    assert a.pop("devices") == 8
    assert stats["patched_steps"] > 0 and not stats["fallback"], stats
    b = full_check_summary_streaming(
        path, Config(), window_uncompressed=1 << 20, halo=256 << 10,
    )
    assert a["per_flag"] == b["per_flag"]
    assert a["considered"] == b["considered"]
    # Sites may arrive in different orders (patched rows vs deferral
    # re-emissions); compare as position-sorted (position, mask) pairs.
    for pk, mk in (
        ("critical_positions", "critical_masks"),
        ("two_check_positions", "two_check_masks"),
    ):
        ap, am = np.asarray(a[pk]), np.asarray(a[mk])
        bp, bm = np.asarray(b[pk]), np.asarray(b[mk])
        ao, bo = np.argsort(ap), np.argsort(bp)
        np.testing.assert_array_equal(ap[ao], bp[bo])
        np.testing.assert_array_equal(am[ao], bm[bo])


def test_full_check_sharded_compaction_overflow_falls_back():
    """A 16-site compaction buffer overflows on 2.bam's thousands of
    two-check sites: the mismatch must be detected and the exact fallback
    must deliver the full site lists anyway."""
    import numpy as np

    from spark_bam_tpu.parallel.stream_mesh import full_check_summary_sharded
    from spark_bam_tpu.tpu.stream_check import full_check_summary_streaming

    a = full_check_summary_sharded(
        BAM2, Config(), mesh=_mesh(),
        window_uncompressed=256 << 10, halo=64 << 10, k_positions=16,
    )
    assert a.pop("devices") == 1  # overflow → exact fallback
    b = full_check_summary_streaming(
        BAM2, Config(), window_uncompressed=256 << 10, halo=64 << 10,
    )
    np.testing.assert_array_equal(
        a["two_check_positions"], b["two_check_positions"]
    )


def test_full_check_sharded_matches_streaming_fuzz(tmp_path):
    """Randomized differential for the mesh full-check: generated BAMs
    (varied record shapes, unmapped rates, block sizes) must produce
    identical aggregations through the sharded and single-device paths —
    catches derivation edges (bare-EOF rule, considered arithmetic) the
    fixtures might not cover."""
    import numpy as np

    from bam_factories import random_bam
    from spark_bam_tpu.parallel.stream_mesh import full_check_summary_sharded
    from spark_bam_tpu.tpu.stream_check import full_check_summary_streaming

    for seed in (3, 11):
        p = tmp_path / f"fz{seed}.bam"
        random_bam(
            p, seed=seed, n_records=(200, 400), read_len=(10, 6000),
            mapped_rate=0.7,
        )
        a = full_check_summary_sharded(
            str(p), Config(), mesh=_mesh(),
            window_uncompressed=128 << 10, halo=32 << 10,
        )
        b = full_check_summary_streaming(
            str(p), Config(), window_uncompressed=128 << 10, halo=32 << 10,
        )
        a.pop("devices")
        assert a["per_flag"] == b["per_flag"], seed
        assert a["considered"] == b["considered"], seed
        assert a["positions"] == b["positions"], seed
        for key in (
            "critical_positions", "critical_masks",
            "two_check_positions", "two_check_masks",
        ):
            np.testing.assert_array_equal(a[key], b[key], err_msg=str(seed))


def test_host_shard_plan_four_hosts_and_tiny_file():
    """Plan arithmetic edges: more host slots than groups leaves trailing
    hosts empty (never mis-assigned), and every owned group appears in
    exactly one host's range."""
    from spark_bam_tpu.parallel.stream_mesh import host_shard_plan

    plan = host_shard_plan(
        BAM2, num_hosts=4, devices_per_host=2,
        window_uncompressed=512 << 10, halo=64 << 10,
    )
    assert [p["host"] for p in plan] == [0, 1, 2, 3]
    covered = []
    for p in plan:
        g0, g1 = p["groups"]
        covered.extend(range(g0, g1))
        if g0 == g1:
            assert p["uncompressed"] == 0 and p["compressed_range"] == (0, 0)
    assert covered == sorted(set(covered))  # disjoint, ordered
    total = sum(p["uncompressed"] for p in plan)
    from spark_bam_tpu.parallel.stream_mesh import _ShardedStream

    st = _ShardedStream(BAM2, Config(), _mesh(), 512 << 10, 64 << 10, None)
    assert total == st.total


def test_mostly_dirty_guard_thresholds():
    """The escape-everywhere guard: all-dirty prefixes trip at 4 steps; a
    lone clean step no longer disables it past 8 steps (>=90% dirty)."""
    from spark_bam_tpu.parallel.stream_mesh import _mostly_dirty

    assert not _mostly_dirty([1, 2, 3], 3)          # too early
    assert _mostly_dirty([1, 2, 3, 4], 4)           # all dirty at 4
    assert not _mostly_dirty([1, 2, 3], 4)          # one clean step at 4
    assert not _mostly_dirty([1] * 6, 7)            # 86% at 7: below bar
    assert _mostly_dirty(list(range(9)), 9)         # 100% at 9
    assert _mostly_dirty(list(range(9)), 10)        # 90% at 10
    assert not _mostly_dirty(list(range(8)), 10)    # 80% at 10
