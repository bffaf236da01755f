"""Candidate-funnel tier-1 suite.

Two claims keep the funnel honest (docs/design.md, "Candidate funnel"):
the stage-0 prefilter is a provable superset filter (every full-pass
survivor passes it), and every funnel projection is verdict-identical to
the full pass — on factory corpora, on seeded decode-fuzz mutants, and on
adversarial byte soup. Everything here runs on the virtual CPU mesh.
"""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

from spark_bam_tpu.bam.header import contig_lengths
from spark_bam_tpu.bgzf.flat import flatten_file
from spark_bam_tpu.core.config import Config
from spark_bam_tpu.tpu import checker as ck
from tests.bam_factories import random_bam

W = 256 << 10

PARITY_KEYS = ("verdict", "escaped", "reads_before", "reads_parsed")


def _window_of(data, w=W):
    padded = np.zeros(w + ck.PAD, dtype=np.uint8)
    n = min(len(data), w)
    padded[:n] = np.asarray(data)[:n]
    return jnp.asarray(padded), jnp.int32(n)


def _lens_of(path):
    arr = np.array(contig_lengths(path).lengths_list(), dtype=np.int32)
    lens = np.zeros(1024, dtype=np.int32)
    lens[: len(arr)] = arr
    return jnp.asarray(lens), jnp.int32(len(arr))


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("funnel")
    paths = []
    for i, kw in enumerate((
        dict(n_records=(150, 400)),
        dict(n_records=(80, 200), mapped_rate=0.3, dup_rate=0.2),
    )):
        p = tmp / f"c{i}.bam"
        random_bam(p, seed=100 + i, **kw)
        paths.append(p)
    return paths


@pytest.fixture(scope="module")
def kernels():
    return (
        ck.make_check_window(W, 10, funnel=True),
        ck.make_check_window(W, 10, funnel=False),
    )


def test_check_window_parity_corpora(corpus, kernels):
    """Funnel on/off: identical verdicts (hence identical record starts),
    escapes, and read counts at every position, both at_eof values."""
    on, off = kernels
    for p in corpus:
        pd, n = _window_of(flatten_file(p).data)
        ld, nc = _lens_of(p)
        for at_eof in (True, False):
            a = on(pd, ld, nc, n, jnp.bool_(at_eof))
            b = off(pd, ld, nc, n, jnp.bool_(at_eof))
            for k in PARITY_KEYS:
                np.testing.assert_array_equal(
                    np.asarray(a[k]), np.asarray(b[k]),
                    err_msg=f"{p.name} at_eof={at_eof} key={k}",
                )
            np.testing.assert_array_equal(
                np.flatnonzero(np.asarray(a["verdict"])),
                np.flatnonzero(np.asarray(b["verdict"])),
            )


def test_count_window_parity(corpus):
    on = ck.make_count_window(W, 10, funnel=True)
    off = ck.make_count_window(W, 10, funnel=False)
    p = corpus[0]
    pd, n = _window_of(flatten_file(p).data)
    ld, nc = _lens_of(p)
    spans = ((0, int(n)), (1000, int(n) // 2))
    for at_eof in (True, False):
        for lo, own in spans:
            a = on(pd, ld, nc, n, jnp.bool_(at_eof), jnp.int32(lo), jnp.int32(own))
            b = off(pd, ld, nc, n, jnp.bool_(at_eof), jnp.int32(lo), jnp.int32(own))
            assert int(a["count"]) == int(b["count"]), (at_eof, lo, own)
            assert int(a["esc_count"]) == int(b["esc_count"]), (at_eof, lo, own)


def test_fuzz_mutant_parity(kernels, tmp_path):
    """Seeded decode-fuzz BAM mutants: the funnel must never flip a verdict
    on corrupted input (where the prefilter's screening earns its keep)."""
    from spark_bam_tpu.tools.fuzz_decode import _mutants_for, _Rng

    on, off = kernels
    rng = _Rng(5)
    checked = 0
    for i, blob in enumerate(_mutants_for("bam", tmp_path, rng, 12)):
        p = tmp_path / f"m{i}.bam"
        p.write_bytes(blob)
        try:
            data = flatten_file(p).data
            ld, nc = _lens_of(p)
        except Exception:
            continue  # mutant broke the header/BGZF layer: nothing to scan
        pd, n = _window_of(data)
        for at_eof in (True, False):
            a = on(pd, ld, nc, n, jnp.bool_(at_eof))
            b = off(pd, ld, nc, n, jnp.bool_(at_eof))
            for k in PARITY_KEYS:
                np.testing.assert_array_equal(
                    np.asarray(a[k]), np.asarray(b[k]),
                    err_msg=f"mutant {i} at_eof={at_eof} key={k}",
                )
        checked += 1
    assert checked >= 5, f"only {checked} mutants survived decode"


def _adversarial(kind: str, corpus, w: int):
    """Byte soup, or a corpus window with 1% of its bytes flipped."""
    rng = np.random.default_rng(11)
    if kind == "soup":
        return rng.integers(0, 256, size=w, dtype=np.uint8)
    data = np.array(flatten_file(corpus[0]).data[:w], dtype=np.uint8,
                    copy=True)
    flips = rng.integers(0, len(data), size=max(1, len(data) // 100))
    data[flips] ^= rng.integers(1, 256, size=len(flips)).astype(np.uint8)
    return data


def _assert_superset(pd, ld, nc, n):
    """Every prefilter bit must also be set by the full pass — hence
    full-pass survivors (F == 0) are a subset of prefilter survivors."""
    pre = np.asarray(ck._prefilter_flags(pd, ld, nc, n))
    full = np.asarray(ck._compute_flags(pd, ld, nc, n))
    stray = pre & ~full
    assert not stray.any(), (
        f"prefilter set bits the full pass did not at "
        f"{np.flatnonzero(stray)[:5]}"
    )
    assert not ((full == 0) & (pre != 0)).any()


def test_superset_on_corpus(corpus):
    for p in corpus:
        pd, n = _window_of(flatten_file(p).data)
        ld, nc = _lens_of(p)
        _assert_superset(pd, ld, nc, n)


def test_superset_on_adversarial_windows(corpus):
    """Byte soup and bit-flipped corpus windows: the superset property is
    structural (prefilter bits are a subset of full-pass bits at every
    position), so it must hold on arbitrary garbage, not just valid BAM."""
    ld, nc = _lens_of(corpus[0])
    for kind in ("soup", "bit-flips"):
        pd, n = _window_of(_adversarial(kind, corpus, W))
        _assert_superset(pd, ld, nc, n)


def test_stream_record_starts_parity(corpus):
    """Whole-stream projection: funnel on vs off yield byte-identical
    record-start positions, and only the funnelled run reports stats."""
    from spark_bam_tpu.tpu.stream_check import StreamChecker

    p = corpus[0]

    def starts(mode):
        checker = StreamChecker(
            p, Config(funnel=mode), window_uncompressed=128 << 10,
            halo=32 << 10,
        )
        got = np.sort(np.concatenate(
            list(checker.record_starts()) or [np.array([], dtype=np.int64)]
        ))
        return got, checker.funnel_stats

    s_on, stats_on = starts("on")
    s_off, stats_off = starts("off")
    np.testing.assert_array_equal(s_on, s_off)
    assert len(s_on) > 0
    assert stats_off is None
    assert stats_on is not None and stats_on["screened"] > 0
    assert stats_on["survivors"] <= stats_on["screened"]


def test_config_funnel_knobs():
    assert Config().funnel == "auto"
    assert Config().funnel_enabled() is True
    assert Config().funnel_enabled(full_masks=True) is False
    assert Config(funnel="off").funnel_enabled() is False
    assert Config(funnel="on").funnel_enabled() is True
    # Explicit "on" still cannot apply where full flag masks are required.
    assert Config(funnel="on").funnel_enabled(full_masks=True) is False
    with pytest.raises(ValueError, match="funnel"):
        Config(funnel="bogus").funnel_enabled()


def test_config_funnel_env_and_dict():
    cfg = Config.from_env({"SPARK_BAM_FUNNEL": "off"})
    assert cfg.funnel == "off"
    cfg = Config.from_dict({"spark.bam.funnel": "on"})
    assert cfg.funnel == "on"


def test_config_flush_every_and_ring_depth():
    kw = 1 << 20
    auto = (1 << 30) // kw
    assert Config().flush_every_for(kw) == auto
    assert Config.from_dict({"spark.bam.flush_every": "auto"}).flush_every is None
    assert Config.from_dict({"spark.bam.flush_every": "8"}).flush_every == 8
    assert Config(flush_every=8).flush_every_for(kw) == 8
    # The int32-overflow cap always wins over a larger operator setting.
    assert Config(flush_every=10 * auto).flush_every_for(kw) == auto
    assert Config(flush_every=0).flush_every_for(kw) == 1
    assert Config(ring_depth=4).ring_depth == 4
    cfg = Config.from_env({"SPARK_BAM_RING_DEPTH": "3"})
    assert cfg.ring_depth == 3


# ------------------------------------------------------------------------
# The funnel's lane stage, sized by its window's survivors (PR 30 for the
# count, PR 34 for ``check_window``: one stage, two consumers). One reference
# for every case: the stage the package had before, ONE compaction, deep
# check and walk at the window's whole capacity (``w // 32`` lanes), kept
# here from the package's own pieces. Its ``check_window`` is what the
# blocked one must return, array for array, and its owned verdicts and
# escapes ARE the count's two scalars.

W1 = 1 << 20                      # capacity 32,768 lanes: blocks are distinct
CAPACITY = ck.lane_capacity(W1)
# Forced lanes a block, 64 … 1 blocks: the widths the package runs (1,024
# at a served row, ``LANE_BLOCK`` = 2,048 at the 32 MiB windows) among them.
BLOCKS = (512, 1024, ck.LANE_BLOCK, 4096, CAPACITY)

#: Every array ``check_window`` returns (``lanes`` is held to the rule).
CHECK_KEYS = ("verdict", "fail_mask", "reads_parsed", "reads_before",
              "exact", "escaped", "survivors")


def _lanes_rule(survivors: int, block: int, capacity: int = CAPACITY) -> int:
    """Lanes the stage runs for ``survivors``: whole blocks, at most the
    capacity (a window over it runs every block and escapes whole)."""
    return min(-(-survivors // block), -(-capacity // block)) * block


def _full_capacity_check_window(padded, lengths, num_contigs, n, at_eof):
    """``check_window(funnel=True)`` with ONE lane stage of the window's
    whole capacity: no blocks, no loop."""
    w = padded.shape[0] - ck.PAD
    S = ck._flag_stage(padded, lengths, num_contigs, n, at_eof, True)
    capacity = ck.lane_capacity(w)
    table = ck._rank_table(S["survivor"])
    cand = ck._ranked_positions(table, jnp.arange(capacity, dtype=jnp.int32))
    live = cand >= 0
    tgt0, (F_cand, _rem, _b_end) = ck._deep_lanes(
        padded, S["U"], lengths, num_contigs, n,
        ck._funnel_tables(padded, n), cand, live)
    F_deep = jnp.zeros(w + 1, dtype=jnp.int32).at[tgt0].set(
        F_cand, mode="drop")[:w]

    def flags_lookup(pi):
        # Two arrays, as the package looked a flag up until PR 36: what its
        # ONE merged array (``_lane_flags``) must read at every position.
        pre = jnp.take(S["F"], pi, mode="clip")
        return jnp.where(pre == 0, jnp.take(F_deep, pi, mode="clip"), pre)

    # ... and every one of the ten steps looks its position up, the first
    # too (the package's takes what pass 1 read at the lane's position).
    lanes = ck._walk_lanes(
        cand, live, flags_lookup, S["misc_at"], n, at_eof, w, 10,
        unroll=True)
    return ck._scatter_lanes({
        "survivor": S["survivor"], "res0": S["res0"],
        "fail_mask0": S["fail_mask0"], "inexact0": S["inexact0"],
        "cand": cand, **lanes,
        "overflow": table.n_set > capacity, "n_survivors": table.n_set,
        "lanes": jnp.int32(capacity),
    }, w)


@pytest.fixture(scope="module")
def full_stage():
    import jax

    return jax.jit(_full_capacity_check_window)


@pytest.fixture(scope="module")
def blocked():
    """``block -> jitted _count_funnel``: the count's program with the lane
    stage forced to ``block`` lanes a block."""
    import functools

    import jax

    cache = {}

    def get(block):
        if block not in cache:
            cache[block] = jax.jit(functools.partial(
                ck._count_funnel, reads_to_check=10, block=block))
        return cache[block]

    return get


@pytest.fixture(scope="module")
def blocked_check():
    """``block -> jitted check_window`` under the funnel, its lane stage
    forced to ``block`` lanes a block (None: the module's own rule)."""
    import jax

    cache = {}

    def get(block):
        if block not in cache:
            cache[block] = jax.jit(
                lambda pd, ld, nc, n, at_eof: ck._scatter_lanes(
                    ck._check_lanes(pd, ld, nc, n, at_eof, funnel=True,
                                    block=block), W1))
        return cache[block]

    return get


def _want(full_stage, pd, ld, nc, n, at_eof, lo, own):
    r = full_stage(pd, ld, nc, n, jnp.bool_(at_eof))
    i = np.arange(W1)
    m = (i >= lo) & (i < own)
    return (
        int(np.sum(m & np.asarray(r["verdict"]))),
        int(np.sum(m & np.asarray(r["escaped"]))),
        int(r["survivors"]),
    )


def _got(out):
    return int(out["count"]), int(out["esc_count"]), int(out["survivors"])


def _assert_check_matches(want, got, block, why=""):
    """EVERY array of the blocked ``check_window`` is the full-capacity
    stage's, and it ran the lanes the rule gives."""
    for k in CHECK_KEYS:
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.asarray(want[k]), err_msg=f"{k} {why}")
    assert int(got["lanes"]) == _lanes_rule(int(want["survivors"]), block)


def _assert_blocked_matches(
        full_stage, blocked, blocked_check, program, block, pd, ld, nc, n):
    """Both ``at_eof``. The count: every owned span, the blocked stage's
    three scalars are the full stage's, and it ran the lanes the rule
    gives. ``check_window``: every returned array."""
    for at_eof in (True, False):
        if program == "check_window":
            _assert_check_matches(
                full_stage(pd, ld, nc, n, jnp.bool_(at_eof)),
                blocked_check(block)(pd, ld, nc, n, jnp.bool_(at_eof)),
                block, f"block={block} at_eof={at_eof}")
            continue
        for lo, own in ((0, int(n)), (1000, int(n) // 2)):
            want = _want(full_stage, pd, ld, nc, n, at_eof, lo, own)
            out = blocked(block)(
                pd, ld, nc, n, jnp.bool_(at_eof), jnp.int32(lo),
                jnp.int32(own))
            assert _got(out) == want, (block, at_eof, lo, own)
            lanes = int(out["lanes"])
            assert lanes == _lanes_rule(want[2], block)
            assert lanes >= min(want[2], CAPACITY)


PROGRAMS = ("count_window", "check_window")


@pytest.fixture(scope="module")
def generated_windows(tmp_path_factory):
    """1 MiB windows of the benchmark's two configurations: ≈ 3,100
    survivors of short reads (7 blocks of 512), ≈ 40 of long reads."""
    from bench.tests.conftest import generate

    out = {}
    for name in ("wgs-short", "longread-hifi"):
        p = tmp_path_factory.mktemp(name) / "file.bam"
        generate(name, 2 ** 31 + 30, p, 3 << 20)
        out[name] = (_window_of(flatten_file(p).data, W1), _lens_of(p))
    return out


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("name", ["wgs-short", "longread-hifi"])
def test_lane_blocks_match_the_full_stage_on_generated_files(
        generated_windows, full_stage, blocked, blocked_check, name, block,
        program):
    (pd, n), (ld, nc) = generated_windows[name]
    _assert_blocked_matches(
        full_stage, blocked, blocked_check, program, block, pd, ld, nc, n)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("which", [0, 1])
def test_lane_blocks_match_the_full_stage_on_corpora(
        corpus, full_stage, blocked, blocked_check, which, block, program):
    p = corpus[which]
    pd, n = _window_of(flatten_file(p).data, W1)
    ld, nc = _lens_of(p)
    _assert_blocked_matches(
        full_stage, blocked, blocked_check, program, block, pd, ld, nc, n)


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("kind", ["soup", "bit-flips"])
def test_lane_blocks_match_the_full_stage_on_adversarial_windows(
        corpus, full_stage, blocked, blocked_check, kind, block, program):
    """The windows ``test_superset_on_adversarial_windows`` builds, a MiB
    wide: byte soup and a bit-flipped corpus window."""
    pd, n = _window_of(_adversarial(kind, corpus, W1), W1)
    ld, nc = _lens_of(corpus[0])
    _assert_blocked_matches(
        full_stage, blocked, blocked_check, program, block, pd, ld, nc, n)


def _planted(survivors: int, stride: int = 32):
    """A zeroed window with exactly ``survivors`` stage-0 survivors: a
    36-byte fixed block every ``stride`` bytes (remaining 40, refID 0 / 40,
    name_len 2, unmapped, nothing else), whose shifted readings all fail
    the prefilter (their ``remaining`` reads 0 or 2 against an implied 34).
    At stride 24 a block's ``remaining`` lands in the block before's
    next_refID, so the header must hold more than 40 contigs."""
    data = np.zeros(W1, dtype=np.uint8)
    at = np.arange(survivors) * stride
    assert survivors == 0 or at[-1] + 36 <= W1
    data[at] = 40          # remaining (little-endian, one byte)
    data[at + 12] = 2      # l_read_name
    data[at + 18] = 4      # flag: unmapped
    return data


def _planted_lens():
    lens = np.zeros(1024, dtype=np.int32)
    lens[:64] = 1_000_000
    return jnp.asarray(lens), jnp.int32(64)


# The compaction's search (PR 48): ``_ranked_positions`` finds the word of
# the k-th set bit down the levels of ``_rank_table``, whole rows at a time.
# The oracle is ``np.flatnonzero``. Lengths: a block's own table in
# ``_list_escapes`` (64 words: its top alone), one that is no multiple of a
# row and has levels, a served row's 2**15 and a 32 MiB window's 2**20.
RANK_WORDS = (64, 8 * ck.RANK_ROW + 6, 1 << 15, 1 << 20)
RANK_MASKS = ("empty", "full", "dense_8pct", "last_word_one_bit",
              "first_word_every_bit")
RANKS = ("negative", "inside", "straddling_the_end")
RANK_LANES = 2048


@functools.lru_cache(maxsize=1)      # a mask's three cases run in a row
def _rank_mask(words: int, mask: str) -> np.ndarray:
    n = words * 32
    at = np.arange(n)
    return {
        "empty": lambda: np.zeros(n, dtype=bool),
        "full": lambda: np.ones(n, dtype=bool),
        "dense_8pct": lambda: np.random.default_rng(words).random(n) < 0.08,
        "last_word_one_bit": lambda: at == n - 5,
        "first_word_every_bit": lambda: at < 32,
    }[mask]()


@functools.lru_cache(maxsize=None)
def _ranked():
    import jax

    return jax.jit(lambda mask, k: ck._ranked_positions(
        ck._rank_table(mask), k))


@pytest.mark.parametrize("ranks", RANKS)
@pytest.mark.parametrize("mask", RANK_MASKS)
@pytest.mark.parametrize("words", RANK_WORDS)
def test_ranked_positions_are_flatnonzeros(words, mask, ranks):
    bits = _rank_mask(words, mask)
    at = np.flatnonzero(bits)
    # Below zero as ``_list_escapes`` asks (``arange - before``), a whole
    # block inside the population, and one that runs off its end.
    first = {
        "negative": -70,
        "inside": max(len(at) // 2 - RANK_LANES, 0),
        "straddling_the_end": len(at) - RANK_LANES // 2,
    }[ranks]
    k = np.arange(first, first + RANK_LANES, dtype=np.int32)
    got = np.asarray(_ranked()(jnp.asarray(bits), jnp.asarray(k)))
    mine = (k >= 0) & (k < len(at))
    want = np.full(RANK_LANES, -1, dtype=np.int32)
    want[mine] = at[k[mine]]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("words, levels", [
    (64, 0), (ck.RANK_TOP, 0), (8 * ck.RANK_ROW + 6, 1), (1 << 15, 1),
    (1 << 20, 2)])
def test_the_search_fetches_a_row_a_level_and_loops_nowhere(words, levels):
    """One row of ``RANK_ROW`` keys a lane a level under the top, then the
    row the word lies in, and no loop: a binary search gathered an element a
    halving (21 at 2**20 words), then the word. A table that is its top
    alone gathers nothing."""
    import jax

    S = jax.ShapeDtypeStruct
    text = _ranked().lower(
        S((words * 32,), jnp.bool_), S((RANK_LANES,), jnp.int32)).as_text()
    _own, _calls, reached = _gathers_by_function(text)
    run = reached("main")
    assert all(size == RANK_LANES * ck.RANK_ROW for _operand, size in run)
    rows = [operand.split("x") for operand, _size in run]
    assert [r[1:] for r in rows] == (
        [[str(ck.RANK_ROW), "i32"]] * levels
        + [[str(ck.RANK_ROW), "ui32"]] * bool(levels))
    assert "stablehlo.while" not in text


# The words by the row (PR 49): a lane fetches ONE row of the word view,
# once a site, and picks its words out of it (``_lane_words``), where it
# gathered an element a word. The view is followed by itself from its word
# ``WORD_REACH`` on, so a position has a row that holds the words behind it
# whole wherever it lies in its own (28 of 128 residues put the deep flags'
# eight words across two rows of the view alone, 16 the walk's three). The
# oracle is the gather from the view itself, as long as it was until PR 49:
# ``jnp.take(view[:N - 3], pos + off, mode="clip")``, at every residue of the position in its row, at the
# view's first row, at its last (the last fixed block a buffer holds, the
# window's last position, and past both ends, where the gather clips) and
# for dead lanes, which are parked on position 0. Two views, a window's and
# a served row's scaled to the tests' widths: rows of ``WORD_ROW`` at both.
WORD_OFFSETS = {"deep_flags": (0, 4, 8, 12, 16, 20, 24, 28),
                "walk": (0, 12, 16)}
WORD_WINDOWS = {"window": W, "served_row": 8 << 10}
WORD_LANES = 512


def _word_positions(where: str, w: int) -> np.ndarray:
    total = w + ck.PAD
    at = np.arange(WORD_LANES, dtype=np.int32)
    return {
        # Every residue four times over, rows apart.
        "every_residue": 5 * ck.WORD_ROW + at * 129 % (w - 640),
        "first_row": at % ck.WORD_ROW,
        "last_block": total - 36 - at % 256,
        "window_end": w - 1 - at % 256,
        "past_the_ends": np.concatenate([
            total - 48 + at[:96], -at[:WORD_LANES - 96]]).astype(np.int32),
        "dead_lanes": at * 0,
    }[where]


@functools.lru_cache(maxsize=None)
def _word_view(w: int):
    data = np.random.default_rng(w).integers(
        0, 256, w + ck.PAD, dtype=np.uint8)
    return ck._words_at(jnp.asarray(data))


@pytest.mark.parametrize(
    "where", ["every_residue", "first_row", "last_block", "window_end",
              "past_the_ends", "dead_lanes"])
@pytest.mark.parametrize("view", sorted(WORD_WINDOWS))
@pytest.mark.parametrize("site", sorted(WORD_OFFSETS))
def test_lane_words_are_the_elements_a_gather_takes(site, view, where):
    import jax

    w, offsets = WORD_WINDOWS[view], WORD_OFFSETS[site]
    U = _word_view(w)
    assert U.shape == (2 * (w + ck.PAD),) and (w + ck.PAD) % ck.WORD_ROW == 0
    view = U[: w + ck.PAD - 3]          # the words with four bytes behind
    pos = _word_positions(where, w)
    if where == "every_residue":
        assert len(set(pos % ck.WORD_ROW)) == ck.WORD_ROW
    got = jax.jit(functools.partial(ck._lane_words, offsets=offsets))(
        U, jnp.asarray(pos))
    assert len(got) == len(offsets)
    for off, words in zip(offsets, got):
        np.testing.assert_array_equal(
            np.asarray(words),
            np.asarray(jnp.take(view, jnp.asarray(pos) + off, mode="clip")),
            err_msg=f"offset {off}")


@pytest.mark.parametrize("site", sorted(WORD_OFFSETS))
def test_a_site_fetches_one_row_and_gathers_no_element(site):
    """ONE fetch of a row a lane for all of a site's words, whatever their
    number, and no loop: eight element gathers and three until PR 49."""
    import jax

    S = jax.ShapeDtypeStruct
    text = jax.jit(
        functools.partial(ck._lane_words, offsets=WORD_OFFSETS[site])).lower(
        S((2 * (W + ck.PAD),), jnp.int32),
        S((WORD_LANES,), jnp.int32)).as_text()
    _own, _calls, reached = _gathers_by_function(text)
    rows = f"{2 * (W + ck.PAD) // ck.WORD_ROW}x{ck.WORD_ROW}xi32"
    assert reached("main") == [(rows, WORD_LANES * ck.WORD_ROW)]
    assert "stablehlo.while" not in text


@pytest.mark.parametrize(
    "where", ["records", "every_residue", "first_row", "window_end",
              "dead_lanes"])
@pytest.mark.parametrize("reader", ["deep_flags_at", "misc_at"])
def test_lane_fields_are_the_position_wide_passes_at_the_lanes(
        corpus, reader, where):
    """``_deep_flags_at`` and ``_misc_at`` read their fields out of fetched
    rows; the funnel-less passes slice theirs position-wide and never fetch
    a row: the same flags, ``remaining`` and ``body_end`` at every lane."""
    pd, n = _window_of(flatten_file(corpus[0]).data)
    assert int(n) == W                        # the window is full of records
    ld, nc = _lens_of(corpus[0])
    F = np.asarray(ck._compute_flags(pd, ld, nc, n))
    if where == "records":
        pos = np.flatnonzero(F == 0).astype(np.int32)
        assert len(pos) > 100
    else:
        pos = _word_positions(where, W)
    remaining, body_end = (np.asarray(x) for x in ck._compute_misc(pd, n))
    U = ck._words_at(pd)
    if reader == "misc_at":
        got = ck._misc_at(U, n, jnp.asarray(pos))
        want = remaining[pos], body_end[pos]
    else:
        got = ck._deep_flags_at(
            pd, U, ld, nc, n, ck._funnel_tables(pd, n), jnp.asarray(pos))
        want = F[pos], remaining[pos], body_end[pos]
    for mine, theirs in zip(got, want):
        np.testing.assert_array_equal(np.asarray(mine), theirs)


EDGE_BLOCK = 4096
#: survivors → stride. The block's edges, none, the capacity's edges, and
#: one window over it (stride 24 fits 43,690 blocks in a MiB).
PLANTED = {
    0: 32, 1: 32, EDGE_BLOCK - 1: 32, EDGE_BLOCK: 32, EDGE_BLOCK + 1: 32,
    CAPACITY - 1: 32, CAPACITY: 24, CAPACITY + 1: 24, 40_000: 24,
}


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("survivors", sorted(PLANTED))
def test_lane_blocks_at_their_edges(
        full_stage, blocked, blocked_check, survivors, program):
    pd, n = _window_of(_planted(survivors, PLANTED[survivors]), W1)
    ld, nc = _planted_lens()
    lo, own = 0, int(n)
    for at_eof in (True, False):
        want = _want(full_stage, pd, ld, nc, n, at_eof, lo, own)
        assert want[2] == survivors  # the window holds what was planted
        if program == "check_window":
            ref = full_stage(pd, ld, nc, n, jnp.bool_(at_eof))
            got = blocked_check(EDGE_BLOCK)(pd, ld, nc, n, jnp.bool_(at_eof))
            _assert_check_matches(ref, got, EDGE_BLOCK, f"at_eof={at_eof}")
            if survivors > CAPACITY:
                # Over the capacity: every block run, every position
                # unresolved, as the full stage reports it.
                assert int(got["lanes"]) == CAPACITY
                assert np.asarray(got["escaped"]).all()
                assert not np.asarray(got["verdict"]).any()
            continue
        out = blocked(EDGE_BLOCK)(
            pd, ld, nc, n, jnp.bool_(at_eof), jnp.int32(lo), jnp.int32(own))
        assert _got(out) == want
        lanes = int(out["lanes"])
        assert lanes == _lanes_rule(survivors, EDGE_BLOCK)
        if survivors > CAPACITY:
            # The overflow escape, as the full stage reports it: every
            # owned position unresolved, nothing counted, every block run.
            assert _got(out)[:2] == (0, own - lo)
            assert lanes == CAPACITY
        else:
            assert survivors <= lanes < survivors + EDGE_BLOCK


@pytest.mark.parametrize("name", ["wgs-short", "longread-hifi"])
def test_count_window_runs_the_lanes_its_survivors_need(
        generated_windows, full_stage, name):
    """The public program at the module's own block (``lane_block``)."""
    (pd, n), (ld, nc) = generated_windows[name]
    kernel = ck.make_count_window(W1, 10, funnel=True)
    lo, own = 0, int(n)
    out = kernel(pd, ld, nc, n, jnp.bool_(True), jnp.int32(lo), jnp.int32(own))
    want = _want(full_stage, pd, ld, nc, n, True, lo, own)
    assert _got(out) == want
    assert int(out["lanes"]) == _lanes_rule(want[2], ck.lane_block(W1))
    assert int(out["lanes"]) < CAPACITY  # fewer than the full stage's
    # Without the funnel the one stage there is runs the whole capacity.
    off = ck.make_count_window(W1, 10, funnel=False)(
        pd, ld, nc, n, jnp.bool_(True), jnp.int32(lo), jnp.int32(own))
    assert _got(off)[:2] == want[:2] and int(off["lanes"]) == CAPACITY


def test_vmapped_count_window_loops_to_the_rows_maximum(
        generated_windows, full_stage):
    """Rows of one device under ``vmap`` (the mesh step with several rows a
    chip): each row's scalars are its own, its lanes its own blocks, and
    the program still holds ONE lane stage: two ``while`` loops whose trip
    count is the rows' maximum, no branch or select over stages."""
    import functools

    import jax

    (pd_s, n_s), (ld, nc) = generated_windows["wgs-short"]
    (pd_l, n_l), _ = generated_windows["longread-hifi"]
    empty = jnp.zeros_like(pd_s)
    rows = jnp.stack([pd_s, empty, pd_l])
    ns = jnp.stack([n_s, jnp.int32(0), n_l])
    los = jnp.zeros(3, jnp.int32)
    one = functools.partial(
        ck.count_window, reads_to_check=10, funnel=True)
    batched = jax.jit(jax.vmap(
        lambda w, n, lo, own: one(w, ld, nc, n, jnp.bool_(False), lo, own)))
    out = batched(rows, ns, los, ns)
    for r, (pd, n) in enumerate(((pd_s, n_s), (empty, jnp.int32(0)),
                                 (pd_l, n_l))):
        want = _want(full_stage, pd, ld, nc, n, False, 0, int(n))
        assert tuple(int(out[k][r]) for k in
                     ("count", "esc_count", "survivors")) == want
        assert int(out["lanes"][r]) == _lanes_rule(want[2], ck.lane_block(W1))
    assert int(out["lanes"][1]) == 0  # a padding row runs no lane
    assert _whiles(batched, rows, ns, los, ns) == 2
    assert _whiles(
        lambda w, n: one(w, ld, nc, n, jnp.bool_(False), jnp.int32(0), n),
        pd_s, n_s) == 2


def _whiles(fn, *args) -> int:
    """The ``while`` loops of a traced program: the blocks of the lane
    stage's two passes (``searchsorted``'s own loop is a ``scan``). Also
    holds that no ``cond`` switches over lane stages."""
    import jax

    def primitives(jaxpr, into):
        for eqn in jaxpr.eqns:
            into.append(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        primitives(getattr(inner, "jaxpr", inner), into)
        return into

    names = primitives(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert "cond" not in names  # no switch over lane stages
    return names.count("while")


def test_vmapped_check_window_gives_each_row_its_own_arrays(
        generated_windows, full_stage):
    """``check_window`` over a short-read row, an empty (padding) row and a
    long-read row under ``vmap``: each row's arrays are the full-capacity
    stage's of that row alone, its ``lanes`` its own blocks (none for the
    padding row), and the program holds ONE lane stage, batched or not: the
    two ``while`` loops of ``_deep_blocks`` / ``_walk_blocks``."""
    import functools

    import jax

    (pd_s, n_s), (ld, nc) = generated_windows["wgs-short"]
    (pd_l, n_l), _ = generated_windows["longread-hifi"]
    empty = jnp.zeros_like(pd_s)
    rows = jnp.stack([pd_s, empty, pd_l])
    ns = jnp.stack([n_s, jnp.int32(0), n_l])
    one = functools.partial(
        ck.check_window, reads_to_check=10, funnel=True)
    batched = jax.jit(jax.vmap(
        lambda w, n: one(w, ld, nc, n, jnp.bool_(False))))
    out = batched(rows, ns)
    block = ck.lane_block(W1)
    for r, (pd, n) in enumerate(((pd_s, n_s), (empty, jnp.int32(0)),
                                 (pd_l, n_l))):
        _assert_check_matches(
            full_stage(pd, ld, nc, n, jnp.bool_(False)),
            {k: v[r] for k, v in out.items()}, block, f"row {r}")
    lanes = [int(x) for x in out["lanes"]]
    assert lanes[1] == 0 < lanes[2] <= lanes[0] < CAPACITY
    assert _whiles(batched, rows, ns) == 2
    assert _whiles(lambda w, n: one(w, ld, nc, n, jnp.bool_(False)),
                   pd_s, n_s) == 2
    # Without the funnel: the one full-capacity stage, no loop over blocks.
    assert _whiles(
        lambda w, n: ck.check_window(
            w, ld, nc, n, jnp.bool_(False), reads_to_check=10),
        pd_s, n_s) == 0


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("name", ["longread-hifi", "wgs-short"])
def test_the_lanes_follow_the_survivors(
        generated_windows, full_stage, blocked, blocked_check, name, program):
    """The block the 32 MiB windows run (``lane_block(32 << 20)``, forced
    here onto the tests' 1 MiB window, where the suite can afford to run
    it; the 32 MiB programs are lowered in ``tests/test_chip_compile.py``):
    the lanes are the survivors rounded up to whole blocks and never a
    whole block more, so a long-read window, whose ≈ 40 survivors fill 4%
    of a block, runs ONE block and not the sixteen-times-wider one it ran
    until PR 40 (16,384 lanes for 979 survivors on the chip)."""
    block = ck.lane_block(32 << 20)
    (pd, n), (ld, nc) = generated_windows[name]
    at_eof = jnp.bool_(True)
    if program == "check_window":
        out = blocked_check(block)(pd, ld, nc, n, at_eof)
    else:
        out = blocked(block)(
            pd, ld, nc, n, at_eof, jnp.int32(0), jnp.int32(int(n)))
    survivors, lanes = int(out["survivors"]), int(out["lanes"])
    assert survivors == int(full_stage(pd, ld, nc, n, at_eof)["survivors"])
    assert 0 < survivors <= CAPACITY
    assert lanes == _lanes_rule(survivors, block)
    assert 0 <= lanes - survivors < block
    if name == "longread-hifi":
        assert lanes == block  # one block holds a long-read window


def test_the_block_follows_the_rows_width():
    """A 32nd of the capacity within its bounds: a served row of 1 MiB
    runs the 1,024 swept there (PR 34), every window from 2 MiB up, the
    count's and check-bam's 32 MiB among them, the 2,048 swept at 32 MiB
    (PR 40; 16,384 until then: sixteen times the served row's block, and a
    long-read window's one block 94% dead), and no window's block is wider
    than the window's capacity."""
    assert ck.LANE_BLOCK_MIN == 1024 and ck.LANE_BLOCK == 2048
    assert ck.lane_block(1 << 20) == ck.LANE_BLOCK_MIN
    for w in (2 << 20, 4 << 20, 16 << 20, 32 << 20, 64 << 20):
        assert ck.lane_block(w) == ck.LANE_BLOCK
    for w in (32 << 10, 64 << 10, W, W1, 2 << 20, 32 << 20):
        assert ck.LANE_BLOCK_MIN <= ck.lane_block(w) <= ck.LANE_BLOCK
        assert ck.lane_block(w) <= ck.lane_capacity(w)
        assert ck.lane_capacity(w) % ck.lane_block(w) == 0
    assert ck.LANE_BLOCK in BLOCKS and ck.LANE_BLOCK_MIN in BLOCKS


# ------------------------------------------------ the count's escape list

def _owned_escapes(full_stage, pd, ld, nc, n, lo, own):
    """``check_window``'s escaped positions inside ``[lo, own)``."""
    r = full_stage(pd, ld, nc, n, jnp.bool_(False))
    i = np.arange(W1)
    return np.flatnonzero((i >= lo) & (i < own) & np.asarray(r["escaped"]))


def _listed(out, r=None):
    at = np.asarray(out["esc_pos"] if r is None else out["esc_pos"][r])
    assert np.all(at[np.argmax(at < 0):] < 0) or np.all(at >= 0)
    return at[at >= 0]


#: Owned ends of a 1 MiB window that is not the file's last: none of its
#: tail owned (no escape), the usual case, and all but the last 64 bytes
#: (every candidate whose chain reaches the end).
OWNED = ((0, W1 // 2), (1000, W1 - (64 << 10)), (0, W1 - 64))


@pytest.mark.parametrize("funnel", [True, False])
@pytest.mark.parametrize("name", ["wgs-short", "longread-hifi"])
def test_the_escape_list_is_check_windows_owned_escapes(
        generated_windows, full_stage, name, funnel):
    (pd, n), (ld, nc) = generated_windows[name]
    kernel = ck.make_count_window(W1, 10, funnel=funnel, escapes=64)
    for lo, own in OWNED:
        want = _owned_escapes(full_stage, pd, ld, nc, n, lo, own)
        out = kernel(pd, ld, nc, n, jnp.bool_(False), jnp.int32(lo),
                     jnp.int32(own))
        assert int(out["esc_count"]) == len(want) <= 64, (lo, own)
        assert np.array_equal(_listed(out), want), (lo, own)
        assert not bool(out["esc_overflow"])
        # The scalars are the program's without the list.
        plain = ck.make_count_window(W1, 10, funnel=funnel)(
            pd, ld, nc, n, jnp.bool_(False), jnp.int32(lo), jnp.int32(own))
        assert "esc_pos" not in plain
        assert all(int(out[k]) == int(plain[k]) for k in plain)
    assert len(_owned_escapes(full_stage, pd, ld, nc, n, *OWNED[-1])) >= 9


@pytest.mark.parametrize("block", (512, 4096))
def test_the_escape_list_spans_lane_blocks_and_reports_its_overflow(
        generated_windows, full_stage, block):
    """Short reads, 512 lanes a block: the escapes of the window's tail lie
    in its last blocks, after blocks with none. With fewer slots than
    escapes the list holds the first of them and says so; an owned span
    that reaches the buffer's last 35 bytes (stage 0's own escapes, which
    the list does not hold) is an overflow too."""
    import functools

    import jax

    (pd, n), (ld, nc) = generated_windows["wgs-short"]
    lo, own = OWNED[-1]
    want = _owned_escapes(full_stage, pd, ld, nc, n, lo, own)
    assert len(want) > 8
    for slots, overflow in ((64, False), (8, True), (1, True)):
        fn = jax.jit(functools.partial(
            ck._count_funnel, reads_to_check=10, block=block,
            escapes=slots))
        out = fn(pd, ld, nc, n, jnp.bool_(False), jnp.int32(lo),
                 jnp.int32(own))
        assert int(out["esc_count"]) == len(want)
        assert np.array_equal(_listed(out), want[:slots])
        assert bool(out["esc_overflow"]) is overflow
    out = fn(pd, ld, nc, n, jnp.bool_(False), jnp.int32(0), n)
    tail = _owned_escapes(full_stage, pd, ld, nc, n, 0, int(n))
    assert int(out["esc_count"]) == len(tail) > len(want)
    assert bool(out["esc_overflow"])


def test_a_window_over_its_lane_capacity_is_an_escape_overflow(full_stage):
    pd, n = _window_of(_planted(CAPACITY + 1, 24), W1)
    ld, nc = _planted_lens()
    out = ck.make_count_window(W1, 10, funnel=True, escapes=64)(
        pd, ld, nc, n, jnp.bool_(False), jnp.int32(0), n)
    assert int(out["esc_count"]) == int(n) and bool(out["esc_overflow"])


def test_the_vmapped_escape_list_is_each_rows_own(
        generated_windows, full_stage):
    """Rows under ``vmap`` (a device with several rows): each row's list is
    its own owned escapes, a padding row's is empty."""
    import functools

    import jax

    (pd_s, n_s), (ld, nc) = generated_windows["wgs-short"]
    (pd_l, n_l), _ = generated_windows["longread-hifi"]
    empty = jnp.zeros_like(pd_s)
    rows = jnp.stack([pd_s, empty, pd_l])
    ns = jnp.stack([n_s, jnp.int32(0), n_l])
    owns = jnp.stack([n_s - 64, jnp.int32(0), n_l - (64 << 10)])
    one = functools.partial(
        ck.count_window, reads_to_check=10, funnel=True, escapes=64)
    out = jax.jit(jax.vmap(
        lambda w, n, own: one(
            w, ld, nc, n, jnp.bool_(False), jnp.int32(0), own)))(
        rows, ns, owns)
    for r, (pd, n) in enumerate(((pd_s, n_s), (empty, jnp.int32(0)),
                                 (pd_l, n_l))):
        want = _owned_escapes(full_stage, pd, ld, nc, n, 0, int(owns[r]))
        assert int(out["esc_count"][r]) == len(want)
        assert np.array_equal(_listed(out, r), want)
        assert not bool(out["esc_overflow"][r])
    assert len(_listed(out, 0)) >= 9 and len(_listed(out, 1)) == 0


# ------------------------------------------------------------------------
# Stage 0 holds a position against the LONGEST contig (PR 32); the exact
# ``pos > len[idx]`` test is the deep flags', at the survivors. A forged
# fixed block that passes every other prefilter check, names a SHORT contig
# and lies past its end (but not past the longest's) is therefore a stage-0
# survivor now; its verdict, and that of every chain that steps onto it, is
# the full pass's all the same.

SHORT, LONG = 1_000, 1_000_000


def _block(ref=(-1, -1), next_ref=(-1, -1)):
    """A 38-byte unmapped record named ``r``: remaining 34, no cigar, no
    sequence. With both refs ``(-1, -1)`` it passes all 19 checks."""
    rec = np.zeros(38, dtype=np.uint8)
    i32 = rec[:36].view("<i4")
    i32[0] = 34
    i32[1], i32[2] = ref
    rec[12] = 2            # l_read_name
    rec[18] = 4            # flag: unmapped
    i32[6], i32[7] = next_ref
    rec[36] = ord("r")
    return rec


def _forged_window(field: str, short_idx: int):
    """``(data, forged, stepped_from, beyond)``: five real records, a forged
    one (the chains of the five step onto it), twelve real ones, then two
    forged blocks on their own and one whose position lies past the longest
    contig too, which stage 0 still rejects."""
    def forged(pos):
        return _block(**{field: (short_idx, pos)})

    parts, at, marks = [], 0, []

    def put(rec, mark=False):
        nonlocal at
        if mark:
            marks.append(at)
        parts.append(rec)
        at += len(rec)

    for _ in range(5):
        put(_block())
    stepped_from = at - 38
    put(forged(SHORT + 4_000), mark=True)
    for _ in range(12):
        put(_block())
    for pos in (SHORT + 1, LONG):
        put(np.zeros(64, dtype=np.uint8))
        put(forged(pos), mark=True)
    put(np.zeros(64, dtype=np.uint8))
    beyond = at
    put(forged(LONG + 1))
    for _ in range(3):
        put(_block())
    return np.concatenate(parts), np.array(marks), stepped_from, beyond


def _lens(values, cmax=1024, fill=0):
    lens = np.full(cmax, fill, dtype=np.int32)
    lens[: len(values)] = values
    return jnp.asarray(lens)


_LARGE_POS = {"ref": "tooLargeReadPos", "next_ref": "tooLargeNextReadPos"}


@pytest.mark.parametrize("program", ["count_window", "check_window"])
@pytest.mark.parametrize("at_eof", [True, False])
@pytest.mark.parametrize("field", ["ref", "next_ref"])
def test_a_position_past_a_short_contig_survives_stage_0_and_fails_deep(
        kernels, field, at_eof, program):
    from spark_bam_tpu.check.flags import BIT

    data, forged, stepped_from, beyond = _forged_window(field, short_idx=1)
    pd, n = _window_of(data)
    ld, nc = _lens([LONG, SHORT]), jnp.int32(2)
    bit = BIT[_LARGE_POS[field]]

    pre = np.asarray(ck._prefilter_flags(pd, ld, nc, n))
    full = np.asarray(ck._compute_flags(pd, ld, nc, n))
    _assert_superset(pd, ld, nc, n)
    assert not pre[forged].any()             # stage-0 survivors ...
    assert np.all(full[forged] & bit)        # ... that only the lookup rejects
    assert pre[beyond] & bit                 # past the longest: still stage 0's

    on, off = kernels
    if program == "check_window":
        a = on(pd, ld, nc, n, jnp.bool_(at_eof))
        b = off(pd, ld, nc, n, jnp.bool_(at_eof))
        for k in PARITY_KEYS:
            np.testing.assert_array_equal(
                np.asarray(a[k]), np.asarray(b[k]), err_msg=k)
        verdict = np.asarray(a["verdict"])
        assert not verdict[forged].any() and not verdict[beyond]
        assert int(a["survivors"]) == int(np.sum(pre[: int(n)] == 0))
        # The chain of the record before the first forged block steps onto
        # it and fails there, one read in, on the block's own deep mask.
        for r in (a, b):
            assert not np.asarray(r["verdict"])[stepped_from]
            assert int(np.asarray(r["reads_before"])[stepped_from]) == 1
            assert int(np.asarray(r["fail_mask"])[stepped_from]) == int(
                full[forged[0]])
        # At the forged blocks the funnel's mask is the full pass's now.
        np.testing.assert_array_equal(
            np.asarray(a["fail_mask"])[forged], full[forged])
    else:
        for lo, own in ((0, int(n)), (38, int(forged[1]) + 1)):
            a, b = (
                ck.make_count_window(W, 10, funnel=f)(
                    pd, ld, nc, n, jnp.bool_(at_eof), jnp.int32(lo),
                    jnp.int32(own))
                for f in (True, False)
            )
            assert int(a["count"]) == int(b["count"]), (lo, own)
            assert int(a["esc_count"]) == int(b["esc_count"]), (lo, own)
        assert int(a["survivors"]) == int(np.sum(pre[: int(n)] == 0))
        assert int(a["survivors"]) >= int(b["survivors"]) + len(forged)


@pytest.mark.parametrize("header", ["no_contigs", "last_is_longest"])
def test_the_stage_0_bound_on_edge_headers(kernels, header):
    """``num_contigs`` 0 (no index is valid, whatever the table's padding
    holds: the bound is never compared) and a header whose LAST contig is
    the longest (the reduction reaches the table's last valid entry)."""
    from spark_bam_tpu.check.flags import BIT

    on, off = kernels
    if header == "no_contigs":
        data, forged, _, beyond = _forged_window("ref", short_idx=1)
        ld, nc = _lens([], fill=7), jnp.int32(0)
    else:
        data, forged, _, beyond = _forged_window("ref", short_idx=0)
        ld, nc = _lens([SHORT, LONG], fill=2 * LONG), jnp.int32(2)
    pd, n = _window_of(data)
    _assert_superset(pd, ld, nc, n)
    pre = np.asarray(ck._prefilter_flags(pd, ld, nc, n))
    if header == "no_contigs":
        assert np.all(pre[forged] & BIT["tooLargeReadIdx"])
    else:
        # Survivors up to the LAST contig's length, and not up to the
        # padding's: the bound is the header's, nothing beyond it.
        assert not pre[forged].any()
        assert pre[beyond] & BIT["tooLargeReadPos"]
    for at_eof in (True, False):
        a = on(pd, ld, nc, n, jnp.bool_(at_eof))
        b = off(pd, ld, nc, n, jnp.bool_(at_eof))
        for k in PARITY_KEYS:
            np.testing.assert_array_equal(
                np.asarray(a[k]), np.asarray(b[k]), err_msg=f"{at_eof} {k}")
        # At EOF the last three records' chains end on the file's edge.
        assert np.asarray(a["verdict"]).sum() >= (3 if at_eof else 0)
        c, d = (
            ck.make_count_window(W, 10, funnel=f)(
                pd, ld, nc, n, jnp.bool_(at_eof), jnp.int32(0), n)
            for f in (True, False)
        )
        assert int(c["count"]) == int(d["count"]) == int(
            np.asarray(a["verdict"]).sum())
        assert int(c["esc_count"]) == int(d["esc_count"])


# The lookup must not come back position-wide unnoticed: in the lowered text
# of the funnelled programs no gather FROM the contig table is as wide as
# the window (a gather costs per index: two of them were 77-92% of a window
# on the chip). The table gets a length nothing else in the program has.
_TABLE = 1021


def _table_gathers(text: str, w: int):
    """``(wide, narrow)``: result sizes of the gathers whose operand is the
    contig table (``[_TABLE]``, or ``[rows, _TABLE]`` under ``vmap``)."""
    import math
    import re

    wide, narrow = [], []
    for line in text.splitlines():
        if "stablehlo.gather" not in line:
            continue
        m = re.search(r":\s*\(tensor<([^>]*)>.*\)\s*->\s*tensor<([^>]*)>",
                      line)
        assert m, line
        operand = m.group(1).split("x")[:-1]
        if not operand or operand[-1] != str(_TABLE):
            continue
        rows = math.prod(int(d) for d in operand[:-1])
        size = math.prod(int(d) for d in m.group(2).split("x")[:-1])
        (wide if size >= rows * w else narrow).append(size)
    return wide, narrow


@pytest.mark.parametrize("program", ["count_window", "serve_step"])
def test_no_window_wide_gather_from_the_contig_table_under_the_funnel(
        program):
    w = 64 << 10
    wide, narrow = _table_gathers(_lower_lane_program(program, True, w), w)
    assert not wide, wide
    # The exact lookup is still there, at the lanes (one ``_take`` in the
    # text serves ref and next_ref alike).
    assert narrow and max(narrow) < w
    # Without the funnel the full pass looks every position up, as it did.
    wide_off, _ = _table_gathers(_lower_lane_program(program, False, w), w)
    assert wide_off


# The lane stage pays per gather index (PR 32 measured it; PR 36 acts on it;
# PR 49 takes the words' indices away), so under the funnel it reads the
# 32-bit words of ONE materialized view of the window by the row and looks a
# flag up in ONE array. Counted in the lowered text, a call each time it is
# written: the one lane-wide gather left that reads the window's bytes is
# the name's last byte; no gather takes an element of the word view; the
# deep flags fetch one of its rows a lane for their eight words; a step of
# the walk looks up one flag and fetches one row for its three words, and
# its first step nothing at all.
_READS = 10


def _gathers_by_function(text: str):
    """``reached(name) -> [(operand type, result size), ...]``: the gathers
    a function of a lowered module runs, those of the functions it calls
    included, a call counted each time it is written."""
    import math
    import re

    own, calls, name = {}, {}, None
    for line in text.splitlines():
        m = re.match(r"\s*func\.func \w+ @([\w.]+)\(", line)
        if m:
            name = m.group(1)
            own[name], calls[name] = [], []
            continue
        if "stablehlo.gather" in line:
            m = re.search(
                r":\s*\(tensor<([^>]*)>.*\)\s*->\s*tensor<([^>]*)>", line)
            assert m, line
            size = math.prod(int(d) for d in m.group(2).split("x")[:-1])
            own[name].append((m.group(1), size))
        if name is not None:
            calls[name] += re.findall(r"call @([\w.]+)\(", line)
    memo = {}

    def reached(fn):
        if fn not in memo:
            memo[fn] = own[fn] + [g for c in calls[fn] for g in reached(c)]
        return memo[fn]

    return own, calls, reached


@functools.lru_cache(maxsize=None)
def _lower_lane_program(program: str, funnel: bool, w: int) -> str:
    import jax

    S = jax.ShapeDtypeStruct
    scalars = [S((), jnp.int32), S((), jnp.int32), S((), jnp.bool_)]
    window = S((w + ck.PAD,), jnp.uint8)
    if program == "count_window":
        fn = jax.jit(ck.make_count_window(w, _READS, funnel=funnel))
        return fn.lower(
            window, S((_TABLE,), jnp.int32), *scalars, S((), jnp.int32),
            S((), jnp.int32)).as_text()
    if program == "check_window":
        fn = jax.jit(ck.make_check_window(w, _READS, funnel=funnel))
        return fn.lower(window, S((_TABLE,), jnp.int32), *scalars).as_text()
    from spark_bam_tpu.parallel.mesh import (
        make_mesh, make_shard_map_serve_step,
    )

    rows = 2
    step = make_shard_map_serve_step(
        make_mesh(jax.devices()[:1]), _READS, funnel=funnel)
    return step.lower(
        S((rows, w + ck.PAD), jnp.uint8), S((rows,), jnp.int32),
        S((rows,), jnp.bool_), S((rows,), jnp.int32), S((rows,), jnp.int32),
        S((rows, _TABLE), jnp.int32), S((rows,), jnp.int32)).as_text()


@pytest.mark.parametrize(
    "program", ["count_window", "check_window", "serve_step"])
def test_the_lane_stage_gathers_words_and_looks_a_flag_up_once(program):
    w = 64 << 10
    lanes = ck.lane_block(w)
    window, words = f"{w + ck.PAD}xui8", f"{2 * (w + ck.PAD)}xi32"
    rows = f"{2 * (w + ck.PAD) // ck.WORD_ROW}x{ck.WORD_ROW}xi32"
    merged = f"{w + 1}xi32"

    own, calls, reached = _gathers_by_function(
        _lower_lane_program(program, True, w))
    run = reached("main")
    # No gather at lane width reads the bytes but the name's last byte.
    assert [g for g in run if g[0] == window] == [(window, lanes)]
    # No word is gathered by element: a site fetches one row of the view,
    # the deep flags once for their eight words, the walk once a step for
    # its three, and its first step nothing (pass 1 read its position).
    steps_looked_up = _READS - 1
    assert not [g for g in run if g[0] == words]
    assert [g for g in run if g[0] == rows] == [
        (rows, lanes * ck.WORD_ROW)] * (1 + steps_looked_up)
    # One flag lookup a step, in the merged array.
    assert run.count((merged, lanes)) == steps_looked_up
    assert not [g for g in run if g[0] == f"{w}xi32"]
    # The walk's step, the function that calls the flag's ``take``, holds
    # two gathers (four until PR 49: the flag and three words).
    looks_up = {f for f in own if (merged, lanes) in own[f]}
    steps = [f for f in own if looks_up & set(calls[f])]
    assert steps
    for f in steps:
        assert len(reached(f)) <= 2, (f, reached(f))

    # Without the funnel the program reads what it read: no word view, the
    # name's last byte at every position, and a step of the rolled walk
    # looks up F, remaining and body_end, each as wide as the window.
    own, calls, reached = _gathers_by_function(
        _lower_lane_program(program, False, w))
    run = reached("main")
    capacity = ck.lane_capacity(w)
    assert not [g for g in run if g[0] in (words, rows, merged)]
    assert [g for g in run if g[0] == window] == [(window, w)]
    assert run.count((f"{w}xi32", capacity)) == 3


# The words at their edges (PR 36): the fields a lane reads as 32-bit words
# of the window's word view, where a byte-wise reading and a word-wise one
# could part: the last fixed block a buffer holds, the window's last
# position, the widest body the format allows (the reach of ``PAD``) and a
# ``remaining`` below zero (the sign through the view's int32). The oracle
# is the program without the funnel, which slices position-wide fields and
# never gathers a word.
def _fixed_block(remaining, name_len=2, n_cigar=0, seq_len=0):
    """A 36-byte fixed block, unmapped, both refs -1."""
    rec = np.zeros(36, dtype=np.uint8)
    i32 = rec.view("<i4")
    i32[0] = remaining
    i32[1] = i32[2] = i32[6] = i32[7] = -1
    rec[12] = name_len
    rec[16:18] = np.frombuffer(np.uint16(n_cigar).tobytes(), dtype=np.uint8)
    rec[18] = 4            # flag: unmapped
    i32[5] = seq_len
    return rec


def _word_edge_window(case: str):
    """``(data, marks)``: a buffer of real records around the case's forged
    blocks, and the positions the case is about."""
    real = [_block() for _ in range(6)]
    if case == "last_block":
        # A stage-0 survivor in the buffer's last 36 bytes: its name lies
        # past ``n``. The six records' chains step onto it.
        parts = real + [_fixed_block(34)]
        data = np.concatenate(parts)
        return data, [len(data) - 36]
    if case == "window_end":
        # ``n == w``: a block at ``w - 36``, and a record whose chain steps
        # to ``w - 1``, the last slot before the flag array's pad slot.
        at = W - 200
        stepper = _block()
        stepper[:4] = np.frombuffer(
            np.int32(W - 5 - at).tobytes(), dtype=np.uint8)
        head = np.zeros(at - 38 * len(real), dtype=np.uint8)
        gap = np.zeros(W - 36 - (at + 38), dtype=np.uint8)
        data = np.concatenate([head] + real + [stepper, gap, _fixed_block(34)])
        assert len(data) == W
        return data, [at, W - 36]
    if case == "widest_body":
        # l_read_name 255 and n_cigar 65,535: a body of 262,431 bytes, past
        # the end of this window from wherever it starts.
        wide = np.concatenate([
            _fixed_block(32 + 255 + 4 * 65535, 255, 65535),
            np.full(254, ord("a"), dtype=np.uint8), np.zeros(1, np.uint8)])
        tail = np.zeros(4096, dtype=np.uint8)
        data = np.concatenate(real + [wide, tail] + real + [wide[:36]])
        return data, [38 * len(real), len(data) - 36]
    if case == "long_cigar":
        # name_len and n_cigar with their top bits set (255; 40,000), in a
        # record that is valid whole: ``remaining`` is short of the body
        # because the implied size wraps low (seq_len -100,000), so the
        # chain goes on from the body's END, which only both fields give.
        forged = np.concatenate([
            _fixed_block(10_288, 255, 40_000, seq_len=-100_000),
            np.full(254, ord("a"), dtype=np.uint8), np.zeros(1, np.uint8),
            np.zeros(4 * 40_000, dtype=np.uint8)])
        data = np.concatenate(real + [forged] + [_block() for _ in range(14)])
        return data, [38 * len(real)]
    assert case == "negative_remaining"
    # ``remaining`` -5 passes the implied-size test when the implied size
    # wraps below it (JVM int32: seq_len 1.5e9), and the chain goes on from
    # the body's end, not from ``pos + 4 + remaining``.
    forged = np.concatenate([
        _fixed_block(-5, seq_len=1_500_000_000),
        np.array([ord("r"), 0], dtype=np.uint8)])
    data = np.concatenate(real + [forged] + [_block() for _ in range(14)])
    return data, [38 * len(real)]


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize(
    "case", ["last_block", "window_end", "widest_body", "long_cigar",
             "negative_remaining"])
def test_lane_words_at_their_edges(kernels, case, program):
    data, marks = _word_edge_window(case)
    pd, n = _window_of(data)
    ld, nc = _lens([LONG]), jnp.int32(1)
    pre = np.asarray(ck._prefilter_flags(pd, ld, nc, n))
    assert not pre[marks].any()  # what the case plants survives stage 0
    _assert_superset(pd, ld, nc, n)
    if case == "negative_remaining":
        assert int(np.asarray(ck._words_at(pd))[marks[0]]) == -5
    for at_eof in (True, False):
        if program == "check_window":
            on, off = kernels
            a = on(pd, ld, nc, n, jnp.bool_(at_eof))
            b = off(pd, ld, nc, n, jnp.bool_(at_eof))
            for k in PARITY_KEYS:
                np.testing.assert_array_equal(
                    np.asarray(a[k]), np.asarray(b[k]),
                    err_msg=f"{case} at_eof={at_eof} {k}")
            if case in ("negative_remaining", "long_cigar") and at_eof:
                # The forged record is a record: its chain, and the six
                # before it, run on through the fourteen behind.
                assert np.asarray(a["verdict"])[: marks[0] + 1: 38].all()
            continue
        # Not at EOF stage 0 escapes the buffer's last 35 positions itself,
        # which the funnel's list leaves to ``esc_overflow``: own up to them.
        end = int(n) if at_eof else int(n) - 64
        for lo, own in ((0, end), (38, end // 2)):
            a, b = (
                ck.make_count_window(
                    W, 10, funnel=f, escapes=ck.ESCAPE_LIST)(
                    pd, ld, nc, n, jnp.bool_(at_eof), jnp.int32(lo),
                    jnp.int32(own))
                for f in (True, False))
            for k in ("count", "esc_count", "esc_pos", "esc_overflow"):
                np.testing.assert_array_equal(
                    np.asarray(a[k]), np.asarray(b[k]),
                    err_msg=f"{case} at_eof={at_eof} [{lo}, {own}) {k}")
