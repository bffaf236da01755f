"""The searches ``tools/lane_sweep.py`` sweeps against the package's are
searches: each column's ``(rank_table, ranked_positions)`` pair finds the
set bits ``np.flatnonzero`` finds, so a column that times faster on the chip
is a faster way to the same answer."""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_SPEC = importlib.util.spec_from_file_location(
    "lane_sweep", Path(__file__).resolve().parents[1] / "tools/lane_sweep.py")
lane_sweep = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(lane_sweep)

LANES = 512


@pytest.mark.parametrize("words", [64, 1 << 15, (1 << 15) + 70])
@pytest.mark.parametrize(
    "name", [n for n, v in lane_sweep.VARIANTS.items() if v is not None])
def test_a_swept_search_finds_what_flatnonzero_finds(name, words):
    rank_table, ranked_positions = lane_sweep.VARIANTS[name]
    bits = np.random.default_rng(words).random(words * 32) < 0.03
    at = np.flatnonzero(bits)
    find = jax.jit(lambda mask, k: ranked_positions(rank_table(mask), k))
    for first in (-5, len(at) // 2, len(at) - LANES // 2):
        k = np.arange(first, first + LANES, dtype=np.int32)
        mine = (k >= 0) & (k < len(at))
        want = np.full(LANES, -1, dtype=np.int32)
        want[mine] = at[k[mine]]
        got = np.asarray(find(jnp.asarray(bits), jnp.asarray(k)))
        np.testing.assert_array_equal(got, want)


# The word family (PR 49): each column's view and ``lane_words`` read the
# words the parent's element gathers read, at every residue of a position
# in its row, the residues that put a site's words across two rows among
# them, and at the last fixed block a buffer holds.
WORD_SITES = {"deep_flags": (0, 4, 8, 12, 16, 20, 24, 28), "walk": (0, 12, 16)}


@pytest.mark.parametrize("site", sorted(WORD_SITES))
@pytest.mark.parametrize(
    "name", [n for n in lane_sweep.WORD_VARIANTS if n != "parent"])
def test_a_swept_word_layout_reads_what_the_gathers_read(name, site):
    from spark_bam_tpu.tpu import checker as ck

    w, offsets = 64 << 10, WORD_SITES[site]
    padded = jnp.asarray(np.random.default_rng(7).integers(
        0, 256, w + ck.PAD, dtype=np.uint8))
    at = np.arange(4 * ck.WORD_ROW, dtype=np.int32)
    pos = jnp.asarray(np.concatenate([
        at, 9 * ck.WORD_ROW + at * 129 % w, w + ck.PAD - 36 - at]))
    parent = lane_sweep.WORD_VARIANTS["parent"]
    want = parent["lane_words"](parent["words_at"](padded), pos, offsets)
    variant = lane_sweep.WORD_VARIANTS[name] or {
        "words_at": ck._words_at, "lane_words": ck._lane_words}
    V = variant["words_at"](padded)
    if "view" in variant:
        V = variant["view"](V, padded)
    got = jax.jit(lambda V, pos: variant["lane_words"](V, pos, offsets))(
        V, pos)
    for mine, theirs in zip(got, want):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
