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
