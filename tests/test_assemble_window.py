"""Window assembly of the fused count program (checker._assemble_window):
``carry[:carry_len] ‖ row_0[:len_0] ‖ row_1[:len_1] ‖ … ‖ zeros`` against
``numpy.concatenate``, on small rows built here. Row bytes past a row's length,
the carry's tail past ``carry_len`` and pad rows are filled with non-zero
garbage, so a byte that leaks from any of them shows."""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_bam_tpu.tpu import checker
from spark_bam_tpu.tpu.inflate import STRIDE

S = 64              # row width of the small fixtures
W, HALO = 1024, 128


def _case(lens, carry_len, rows=8, stride=S, window=W, halo=HALO):
    return stride, window, halo, rows, lens, carry_len


CASES = {
    "no_carry": _case([60, 64, 1, 33], 0),
    "carry_len_is_halo": _case([60, 64, 1, 33], HALO),
    "partial_carry_garbage_tail": _case([60, 64, 1, 33], 37),
    "zero_rows_at_start": _case([0, 0, 60, 64, 5], 37),
    "two_adjacent_zero_rows_in_the_middle": _case([60, 0, 0, 64, 5], 37),
    "trailing_pad_rows": _case([60, 64, 5, 0, 0, 0, 0, 0], 37),
    "fewer_lens_than_rows": _case([60, 64, 5], 37, rows=16),
    "one_byte_last_row": _case([64, 64, 1], 100),
    "one_byte_rows": _case([1, 1, 1, 1, 1, 1], 3),
    "n_is_window_exactly": _case([64] * 14, HALO, rows=16),
    "n_is_window_last_row_short": _case([64] * 13 + [63, 1], HALO, rows=16),
    "n_smaller_than_halo": _case([20, 9], 11),
    "all_rows_empty": _case([0, 0, 0, 0], 37),
    "all_rows_empty_no_carry": _case([0, 0, 0, 0], 0),
    "full_65536_byte_row": _case(
        [STRIDE, 0xFF00, 1], STRIDE // 4, rows=4, stride=STRIDE,
        window=4 * STRIDE, halo=STRIDE // 2,
    ),
}


def _fixture(name):
    stride, window, halo, rows, lens, carry_len = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    resolved = rng.integers(1, 256, (rows, stride), dtype=np.uint8)
    carry = rng.integers(1, 256, halo, dtype=np.uint8)
    lens = np.asarray(lens, np.int32)
    n = carry_len + int(lens.sum())
    assert n <= window and carry_len <= halo and lens.max(initial=0) <= stride
    want = np.zeros(window, np.uint8)
    want[:n] = np.concatenate(
        [carry[:carry_len]] + [resolved[b, :l] for b, l in enumerate(lens)]
    )
    return resolved, lens, carry, carry_len, n, window, halo, want


@pytest.mark.parametrize("name", sorted(CASES))
def test_assemble_window_is_the_concatenation(name):
    resolved, lens, carry, carry_len, n, window, halo, want = _fixture(name)
    val = checker._assemble_window(
        jnp.asarray(resolved), jnp.asarray(lens), jnp.asarray(carry),
        jnp.int32(carry_len), jnp.int32(n), window=window, halo=halo,
    )
    assert val.shape == (window,) and val.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(val), want)


@pytest.mark.parametrize("own", [0, 300, W - HALO, W - HALO // 2, W])
def test_count_from_planes_carry_is_the_owned_end_tail(own):
    """The next carry is ``val[own : own + halo]``, zeros past the window."""
    resolved, lens, carry, carry_len, n, window, halo, want = _fixture(
        "n_is_window_last_row_short"
    )
    out = checker._count_from_planes(
        jnp.asarray(resolved), jnp.int32(0), jnp.asarray(lens),
        jnp.asarray(carry), jnp.zeros(8, jnp.int32), jnp.int32(1),
        jnp.int32(carry_len), jnp.int32(n), jnp.bool_(False), jnp.int32(0),
        jnp.int32(own), window=window, halo=halo, reads_to_check=10,
        flags_impl="xla", pallas_interpret=False, funnel=True,
    )
    tail = np.concatenate([want, np.zeros(halo, np.uint8)])[own:own + halo]
    np.testing.assert_array_equal(np.asarray(out["carry"]), tail)
