"""The window layout, which the host does: ``stream_check.halo_windows``
lays ``carry ‖ view`` and names each buffer's owned span. Against the plain
reference (the views' concatenation, cut by arithmetic written here), on
small views built here: no device, nothing compiled.

Every view's bytes are random and non-zero, so a byte taken from the wrong
place shows."""

from types import SimpleNamespace

import numpy as np
import pytest

from spark_bam_tpu.tpu.stream_check import halo_windows

HALO = 128

#: name → (view sizes, halo, header end): the shapes a window can take.
CASES = {
    "no_carry_one_view": ([417], HALO, 40),
    "carry_is_exactly_the_halo": ([300, 300, 300], HALO, 40),
    "n_is_the_window_exactly": ([1024 - HALO, 1024 - HALO, 77], HALO, 40),
    "n_smaller_than_the_halo": ([20, 9, 300], HALO, 4),
    "one_byte_last_view": ([300, 300, 1], HALO, 40),
    "one_byte_views": ([1, 1, 1, 1, 1, 1], 3, 2),
    "empty_views_at_the_start": ([0, 0, 300, 64, 5], HALO, 40),
    "two_adjacent_empty_views_in_the_middle": ([300, 0, 0, 300, 5], HALO, 40),
    "trailing_empty_views": ([300, 64, 5, 0, 0], HALO, 40),
    "last_view_short": ([300, 300, 63], HALO, 40),
    "all_views_empty": ([0, 0, 0, 0], HALO, 0),
    "no_halo": ([300, 300, 5], 0, 40),
    "header_ends_inside_the_first_window": ([300, 300], HALO, 171),
    "lo_clamps_past_the_header": ([300, 300, 300], HALO, 500),
    "full_65536_byte_members": ([65536, 0xFF00, 1], 65536 // 2, 40),
}


def _views(sizes, seed=0):
    rng = np.random.default_rng(seed)
    views = [
        SimpleNamespace(
            data=rng.integers(1, 256, n, dtype=np.uint8), at_eof=False)
        for n in sizes
    ]
    views[-1].at_eof = True  # as InflatePipeline marks its last view
    return views


def _rows(sizes, halo, header_end, seed=0):
    views = _views(sizes, seed)
    stream = np.concatenate([v.data for v in views])
    return stream, list(halo_windows(iter(views), halo, header_end))


@pytest.mark.parametrize("name", sorted(CASES))
def test_windows_are_carry_then_view_and_the_owned_spans_tile(name):
    sizes, halo, header_end = CASES[name]
    stream, rows = _rows(sizes, halo, header_end, sorted(CASES).index(name))
    assert len(rows) == len(sizes)
    frontier = 0        # where the owned spans have got to
    carry_len = 0       # what the row before left un-owned
    for i, (buf, base, own_end, lo, at_eof) in enumerate(rows):
        n = carry_len + sizes[i]
        assert base == frontier and len(buf) == n
        # carry ‖ view is the stream itself from ``base``: no byte from
        # anywhere else, none missing.
        np.testing.assert_array_equal(buf, stream[base: base + n])
        last = i == len(sizes) - 1
        assert at_eof is last
        assert own_end == (n if last else max(n - halo, 0))
        # Every owned position of a buffer that is not the last has the
        # halo's bytes of lookahead behind it in the same buffer.
        assert last or own_end == 0 or n - own_end == halo
        assert lo == min(max(header_end - base, 0), own_end)
        frontier += own_end
        carry_len = n - own_end
    assert frontier == len(stream)  # the owned spans tile the stream
    # The header's bytes are owned by no span: the ``[lo, own_end)`` of all
    # rows cover exactly ``[header_end, total)``.
    counted = sum(own_end - lo for _b, _base, own_end, lo, _e in rows)
    assert counted == max(len(stream) - header_end, 0)


@pytest.mark.parametrize("halo", [0, 1, 100, 300, 1000])
def test_the_owned_end_tail_is_the_next_carry(halo):
    """Whatever the halo (none, one byte, less than a view, a whole view,
    more than the stream holds), the head of each buffer is the tail the
    buffer before did not own."""
    sizes = [300, 300, 300, 300]
    _stream, rows = _rows(sizes, halo, 40, seed=halo)
    for (prev, _b, own_end, _lo, _e), (buf, *_rest) in zip(rows, rows[1:]):
        tail = prev[own_end:]
        assert len(tail) == min(halo, len(prev))
        np.testing.assert_array_equal(buf[: len(tail)], tail)
        assert len(buf) == len(tail) + 300
