"""The host buffers of the count's windows (``tpu/inflate.FRAMES``): a
window is inflated into a frame that is its padded operand too, and the
frames are kept from one window and one pass to the next. Held to the plain
pipeline (one fresh array a group) and to the count itself, on the CPU."""

import numpy as np
import pytest

from spark_bam_tpu.core.config import Config
from spark_bam_tpu.tpu import inflate
from spark_bam_tpu.tpu.checker import PAD
from spark_bam_tpu.tpu.inflate import InflatePipeline
from spark_bam_tpu.tpu.stream_check import StreamChecker, halo_windows

WINDOW = 1 << 20
HALO = 64 << 10


@pytest.fixture(scope="module")
def bam(tmp_path_factory):
    from spark_bam_tpu.benchmarks.synth import synth_bam

    path = tmp_path_factory.mktemp("frames") / "mid.bam"
    synth_bam(path, 3 << 20)
    return path


@pytest.fixture()
def frames(monkeypatch):
    """A free list of the test's own, so that no other test's frames show."""
    own = inflate._Frames()
    monkeypatch.setattr(inflate, "FRAMES", own)
    return own


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_framed_views_are_the_plain_views_with_zeros_behind(bam, frames, depth):
    size = HALO + 2 * WINDOW + PAD
    plain = list(InflatePipeline(bam, WINDOW, depth=depth))
    framed = list(InflatePipeline(bam, WINDOW, depth=depth).frames(HALO, size))
    assert len(plain) == len(framed) > 2
    for a, b in zip(plain, framed):
        np.testing.assert_array_equal(a.data, b.data)
        assert a.at_eof == b.at_eof
        assert len(b.frame) == size and b.lead == HALO
        assert np.shares_memory(b.data, b.frame)
        np.testing.assert_array_equal(
            b.frame[HALO: HALO + len(b.data)], b.data)
        assert not b.frame[HALO + len(b.data):].any()


def test_a_group_too_large_for_the_frame_is_refused(bam, frames):
    with pytest.raises(ValueError, match="does not fit a frame"):
        list(InflatePipeline(bam, WINDOW).frames(HALO, WINDOW // 2))


def test_a_dirty_frame_is_zeroed_behind_the_window(bam, frames):
    size = HALO + 2 * WINDOW + PAD
    for _ in range(3):
        dirty = np.full(size, 0xAB, dtype=np.uint8)
        frames.give([dirty])
    views = list(InflatePipeline(bam, WINDOW, depth=1).frames(HALO, size))
    assert any(v.frame is dirty for v in views)
    for v in views:
        assert not v.frame[HALO + len(v.data):].any()


def test_halo_windows_lays_the_carry_in_the_frame(bam, frames):
    size = HALO + 2 * WINDOW + PAD
    plain = list(halo_windows(InflatePipeline(bam, WINDOW), HALO, 100))
    views = []

    def tap():
        for view in InflatePipeline(bam, WINDOW).frames(HALO, size):
            views.append(view)
            yield view

    rows = halo_windows(tap(), HALO, 100)
    for want, got, view in zip(plain, rows, views):
        np.testing.assert_array_equal(want[0], got[0])
        assert want[1:] == got[1:]
        n = len(got[0])
        assert np.shares_memory(got[0], view.frame)
        # The padded operand in place: the window, then zeros.
        start = view.lead + len(view.data) - n
        operand = view.frame[start: start + 2 * WINDOW + PAD]
        np.testing.assert_array_equal(operand[:n], want[0])
        assert len(operand) == 2 * WINDOW + PAD and not operand[n:].any()


def test_the_free_list_keeps_one_size_and_no_more_than_keep(frames):
    for _ in range(frames.KEEP + 3):
        frames.give([np.empty(100, dtype=np.uint8)])
    assert len(frames._free) == frames.KEEP
    frames.give([np.empty(200, dtype=np.uint8)])
    assert [len(f) for _key, (f,), _note in frames._free] == [200]
    (frame,), _note = frames.take([((200,), np.uint8)])
    assert len(frame) == 200 and not frames._free
    (frame,), _note = frames.take([((300,), np.uint8)])
    assert len(frame) == 300


def test_count_passes_reuse_their_frames_and_agree(bam, frames, monkeypatch):
    cfg = dict(window_uncompressed=WINDOW, halo=HALO)
    want = StreamChecker(bam, Config(), use_device=False, **cfg).count_reads()
    made = []
    real_empty = inflate.np.empty

    def empty(shape, *a, **k):
        if isinstance(shape, tuple) and shape[0] > WINDOW:
            made.append(shape)
        return real_empty(shape, *a, **k)

    monkeypatch.setattr(inflate.np, "empty", empty)
    assert StreamChecker(bam, Config(), **cfg).count_reads() == want
    first = len(made)
    assert 1 <= first <= frames.KEEP and len(set(made)) == 1
    assert 1 <= len(frames._free) <= frames.KEEP
    # What a pass left in its frames must not show in the next.
    for _key, (frame,), _note in frames._free:
        frame[:] = 0xAB
    assert StreamChecker(bam, Config(), **cfg).count_reads() == want
    assert len(made) == first


def test_many_threads_take_and_give_and_no_array_is_in_two_hands(frames):
    """The mesh takes blocks on its assembly thread and hands them back from
    the feeding thread: more threads than cores take an array, write their
    own mark over it, look again, and give it back; an array handed to two
    at once would show the other's mark, and the kept set never holds more
    than it was told to keep."""
    import sys
    import threading
    import time

    keep, spec = 4, [((4096,), np.uint8)]
    stop = time.monotonic() + 1.0
    faults: list = []
    rounds = []

    def worker(mark: int):
        n = 0
        while time.monotonic() < stop and not faults:
            (a,), _note = frames.take(spec)
            a[:] = mark
            time.sleep(0)
            if not (a == mark).all():
                faults.append(("shared", mark))
            frames.give([a], keep=keep, note=mark)
            if len(frames._free) > keep:
                faults.append(("over", len(frames._free)))
            n += 1
        rounds.append(n)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(m,))
                   for m in range(1, 33)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert not faults and len(rounds) == 32 and min(rounds) > 0
    assert 0 < len(frames._free) <= keep
