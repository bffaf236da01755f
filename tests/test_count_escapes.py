"""The one-chip count on files with records longer than the halo: the
window program lists the owned candidates whose chains ran past its buffer,
the stream resolves them on the host from the bytes the following windows
bring, and the pass does not start over.

Files come from the benchmark's ultra-long generator at kilobyte sizes
(``bench/generators/ultralong.py``: ordinary reads of a few kilobytes and
"whales" of 100-200 KB) and are counted through
``StreamChecker(path, Config(), window_uncompressed=…, halo=…)`` with
windows of four BGZF members and a 64 KiB halo, so that escapes happen in
kilobytes. The oracle is the generator's own index; the NumPy engine runs
the same windows beside it.
"""

import numpy as np
import pytest

from spark_bam_tpu.core.config import Config
from spark_bam_tpu.tpu import checker
from spark_bam_tpu.tpu.stream_check import StreamChecker
from tests.test_host_fed_count import _observed

MEMBER = 0xFF00
WINDOW = 4 * MEMBER          # the stream's groups: four whole members
HALO = 64 << 10
OWN_END_1 = WINDOW - HALO    # the first window owns [header, 195,584)
OWN_END_2 = 2 * WINDOW - HALO
CFG = dict(window_uncompressed=WINDOW, halo=HALO)

PARAMS = {
    "read_length_n50": 2000, "read_length_sigma": 0.5,
    "read_length_min": 400, "read_length_max": 4000,
    "op_every": 15, "coverage": 30, "contig": 0, "origin": 10_000_000,
    "quality_min": 1, "quality_max": 50, "read_group": "3f9a2c1e7b5d4e60",
    "whales": 1, "whale_length_min": 60_000, "whale_length_max": 80_000,
    "whale_start_end": OWN_END_1, "whale_start_span": 8192,
}

#: name → (parameters changed, file size): where the whale lies against the
#: windows' owned ends, and what of the file follows it.
CASES = {
    # The whale ends inside the first buffer; its chain does not.
    "before-the-owned-end": (
        {"whale_start_end": 145_000, "whale_length_max": 62_000}, 800_000),
    # Owned by the first window, ending 40-80 KB past its buffer.
    "across-the-owned-end": ({}, 800_000),
    # In the first window's halo: owned by the second, reached by the
    # chains of the first's last records.
    "after-the-owned-end": (
        {"whale_start_end": OWN_END_1 + 8192 + 64}, 800_000),
    # In the halo of the last window but one, so owned by the last.
    "in-the-last-window": (
        {"whale_start_end": OWN_END_2 + 24_000}, 700_000),
    # Two whales of 177-212 KB in a row: the first one's chain ends past
    # the second window's buffer too, and the second whale is the second
    # window's own escape while the first is still pending.
    "two-in-a-row": (
        {"whales": 2, "whale_length_min": 100_000,
         "whale_length_max": 120_000}, 1_000_000),
    # One whale of 700 KB, longer than the two windows the ring holds when
    # its escape is read: it stays pending while further windows arrive.
    "longer-than-two-windows": (
        {"whale_length_min": 400_000, "whale_length_max": 420_000},
        1_300_000),
    # The file ends with the whale or a few records after it: the chains
    # resolve at the end of the file, not by their tenth record.
    "the-file-ends-inside-the-lookahead": ({}, 300_000),
}


def _generate(tmp_path, case: str, seed: int = 2 ** 31 + 31):
    from bench.generators import ultralong

    changed, size = CASES[case]
    path = tmp_path / f"{case}.bam"
    index = ultralong.generate({**PARAMS, **changed}, seed, size, path)
    return path, index


@pytest.mark.parametrize("case", sorted(CASES))
def test_escapes_resolve_on_the_host_and_the_pass_does_not_start_over(
        case, tmp_path):
    path, index = _generate(tmp_path, case)
    halo_exceeded = np.diff(np.append(
        index["record_starts"], index["uncompressed_bytes"])).max() > HALO
    assert halo_exceeded and len(index["whale_starts"]) >= 1
    got, counters, spans = _observed(
        StreamChecker(path, Config(), **CFG).count_reads)
    assert got == len(index["record_starts"])
    assert got == StreamChecker(
        path, Config(), use_device=False, **CFG).count_reads()
    assert not counters.get("check.count_escape_retries")
    assert not counters.get("check.escape_overflows")
    assert (counters["check.escape_candidates"]
            == counters["check.escape_resolved"] >= 1)
    assert spans["check.escape_resolve"] >= 1
    # Nothing of the spans path ran.
    assert not counters.get("check.escaped")
    assert not counters.get("check.deferred")


def test_the_whale_itself_is_among_the_candidates(tmp_path):
    """The case the benchmark's cell is built on: the first window owns the
    whale, which ends past its buffer, so its own chain and those of the
    records just before it escape: about ten candidates, all record
    starts, all resolved."""
    path, index = _generate(tmp_path, "across-the-owned-end")
    (whale,) = index["whale_starts"]
    assert OWN_END_1 - 8192 <= whale < OWN_END_1
    _got, counters, _ = _observed(
        StreamChecker(path, Config(), **CFG).count_reads)
    assert 2 <= counters["check.escape_candidates"] <= 12


def test_the_funnel_off_count_lists_its_escapes_too(tmp_path):
    path, index = _generate(tmp_path, "across-the-owned-end")
    got, counters, _ = _observed(
        StreamChecker(path, Config(funnel="off"), **CFG).count_reads)
    assert got == len(index["record_starts"])
    assert not counters.get("check.count_escape_retries")
    assert (counters["check.escape_candidates"]
            == counters["check.escape_resolved"] >= 1)


def test_a_list_overflow_still_starts_over_and_is_exact(
        tmp_path, monkeypatch):
    """One slot for some ten escapes: the window reports the overflow and
    the whole file goes through the spans path, as every escape did
    before."""
    path, index = _generate(tmp_path, "across-the-owned-end")
    monkeypatch.setattr(checker, "ESCAPE_LIST", 1)
    got, counters, _ = _observed(
        StreamChecker(path, Config(), **CFG).count_reads)
    assert got == len(index["record_starts"])
    assert counters["check.count_escape_retries"] == 1
    assert counters["check.escape_overflows"] == 1
    assert counters["check.escaped"] >= 2  # the spans path's own count


def test_a_chain_beyond_the_lookahead_cap_starts_over(tmp_path):
    """``(reads_to_check + 2) * max_read_size`` of lookahead is the most a
    candidate may ask for (the mesh's cap): past it the pass starts over
    rather than hold the rest of the file."""
    path, index = _generate(tmp_path, "longer-than-two-windows")
    config = Config(max_read_size=4096)  # cap: 48 KiB, a whale is 700 KB
    got, counters, _ = _observed(
        StreamChecker(path, config, **CFG).count_reads)
    assert got == len(index["record_starts"])
    assert counters["check.count_escape_retries"] == 1
    assert counters["check.escape_candidates"] >= 1


def test_the_count_keeps_no_more_buffers_than_its_ring(
        tmp_path, monkeypatch):
    """The host buffers a window's escapes would resolve from are those of
    the windows in flight: ``ring_depth`` + 1 at most."""
    from spark_bam_tpu.tpu.stream_check import _CountEscapes

    path, _index = _generate(tmp_path, "two-in-a-row")
    seen = []
    real = _CountEscapes.settle

    def settle(self, escaped, ring, at_eof):
        seen.append(len(ring))
        return real(self, escaped, ring, at_eof)

    monkeypatch.setattr(_CountEscapes, "settle", settle)
    StreamChecker(path, Config(), **CFG).count_reads()
    assert seen and max(seen) == Config().ring_depth + 1
