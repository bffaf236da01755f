"""Device record parser vs the sequential codec on real fixture records."""

import numpy as np
import pytest

from spark_bam_tpu.bam.header import read_header
from spark_bam_tpu.bam.record import BamRecord
from spark_bam_tpu.bgzf.flat import flatten_file
from spark_bam_tpu.bam.index_records import read_records_index
from spark_bam_tpu.tpu.parser import interval_flag_filter, parse_flat_records


@pytest.fixture(scope="module")
def bam2(bam2_like):
    return bam2_like.path


@pytest.fixture(scope="module")
def parsed(bam2):
    flat = flatten_file(bam2)
    records = read_records_index(str(bam2) + ".records")
    starts = np.array(
        [flat.flat_of_pos(p.block_pos, p.offset) for p in records], dtype=np.int64
    )
    return flat, starts, parse_flat_records(flat.data, starts)


def test_parser_matches_codec(bam2_like, parsed):
    flat, starts, batch = parsed
    assert len(batch) == (bam2_like.records or len(starts)) > 500
    rng = np.random.default_rng(3)
    for i in rng.integers(0, len(starts), 100).tolist():
        rec, _ = BamRecord.decode(flat.data, int(starts[i]))
        assert batch.columns["ref_id"][i] == rec.ref_id
        assert batch.columns["pos"][i] == rec.pos
        assert batch.columns["flag"][i] == rec.flag
        assert batch.columns["mapq"][i] == rec.mapq
        assert batch.columns["l_seq"][i] == rec.read_length
        assert batch.columns["n_cigar"][i] == len(rec.cigar)
        assert batch.columns["next_ref_id"][i] == rec.next_ref_id
        assert batch.columns["next_pos"][i] == rec.next_pos
        assert batch.columns["tlen"][i] == rec.tlen
        assert batch.columns["ref_span"][i] == rec.reference_span()
    assert batch.columns["span_exact"].all()


def test_interval_filter_matches_load_api(bam2_like, parsed):
    import jax.numpy as jnp

    flat, starts, batch = parsed
    # Whole-contig interval: the golden count is 2450 (50 unmapped
    # excluded); of a generated file, what the codec says is mapped there.
    golden = bam2_like.on_contig
    if golden is None:
        decoded = [BamRecord.decode(flat.data, int(s))[0] for s in starts]
        golden = sum(r.ref_id == 0 and not r.flag & 4 for r in decoded)
        assert 0 < golden < len(starts)
    intervals = jnp.asarray(np.array([[0, 0, 100_000_000]], dtype=np.int32))
    mask = np.asarray(
        interval_flag_filter(
            {k: jnp.asarray(v) for k, v in batch.columns.items()},
            intervals,
            jnp.int32(0),
            jnp.int32(0),
        )
    )
    assert int(mask.sum()) == golden
    # Flag filter: forbidding the unmapped bit changes nothing here; requiring
    # read-paired keeps only paired reads.
    mask2 = np.asarray(
        interval_flag_filter(
            {k: jnp.asarray(v) for k, v in batch.columns.items()},
            intervals,
            jnp.int32(0x1),
            jnp.int32(0),
        )
    )
    paired = (batch.columns["flag"] & 1) == 1
    assert int(mask2.sum()) == int((mask & paired).sum())


def test_lazy_payloads_match_codec(bam2, parsed):
    flat, starts, batch = parsed
    rec, _ = BamRecord.decode(flat.data, int(starts[7]))
    assert batch.name(7) == rec.read_name
    assert batch.seq(7) == rec.seq
    assert batch.qual(7) == rec.qual


def test_shape_bucketing_bounds_compiles(bam2):
    """Streaming windows vary in size every step; the parser must bucket
    both buffer and row-count shapes to powers of two so the jit compiles
    O(log) variants, not one per window."""
    from spark_bam_tpu.tpu.parser import parse_records

    flat = flatten_file(bam2)
    records = read_records_index(str(bam2) + ".records")
    starts = np.array(
        [flat.flat_of_pos(p.block_pos, p.offset) for p in records[:40]],
        dtype=np.int64,
    )
    early = starts[starts < 90_000]
    # Different buffer lengths in the same pow2 bucket and different row
    # counts in the same pow2 bucket: the second call must be a full
    # cache hit (order-independent: the first call may itself hit).
    parse_flat_records(flat.data[:100_000], early[:5])
    mid = parse_records._cache_size()
    parse_flat_records(flat.data[:120_000], early[:7])
    assert parse_records._cache_size() == mid
