"""Where one fused 24 MiB count window's device time goes, piece by piece.

Run on the chip (one process, through the builder's tool):
``python tools/chip_window_breakdown.py``. Jits the pieces of
``tpu/checker.count_window_tokens`` alone — unpack, LZ77 resolve, the 32 MiB
check with and without the funnel, assembly + check, the whole program — on
one real middle window of a generated BAM and times each with
``block_until_ready`` (first call = compile + run, then three steady runs).
Prints one JSON line per piece and appends them to
``chiprun_out/breakdown.jsonl``. A timing aid, not a benchmark: PERF.md
(PR 22) holds the first reading.
"""
import json, sys, time
from pathlib import Path
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import jax, jax.numpy as jnp, numpy as np
from spark_bam_tpu.core.platform import enable_compile_cache
enable_compile_cache()
from spark_bam_tpu.benchmarks.synth import synth_bam, synthetic_fixture
from spark_bam_tpu.bgzf.flat import inflate_blocks
from spark_bam_tpu.bgzf.index_blocks import blocks_metadata
from spark_bam_tpu.core.channel import open_channel
from spark_bam_tpu.tpu import checker as ck
from spark_bam_tpu.tpu.inflate import (_resolve_packed, _unpack_tokens, tokenize_group, window_plan)
from spark_bam_tpu.tpu.stream_check import pad_contig_lengths
from spark_bam_tpu.bam.header import read_header
DATA = ROOT / ".smoke_data"; DATA.mkdir(exist_ok=True)
OUT = ROOT / "chiprun_out"; OUT.mkdir(exist_ok=True)
log = open(OUT / "breakdown.jsonl", "a")
def emit(o):
    line = json.dumps(o); print(line, flush=True); log.write(line + "\n"); log.flush()
seed = synthetic_fixture(cache_dir=DATA)
path = DATA / "bd.bam"; synth_bam(path, 48 << 20, fixture=seed)
metas = list(blocks_metadata(path)); groups = window_plan(metas, 24 << 20)
g = groups[1]  # a full middle window
W, H = 32 << 20, 4 << 20
hdr = read_header(path); lens = jnp.asarray(pad_contig_lengths(np.array(hdr.contig_lengths.lengths_list(), np.int32))); nc = jnp.int32(2)
with open_channel(path) as ch:
    packed, out_lens, b = tokenize_group(ch, g)
    view = inflate_blocks(ch, g, threads=8)
n = int(out_lens.sum())
emit({"blocks": len(g), "b_pad": int(len(out_lens)), "bytes": n, "kind": jax.devices()[0].device_kind})
def timeit(name, fn, reps=3):
    t = time.perf_counter(); r = fn(); jax.block_until_ready(r); first = time.perf_counter() - t
    ts = []
    for _ in range(reps):
        t = time.perf_counter(); r = fn(); jax.block_until_ready(r); ts.append(round(time.perf_counter() - t, 4))
    emit({"piece": name, "first_s": round(first, 2), "steady_s": ts})
    return r
packed_dev = jnp.asarray(packed); ol = jnp.asarray(out_lens.astype(np.int32))
unpack = jax.jit(_unpack_tokens)
timeit("unpack_tokens", lambda: unpack(packed_dev))
res = timeit("resolve_packed(unpack+lz77)", lambda: _resolve_packed(packed_dev))
emit({"rounds": int(res[1])})
carry = jnp.zeros(H, jnp.uint8)
padded = np.zeros(W + ck.PAD, np.uint8); padded[:n] = view.data[:n]; padded_dev = jnp.asarray(padded)
cw = ck.make_count_window(W, 10, "xla", funnel=True)
r = timeit("count_window(xla,funnel) 32MiB", lambda: cw(padded_dev, lens, nc, jnp.int32(n), jnp.bool_(False), jnp.int32(0), jnp.int32(n - H)))
emit({"count": int(r["count"]), "esc": int(r["esc_count"])})
cw0 = ck.make_count_window(W, 10, "xla", funnel=False)
timeit("count_window(xla,no funnel) 32MiB", lambda: cw0(padded_dev, lens, nc, jnp.int32(n), jnp.bool_(False), jnp.int32(0), jnp.int32(n - H)))
asm = jax.jit(lambda resolved, ol: ck._count_from_planes(resolved, jnp.int32(0), ol, carry, lens, nc, jnp.int32(0), jnp.int32(n), jnp.bool_(False), jnp.int32(0), jnp.int32(n - H), window=W, halo=H, reads_to_check=10, flags_impl="xla", pallas_interpret=False, funnel=True))
timeit("assemble+count (from resolved planes)", lambda: asm(res[0], ol))
kt = ck.make_count_window_tokens(W, H, 10, "xla", funnel=True)
r = timeit("count_window_tokens (whole fused window)", lambda: kt(packed_dev, ol, carry, lens, nc, jnp.int32(0), jnp.int32(n), jnp.bool_(False), jnp.int32(0), jnp.int32(n - H)))
emit({"count": int(r["count"])})
