"""k-bit blockwise-quantized KV cache (kernels/kv_dequant.py + the kvq
branches of models/attention.py + the serving slot pool over packed leaves).

Three layers of guarantees:

  (a) the codec: encode -> dequant round-trips within the data type's
      expected error, the words are plane-major, and the Pallas kernel
      (interpret mode) matches the jnp oracle exactly;
  (b) the model: decode with a k-bit cache stays within a stated
      per-token logit tolerance of the bf16-cache oracle (teacher-forced,
      so the check is deterministic), and the static Engine and the
      continuous Server are token-identical at the SAME kv_bits — cache
      quantization is per token-row, so batching composition cannot
      change outputs;
  (c) the pool: slot alloc/free/re-prefill invariants hold over packed
      leaves, and the 4-bit pool resides in >= 3x fewer HBM bytes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.data import synthetic
from repro.kernels import kv_dequant as kd
from repro.models import lm
from repro.serving import (
    KV_LOGIT_TOL,
    Engine,
    Server,
    SlotKVCache,
    kv_oracle_logit_gap,
)

CFG = get_arch("tiny-160k")


@pytest.fixture(scope="module")
def params():
    return lm.init_params(jax.random.PRNGKey(0), CFG)


def _prompts(batch, length, seed=1):
    return np.asarray(
        synthetic.ZipfMarkov(CFG.vocab_size).sample(
            jax.random.PRNGKey(seed), batch, length
        )
    )


# -------------------------------------------------------------------------
# (a) the codec
# -------------------------------------------------------------------------

@pytest.mark.parametrize("bits,dtype,tol", [
    (4, "float", 0.30), (4, "int", 0.30), (8, "float", 0.05),
    (8, "int", 0.02), (4, "dynamic", 0.25),
])
def test_encode_dequant_roundtrip(bits, dtype, tol):
    spec = kd.KVQuantSpec(bits=bits, block_size=64, dtype_name=dtype)
    x = jax.random.normal(jax.random.PRNGKey(bits), (13, 7, 64)) * 0.4
    packed, scales = kd.encode_rows(x, spec)
    assert packed.dtype == jnp.uint32 and scales.dtype == jnp.bfloat16
    assert packed.shape == (13, 7, 64 * bits // 32)
    y = kd.dequant_rows_ref(packed, scales, spec, 64).astype(jnp.float32)
    rel = float(jnp.sqrt(jnp.mean((y - x) ** 2)) / jnp.sqrt(jnp.mean(x**2)))
    assert rel < tol, (bits, dtype, rel)


@pytest.mark.parametrize("bits,dtype", [(4, "float"), (8, "int"),
                                        (4, "dynamic")])
def test_pallas_kernel_matches_oracle(bits, dtype):
    spec = kd.KVQuantSpec(bits=bits, block_size=32, dtype_name=dtype)
    x = jax.random.normal(jax.random.PRNGKey(7), (37, 96))
    packed, scales = kd.encode_rows(x, spec)
    ref = kd.dequant_rows_ref(packed, scales, spec, 96)
    ker = kd.dequant_rows_pallas(packed, scales, spec, 96,
                                 interpret=True, tile_rows=16)
    assert bool(jnp.all(ref == ker))  # same math, bit-for-bit


def test_words_are_plane_major():
    """Code p of word w in a token row is feature p * W + w (W words a
    row): each shift/mask plane of the words is W consecutive features."""
    bits, feat = 4, 16
    W = feat // 8
    codes = jnp.arange(feat, dtype=jnp.uint32)[None, :]   # feature f -> f
    by_hand = [sum((p * W + w) << (4 * p) for p in range(8))
               for w in range(W)]
    words = kd.pack_rows(codes, bits)
    assert words.tolist() == [by_hand]
    assert bool(jnp.all(kd.unpack_rows(words, bits) == codes))
    spec = kd.KVQuantSpec(bits=bits, block_size=feat)
    vals = kd.dequant_rows_ref(jnp.asarray([by_hand], jnp.uint32),
                               jnp.ones((1, 1), jnp.bfloat16), spec, feat,
                               out_dtype=jnp.float32)
    assert bool(jnp.all(vals[0] == kd.kv_codebook(spec)))


@pytest.mark.parametrize("bits,dtype", [(4, "float"), (8, "int"),
                                        (4, "dynamic")])
def test_roundtrip_same_values_as_interleaved_words(bits, dtype):
    """The word order is storage only: the values a token row decodes to
    are those of the interleaved order (core/packing) on the same codes."""
    from repro.core import packing

    spec = kd.KVQuantSpec(bits=bits, block_size=32, dtype_name=dtype)
    x = jax.random.normal(jax.random.PRNGKey(5), (9, 96)) * 0.7
    packed, scales = kd.encode_rows(x, spec)
    codes = kd.unpack_rows(packed, bits)
    inter = packing.unpack(packing.pack(codes, bits), bits, 96)
    vals = jnp.take(kd.kv_codebook(spec), inter.astype(jnp.int32), axis=0)
    old = (vals.reshape(9, 3, 32)
           * scales[..., None].astype(jnp.float32)).reshape(9, 96)
    new = kd.dequant_rows_ref(packed, scales, spec, 96)
    assert bool(jnp.all(new == old.astype(jnp.bfloat16)))


@pytest.mark.parametrize("dtype", ["float", "int"])
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("feat", [512, 1024])
def test_pallas_kernel_matches_oracle_at_serving_widths(feat, bits, dtype):
    """qwen2-7b (4 KV heads x 128) and deepseek-coder-33b (8 x 128) rows,
    at the kernel's own tile rows and a row count no tile divides."""
    spec = kd.KVQuantSpec(bits=bits, block_size=64, dtype_name=dtype)
    rows = kd.kv_tile_rows(spec, feat) + 40
    x = jax.random.normal(jax.random.PRNGKey(feat + bits), (rows, feat))
    packed, scales = kd.encode_rows(x, spec)
    ref = kd.dequant_rows_ref(packed, scales, spec, feat)
    ker = kd.dequant_rows_pallas(packed, scales, spec, feat, interpret=True)
    assert bool(jnp.all(ref == ker))


def test_block_size_clamps_to_feature_dim():
    spec = kd.KVQuantSpec(bits=4, block_size=64)
    bs, n_blocks, n_words = kd.kv_layout(spec, 32)  # tiny heads: feat < bs
    assert bs == 32 and n_blocks == 1 and n_words == 4
    # non-dividing block size falls back to the gcd
    assert kd.kv_layout(kd.KVQuantSpec(4, 48), 64)[0] == 16


def test_quantile_codebook_rejected():
    import dataclasses

    with pytest.raises(ValueError):
        CFG.with_kv_quant(4, dtype="quantile")
    # even a hand-built config cannot smuggle one past kv_spec
    smuggled = dataclasses.replace(CFG, kv_bits=4, kv_dtype="quantile")
    with pytest.raises(ValueError):
        kd.kv_spec(smuggled)


# -------------------------------------------------------------------------
# (b) model parity vs the bf16-cache oracle
# -------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
def test_logit_parity_vs_bf16_oracle(params, bits):
    """The shared teacher-forced harness (serving.kv_oracle_logit_gap —
    also the bench's acceptance check) stays within the stated bound."""
    prompts = _prompts(2, 10, seed=3)
    gap, _ = kv_oracle_logit_gap(params, CFG.with_kv_quant(bits), prompts, 8)
    assert gap < KV_LOGIT_TOL[bits], (bits, gap)
    # more bits must not be (meaningfully) worse than fewer
    if bits == 8:
        assert gap < 0.5 * KV_LOGIT_TOL[4]


@pytest.mark.parametrize("bits", [8, 4])
def test_server_matches_engine_at_same_kv_bits(params, bits):
    """Static vs continuous at the SAME cache precision is exact: the
    bucketed prefill-into-slot scatter of packed leaves must not change
    what each request's rows contain."""
    cfg_q = CFG.with_kv_quant(bits)
    B, S, N = 3, 9, 6
    prompts = _prompts(B, S, seed=11)
    ref = np.asarray(
        Engine(params, cfg_q, max_seq_len=S + N).generate(
            jnp.asarray(prompts), N)
    )
    srv = Server(params, cfg_q, num_slots=2, max_seq_len=S + N)
    ids = [srv.submit(prompts[b], N, arrival_time=0.7 * b) for b in range(B)]
    res = srv.run_until_drained()
    for b, rid in enumerate(ids):
        assert res[rid] == list(ref[b]), (bits, b)


# -------------------------------------------------------------------------
# (c) the slot pool over packed leaves
# -------------------------------------------------------------------------

def test_pool_leaves_are_packed_and_small():
    pool16 = SlotKVCache(CFG, 4, 32)
    pool4 = SlotKVCache(CFG.with_kv_quant(4), 4, 32)
    leaves = {getattr(k, "key", None)
              for p, _ in jax.tree_util.tree_leaves_with_path(pool4.caches)
              for k in p if getattr(k, "key", None)}
    assert {"k_packed", "k_scales", "v_packed", "v_scales", "pos"} <= leaves
    assert "k" not in leaves and "v" not in leaves
    ratio = pool16.kv_bytes()["total"] / pool4.kv_bytes()["total"]
    assert ratio >= 3.0, ratio


def test_slot_recycling_with_packed_leaves(params):
    """More requests than slots at kv_bits=4: alloc/free/re-prefill over
    packed leaves, invariants checked live at every emitted token."""
    cfg_q = CFG.with_kv_quant(4)
    n_req, n_slots, N = 6, 2, 5
    prompts = [_prompts(1, L, seed=40 + i)[0]
               for i, L in enumerate([6, 9, 12, 7, 10, 5])]
    srv = Server(params, cfg_q, num_slots=n_slots, max_seq_len=20)

    def check(_rid, tok):
        assert srv.pool.n_free + srv.pool.n_active == n_slots
        assert sorted(srv.scheduler.running) == [
            s for s in range(n_slots) if srv.pool.active[s]]
        assert 0 <= tok < CFG.vocab_size

    ids = [srv.submit(p, N, arrival_time=1.5 * i, on_token=check)
           for i, p in enumerate(prompts)]
    res = srv.run_until_drained()
    assert srv.pool.n_free == n_slots
    assert all(len(res[rid]) == N for rid in ids)
    # a freed slot was re-prefilled (6 requests through 2 slots)
    assert n_req > n_slots


def test_append_quantize_roundtrip_in_cache(params):
    """write_cache_decode's append-quantize stores what dequant_cache_kv
    reads back, within codec error, at both 4 and 8 bits."""
    from repro.models import attention as attn

    for bits in (8, 4):
        cfg_q = CFG.with_kv_quant(bits)
        kvq = kd.kv_spec(cfg_q)
        B, S_c, K, Dh = 2, 6, CFG.n_kv_heads, CFG.head_dim
        cache = attn.init_kv_cache(cfg_q, B, S_c, kvq=kvq)
        ks = jax.random.normal(jax.random.PRNGKey(1), (S_c, B, K, Dh))
        vs = jax.random.normal(jax.random.PRNGKey(2), (S_c, B, K, Dh))
        for t in range(S_c):
            cache = attn.write_cache_decode(cache, ks[t], vs[t],
                                            jnp.int32(t), kvq=kvq)
        k_rt, v_rt = attn.dequant_cache_kv(cache, kvq, K, Dh)
        k_true = ks.transpose(1, 0, 2, 3)
        rel = float(jnp.sqrt(jnp.mean((k_rt.astype(jnp.float32) - k_true) ** 2))
                    / jnp.sqrt(jnp.mean(k_true**2)))
        assert rel < (0.05 if bits == 8 else 0.30), (bits, rel)
        assert np.array_equal(np.asarray(cache["pos"]), np.arange(S_c))
