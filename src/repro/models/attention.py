"""Attention: GQA with RoPE, sliding windows, logit softcaps, QK-norm.

Two execution regimes:

* train / prefill — ``flash_attention``: q is processed in statically
  sliced chunks (python loop, so causal/window KV ranges are exact static
  slices — no wasted FLOPs on fully-masked blocks), with an online-softmax
  lax.scan over KV chunks inside.  The 32k x 32k score matrix never
  materializes.
* decode — ``decode_attention_partial`` computes flash-decoding partial
  (max, denom, weighted-values) statistics over a LOCAL slice of the KV
  cache; ``combine_partials`` merges them (psum'd over the `model` axis by
  the sharded wrapper in models/sharding.py).  This makes the KV cache
  sequence-shardable with no head-count divisibility constraints.

The KV cache is a dict {"k","v": [B, S_c, K, Dh], "pos": [S_c] int32} where
``pos[slot]`` is the absolute position held in that slot (-1 = empty).
Full caches write slot=position; sliding-window caches are ring buffers
(slot = position %% window) — the pos array makes masking identical for
both and is what lets danube/gemma2-local decode with O(window) memory.

Continuous batching generalizes both `pos` arguments from a shared scalar
to a PER-ROW vector [B]: ``pos`` may be [B] (each batch row decodes at its
own absolute position; -1 = idle row) and the cache's ``pos`` array may be
[B, S_c] (per-slot occupancy, docs/serving.md).  Every decode entry point
below dispatches on ``pos.ndim`` so the legacy scalar path is untouched.

k-bit caches (cfg.kv_bits in {4, 8}) swap the dense k/v leaves for packed
codes + per-block absmax scales (kernels/kv_dequant.py defines the layout):
{"k_packed","k_scales","v_packed","v_scales": [B, S_c, ...], "pos": ...}.
Writes quantize the new token inside the jitted step (append-quantize);
reads dequantize the local cache slice before the same masked partial
math, so the pos/idle-row semantics above hold verbatim.  Every entry
point takes an optional ``kvq`` KVQuantSpec and dispatches on it plus the
cache keys — a None spec is byte-for-byte the legacy bf16 path.

Packed caches SEQUENCE-SHARD exactly like dense ones: codes and scales
are per-token feature-dim state, so splitting the slot axis never splits
a block or a code word.  models/sharding.Sharder.decode_attn_fn reuses
``encode_rows``/``dequant_rows`` and the partial/combine entry points
below inside its shard_map body — this module stays mesh-agnostic.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import kv_dequant
from repro.models.layers import apply_rope, dense, init_dense, rmsnorm, softcap

NEG_INF = -1e30


def _is_quantized_cache(cache: dict) -> bool:
    return "k_packed" in cache


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def init_attention(key, cfg) -> dict:
    D, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], D, H * Dh, bias=cfg.qkv_bias),
        "wk": init_dense(ks[1], D, K * Dh, bias=cfg.qkv_bias),
        "wv": init_dense(ks[2], D, K * Dh, bias=cfg.qkv_bias),
        "wo": init_dense(ks[3], H * Dh, D, scale=(H * Dh) ** -0.5 / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.zeros((Dh,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.zeros((Dh,), jnp.float32)}
    return p


def project_qkv(params, x, cfg, positions, constrain=None):
    """x [B,S,D] -> q [B,S,H,Dh], k,v [B,S,K,Dh] with RoPE applied.

    `constrain` (the Sharder callback) pins the head layout BEFORE the
    norm/RoPE math: under tensor parallelism the projections come out of
    column-parallel weights feature-sharded, and re-sharding to heads (or
    replicated, when the head count does not divide TP) here keeps the
    rotation arithmetic shard-local — GSPMD resolving the layout inside
    RoPE's split/concat instead is both slower and numerically fragile."""
    B, S, _ = x.shape
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mm = cfg.matmul_mode
    q = dense(params["wq"], x, mode=mm).reshape(B, S, H, Dh)
    k = dense(params["wk"], x, mode=mm).reshape(B, S, K, Dh)
    v = dense(params["wv"], x, mode=mm).reshape(B, S, K, Dh)
    if constrain is not None:
        q = constrain(q, "heads")
        k = constrain(k, "kv_heads")
        v = constrain(v, "kv_heads")
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"]["scale"])
        k = rmsnorm(k, params["k_norm"]["scale"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# --------------------------------------------------------------------------
# flash attention (train / prefill)
# --------------------------------------------------------------------------

def _chunk_attend(q, k, v, q_pos, k_pos, *, causal, window, cap, sm_scale):
    """One (q-chunk, kv-chunk) tile: masked scores + softmax pieces.

    q [B,cq,K,G,Dh]; k,v [B,ck,K,Dh]; returns (m [B,K,G,cq], p@v, sum_p).
    Scores accumulate in f32 (MXU preferred type); p is cast back to the
    kv dtype for the pv matmul (standard flash practice).
    """
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32
    )
    s = s * sm_scale
    if cap:
        s = cap * jnp.tanh(s / cap)
    valid = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        valid &= k_pos[None, :] <= q_pos[:, None]
    if window:
        valid &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(valid[None, None, None, :, :], s, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1), NEG_INF / 2)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[None, None, None, :, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum(
        "bkgqs,bskd->bkgqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m, l, pv


def flash_attention(
    q,
    k,
    v,
    *,
    q_start: int = 0,
    causal: bool = True,
    window: int = 0,
    cap: float = 0.0,
    chunk_q: int = 512,
    chunk_kv: int = 1024,
):
    """Chunked online-softmax attention.

    q [B,Sq,H,Dh] ; k,v [B,Skv,K,Dh] (GQA: H = K*G). q_start: absolute
    position of q[0] relative to k[0] (train/prefill: 0).
    Static per-q-chunk KV ranges skip fully-masked blocks exactly.
    """
    B, Sq, H, Dh = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    sm_scale = Dh**-0.5
    chunk_q = min(chunk_q, Sq)
    chunk_kv = min(chunk_kv, Skv)
    qg = q.reshape(B, Sq, K, G, Dh)

    outs = []
    n_q_chunks = -(-Sq // chunk_q)
    for iq in range(n_q_chunks):
        qs, qe = iq * chunk_q, min(Sq, (iq + 1) * chunk_q)
        cq = qe - qs
        q_chunk = qg[:, qs:qe]
        q_pos = q_start + qs + jnp.arange(cq)
        # static KV range for this q chunk
        hi = min(Skv, q_start + qe) if causal else Skv
        lo = max(0, q_start + qs - window + 1) if window else 0
        lo = (lo // chunk_kv) * chunk_kv
        hi = min(Skv, -(-hi // chunk_kv) * chunk_kv)
        n_kv = (hi - lo) // chunk_kv

        if n_kv <= 0:
            outs.append(jnp.zeros((B, cq, K, G, Dh), q.dtype))
            continue

        k_slab = jax.lax.dynamic_slice_in_dim(k, lo, n_kv * chunk_kv, axis=1)
        v_slab = jax.lax.dynamic_slice_in_dim(v, lo, n_kv * chunk_kv, axis=1)
        k_slab = k_slab.reshape(B, n_kv, chunk_kv, K, Dh)
        v_slab = v_slab.reshape(B, n_kv, chunk_kv, K, Dh)
        kpos0 = lo + jnp.arange(n_kv)[:, None] * chunk_kv + jnp.arange(chunk_kv)[None, :]

        def body(carry, xs):
            m, l, acc = carry
            k_c, v_c, k_pos = xs
            m_c, l_c, pv_c = _chunk_attend(
                q_chunk, k_c, v_c, q_pos, k_pos,
                causal=causal, window=window, cap=cap, sm_scale=sm_scale,
            )
            m_new = jnp.maximum(m, m_c)
            a = jnp.exp(m - m_new)
            b = jnp.exp(m_c - m_new)
            l = l * a + l_c * b
            acc = acc * a[..., None] + pv_c * b[..., None]
            return (m_new, l, acc), None

        # remat per KV tile: without this, differentiating the scan stores
        # every [B,K,G,cq,ckv] probability tile — O(S^2) bwd memory.  With
        # it, bwd memory is O(S) carries and tiles are recomputed.
        body = jax.checkpoint(body)

        m0 = jnp.full((B, K, G, cq), NEG_INF / 2, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, K, G, cq, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0), (k_slab.swapaxes(0, 1), v_slab.swapaxes(0, 1), kpos0)
        )
        o = acc / jnp.maximum(l, 1e-30)[..., None]  # [B,K,G,cq,Dh]
        outs.append(o.transpose(0, 3, 1, 2, 4).astype(q.dtype))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.reshape(B, Sq, H, Dh)


def prefill_chunk_attention(q, k, v, q_pos, *, cap=0.0):
    """One chunk of a chunked prefill: C query rows at TRACED absolute
    positions ``q_pos`` [C] attend causally over a fixed-length dense
    workspace k,v [B,Skv,K,Dh] that already holds every position up to
    ``q_pos[-1]`` (the server writes the chunk's own K/V before calling).

    Bitwise equal to ``flash_attention`` on the full prompt for the same
    query rows when Skv fits one KV chunk (Skv <= chunk_kv): the online-
    softmax scan then runs exactly one iteration whose combine is exact —
    ``m_new = max(NEG_INF/2, m_c) = m_c`` (``_chunk_attend`` clamps m_c
    at NEG_INF/2), ``b = exp(0) = 1``, ``l = 0*a + l_c = l_c``,
    ``acc = pv_c`` — so scan + epilogue collapse to this single
    ``_chunk_attend`` + epilogue.  Masked workspace rows (future
    positions, unwritten zeros) contribute exact zeros either way.  The
    server gates its chunked path on Skv <= 1024 to keep this argument
    (and one compile per bucket: q_pos is traced, no static q_start).
    Window/ring caches are excluded — a ring overwrite inside the prompt
    would break "workspace row i holds position i"."""
    B, C, H, Dh = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, C, K, G, Dh)
    k_pos = jnp.arange(Skv)
    m, l, pv = _chunk_attend(
        qg, k, v, q_pos, k_pos,
        causal=True, window=0, cap=cap, sm_scale=Dh**-0.5,
    )
    o = pv / jnp.maximum(l, 1e-30)[..., None]  # [B,K,G,C,Dh]
    o = o.transpose(0, 3, 1, 2, 4).astype(q.dtype)
    return o.reshape(B, C, H, Dh)


# --------------------------------------------------------------------------
# KV cache + decode
# --------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16, *,
                  per_slot: bool = False, kvq=None) -> dict:
    """per_slot=True gives each batch row its own position array [B, S_c]
    (continuous batching: rows hold independent requests at independent
    positions).  Default keeps the shared [S_c] layout.  A KVQuantSpec
    `kvq` swaps the dense k/v leaves for packed codes + scales; stale
    code words are harmless because pos=-1 masks the whole entry."""
    K, Dh = cfg.n_kv_heads, cfg.head_dim
    pos_shape = (batch, cache_len) if per_slot else (cache_len,)
    pos = jnp.full(pos_shape, -1, jnp.int32)
    if kvq is not None:
        feat = K * Dh
        _, n_blocks, n_words = kv_dequant.kv_layout(kvq, feat)
        return {
            "k_packed": jnp.zeros((batch, cache_len, n_words), jnp.uint32),
            "k_scales": jnp.zeros((batch, cache_len, n_blocks), jnp.bfloat16),
            "v_packed": jnp.zeros((batch, cache_len, n_words), jnp.uint32),
            "v_scales": jnp.zeros((batch, cache_len, n_blocks), jnp.bfloat16),
            "pos": pos,
        }
    return {
        "k": jnp.zeros((batch, cache_len, K, Dh), dtype),
        "v": jnp.zeros((batch, cache_len, K, Dh), dtype),
        "pos": pos,
    }


def cache_slot(pos, cache_len: int, window: int):
    """Ring slot for window caches, identity otherwise. pos may be traced."""
    if window and window <= cache_len:
        return pos % cache_len
    return pos


def write_cache_decode(cache: dict, k_new, v_new, pos, *, window: int = 0,
                       kvq=None) -> dict:
    """Write one token's K/V at absolute position `pos`.

    pos is a traced scalar (all rows share the position, legacy batch
    decode) or a vector [B] with a per-row cache pos array [B, S_c]
    (continuous batching).  Vector rows with pos < 0 are idle slots: the
    write lands at a clamped slot with pos=-1, i.e. an entry that the
    attention mask treats as empty — idle rows stay inert.

    With a KVQuantSpec this is the APPEND-QUANTIZE path: the new token's
    K/V rows are blockwise-encoded inside the same jitted step and only
    the packed codes + scales are written — the bf16 values of a cached
    token never touch HBM.
    """
    pos = jnp.asarray(pos, jnp.int32)
    if kvq is not None and _is_quantized_cache(cache):
        B = k_new.shape[0]
        feat = k_new.shape[-2] * k_new.shape[-1]
        kp, ks = kv_dequant.encode_rows(k_new.reshape(B, feat), kvq)
        vp, vs = kv_dequant.encode_rows(v_new.reshape(B, feat), kvq)
        S_c = cache["k_packed"].shape[1]
        if pos.ndim == 0:
            slot = cache_slot(pos, S_c, window)
            out = {
                key: jax.lax.dynamic_update_slice_in_dim(
                    cache[key], val[:, None], slot, axis=1
                )
                for key, val in (("k_packed", kp), ("k_scales", ks),
                                 ("v_packed", vp), ("v_scales", vs))
            }
            out["pos"] = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], pos[None], slot, axis=0
            )
            return out
        assert cache["pos"].ndim == 2, "vector pos needs a per-slot cache"
        slot = jnp.clip(cache_slot(pos, S_c, window), 0, S_c - 1)
        rows = jnp.arange(B)
        out = {
            key: cache[key].at[rows, slot].set(val)
            for key, val in (("k_packed", kp), ("k_scales", ks),
                             ("v_packed", vp), ("v_scales", vs))
        }
        out["pos"] = cache["pos"].at[rows, slot].set(pos)
        return out
    S_c = cache["k"].shape[1]
    if pos.ndim == 0:
        slot = cache_slot(pos, S_c, window)
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new[:, None], slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new[:, None], slot, axis=1)
        p = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], pos[None], slot, axis=0
        )
        return {"k": k, "v": v, "pos": p}
    assert cache["pos"].ndim == 2, "vector pos needs a per-slot cache ([B,S_c] pos)"
    B = pos.shape[0]
    slot = jnp.clip(cache_slot(pos, S_c, window), 0, S_c - 1)
    rows = jnp.arange(B)
    k = cache["k"].at[rows, slot].set(k_new)
    v = cache["v"].at[rows, slot].set(v_new)
    p = cache["pos"].at[rows, slot].set(pos)
    return {"k": k, "v": v, "pos": p}


def write_cache_local_window(kv_leaves: dict, pos_arr, k_new, v_new, pos, *,
                             S_total: int, offset, window: int = 0, kvq=None):
    """Shard-local flavor of :func:`write_cache_decode`: write one token's
    K/V into a LOCAL slice ``[offset, offset + S_loc)`` of a
    sequence-sharded cache — the write lands only on the shard whose
    window contains the token's slot (``ok`` masks the rest), everything
    else (scalar vs per-row vector ``pos``, idle-row pos=-1 clamping,
    ring slots, append-quantize for packed caches) matches the
    single-device function above; keep the two in lockstep.

    ``kv_leaves`` maps cache keys ("k"/"v" or the packed quartet) to
    their LOCAL slices [B, S_loc, ...]; ``pos_arr`` is the local [S_loc]
    or per-slot [B, S_loc] position slice.  Runs inside the shard_map
    body of models/sharding.Sharder.decode_attn_fn.  Returns
    (updated kv_leaves, updated pos_arr)."""
    d = dict(kv_leaves)
    some = next(iter(d.values()))
    B, S_loc = some.shape[0], some.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if kvq is not None:
        feat = k_new.shape[-2] * k_new.shape[-1]
        kp, ks = kv_dequant.encode_rows(k_new.reshape(B, feat), kvq)
        vp, vs = kv_dequant.encode_rows(v_new.reshape(B, feat), kvq)
        new_vals = {"k_packed": kp, "k_scales": ks,
                    "v_packed": vp, "v_scales": vs}
    else:
        new_vals = {"k": k_new, "v": v_new}
    per_slot = pos_arr.ndim == 2
    if per_slot:
        # vector pos [B]: each row writes its own slot; idle rows
        # (pos=-1) land clamped with stored pos -1, i.e. masked
        slot = jnp.clip(cache_slot(pos, S_total, window), 0, S_total - 1)
    else:
        slot = cache_slot(pos, S_total, window)
    lp = slot - offset
    ok = (lp >= 0) & (lp < S_loc)
    lpc = jnp.clip(lp, 0, S_loc - 1)
    if per_slot:
        rows = jnp.arange(B)
        for key in d:
            new = new_vals[key]
            sel = ok.reshape((B,) + (1,) * (new.ndim - 1))
            cur = d[key][rows, lpc]
            d[key] = d[key].at[rows, lpc].set(jnp.where(sel, new, cur))
        pcur = pos_arr[rows, lpc]
        pos_arr = pos_arr.at[rows, lpc].set(jnp.where(ok, pos, pcur))
    else:
        for key in d:
            new = new_vals[key][:, None]
            cur = jax.lax.dynamic_slice_in_dim(d[key], lpc, 1, 1)
            d[key] = jax.lax.dynamic_update_slice_in_dim(
                d[key], jnp.where(ok, new, cur), lpc, 1
            )
        pcur = jax.lax.dynamic_slice_in_dim(pos_arr, lpc, 1, 0)
        pos_arr = jax.lax.dynamic_update_slice_in_dim(
            pos_arr, jnp.where(ok, pos[None], pcur), lpc, 0
        )
    return d, pos_arr


def write_cache_prefill(cache: dict, k_seq, v_seq, *, window: int = 0,
                        kvq=None) -> dict:
    """Write a prefilled sequence [B,S,K,Dh] into slots [0..S) (or the ring).

    Quantized caches encode every token row first; blocks never span
    tokens, so the per-position ring scatter is identical to the bf16 one.
    """
    B, S = k_seq.shape[:2]
    if kvq is not None and _is_quantized_cache(cache):
        feat = k_seq.shape[-2] * k_seq.shape[-1]
        kp, ks = kv_dequant.encode_rows(k_seq.reshape(B, S, feat), kvq)
        vp, vs = kv_dequant.encode_rows(v_seq.reshape(B, S, feat), kvq)
        leaves = (("k_packed", kp), ("k_scales", ks),
                  ("v_packed", vp), ("v_scales", vs))
        S_c = cache["k_packed"].shape[1]
        if window and window <= S_c and S > S_c:
            positions = jnp.arange(S - S_c, S, dtype=jnp.int32)
            slots = positions % S_c
            order = jnp.argsort(slots)
            out = {
                key: cache[key].at[:, slots[order]].set(val[:, -S_c:][:, order])
                for key, val in leaves
            }
            out["pos"] = cache["pos"].at[slots[order]].set(positions[order])
            return out
        out = {
            key: jax.lax.dynamic_update_slice_in_dim(cache[key], val, 0, axis=1)
            for key, val in leaves
        }
        out["pos"] = cache["pos"].at[:S].set(jnp.arange(S, dtype=jnp.int32))
        return out
    S_c = cache["k"].shape[1]
    if window and window <= S_c and S > S_c:
        # keep only the last S_c positions, ring-aligned
        keep = S_c
        k_seq, v_seq = k_seq[:, -keep:], v_seq[:, -keep:]
        positions = jnp.arange(S - keep, S, dtype=jnp.int32)
        slots = positions % S_c
        order = jnp.argsort(slots)
        k = cache["k"].at[:, slots[order]].set(k_seq[:, order])
        v = cache["v"].at[:, slots[order]].set(v_seq[:, order])
        p = cache["pos"].at[slots[order]].set(positions[order])
        return {"k": k, "v": v, "pos": p}
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_seq, 0, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_seq, 0, axis=1)
    p = cache["pos"].at[:S].set(jnp.arange(S, dtype=jnp.int32))
    return {"k": k, "v": v, "pos": p}


def decode_attention_partial(q, k_cache, v_cache, pos_arr, pos, *, cap=0.0, window=0):
    """Flash-decoding partials over a local cache slice.

    q [B,H,Dh]; k_cache,v_cache [B,S_loc,K,Dh]; pos_arr [S_loc] absolute
    positions (-1 empty).  Returns (m, l, pv): [B,K,G], [B,K,G], [B,K,G,Dh].
    Combine across slices with `combine_partials`.

    Per-slot mode: pos [B] and pos_arr [B,S_loc] — each row masks against
    its own position (rows with pos < 0 see an all-empty cache and return
    l=0, i.e. a zero attention output).
    """
    B, H, Dh = q.shape
    K = k_cache.shape[2]
    G = H // K
    # bf16 operands with f32 MXU accumulation — no f32 cache copies
    # (EXPERIMENTS.md §Perf iteration 3)
    qg = q.reshape(B, K, G, Dh)
    s = jnp.einsum(
        "bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32
    ) * (Dh**-0.5)
    if cap:
        s = cap * jnp.tanh(s / cap)
    pos = jnp.asarray(pos)
    pos_q = pos[:, None] if pos.ndim else pos
    valid = (pos_arr >= 0) & (pos_arr <= pos_q)
    if window:
        valid &= pos_arr > pos_q - window
    # [S_loc] -> broadcast over batch; [B,S_loc] -> per-row mask
    vmask = valid[None, None, None, :] if valid.ndim == 1 else valid[:, None, None, :]
    s = jnp.where(vmask, s, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1), NEG_INF / 2)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(vmask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum(
        "bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return m, l, pv


def combine_partials(m, l, pv, axis_name: str | None):
    """Merge flash-decoding partials; psum over `axis_name` when sharded."""
    if axis_name is None:
        o = pv / jnp.maximum(l, 1e-30)[..., None]
        return o
    m_g = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis_name)
    pv_g = jax.lax.psum(pv * corr[..., None], axis_name)
    return pv_g / jnp.maximum(l_g, 1e-30)[..., None]


def dequant_cache_kv(cache: dict, kvq, n_kv_heads: int, head_dim: int):
    """Materialize bf16 k/v [B, S_c, K, Dh] from a packed cache — the
    dequant-attention read path (Pallas kernel on TPU, jnp oracle
    elsewhere; kernels/kv_dequant.py)."""
    feat = n_kv_heads * head_dim
    shape = cache["k_packed"].shape[:2] + (n_kv_heads, head_dim)
    k = kv_dequant.dequant_rows(
        cache["k_packed"], cache["k_scales"], kvq, feat
    ).reshape(shape)
    v = kv_dequant.dequant_rows(
        cache["v_packed"], cache["v_scales"], kvq, feat
    ).reshape(shape)
    return k, v


def decode_attention(q, cache, pos, *, cap=0.0, window=0, kvq=None):
    """Unsharded single-token attention against a cache (CPU/test path).
    Packed caches are dequantized into the same masked partial math, so
    pos/idle-row semantics are shared with the bf16 path."""
    B, H, Dh = q.shape
    if kvq is not None and _is_quantized_cache(cache):
        feat = cache["k_packed"].shape[-1] * (32 // kvq.bits)
        k_cache, v_cache = dequant_cache_kv(cache, kvq, feat // Dh, Dh)
    else:
        k_cache, v_cache = cache["k"], cache["v"]
    m, l, pv = decode_attention_partial(
        q, k_cache, v_cache, cache["pos"], pos, cap=cap, window=window
    )
    o = combine_partials(m, l, pv, None)
    return o.reshape(B, H, Dh).astype(q.dtype)


# --------------------------------------------------------------------------
# paged decode (serving/pages.py builds the closure that threads page_map)
# --------------------------------------------------------------------------

def write_cache_paged(cache: dict, k_new, v_new, pos, page_map, *,
                      page_size: int, kvq=None) -> dict:
    """Write one token's K/V into PAGE-MAJOR storage.

    ``cache`` leaves are [n_pages, ps, ...] with a per-page pos array
    [n_pages, ps]; ``pos`` is the per-row vector [B] (-1 = idle row);
    ``page_map`` [B, P] maps each row's logical page index to its physical
    page id (0 for unallocated table entries).  Row b's token at absolute
    position p lands in page ``page_map[b, p // ps]`` at offset ``p % ps``
    — the same (row, position) cell the slot pool writes, relocated
    page-wise.  Idle rows (pos < 0) and any out-of-table position redirect
    to the reserved trash page 0, where only pos = -1 is ever stored, so
    they stay inert exactly like the slot path's clamped idle writes.
    Append-quantize semantics match :func:`write_cache_decode` verbatim.
    Window/ring caches are not supported (the server gates paged mode to
    full-cache attention archs)."""
    pos = jnp.asarray(pos, jnp.int32)
    assert pos.ndim == 1, "paged writes need a per-row pos vector"
    B = pos.shape[0]
    S_total = page_map.shape[1] * page_size
    safe = jnp.clip(pos, 0, S_total - 1)
    live = pos >= 0
    page = jnp.where(live, page_map[jnp.arange(B), safe // page_size], 0)
    off = jnp.where(live, safe % page_size, 0)
    if kvq is not None and _is_quantized_cache(cache):
        feat = k_new.shape[-2] * k_new.shape[-1]
        kp, ks = kv_dequant.encode_rows(k_new.reshape(B, feat), kvq)
        vp, vs = kv_dequant.encode_rows(v_new.reshape(B, feat), kvq)
        out = {
            key: cache[key].at[page, off].set(val)
            for key, val in (("k_packed", kp), ("k_scales", ks),
                             ("v_packed", vp), ("v_scales", vs))
        }
        out["pos"] = cache["pos"].at[page, off].set(jnp.where(live, pos, -1))
        return out
    out = {
        "k": cache["k"].at[page, off].set(k_new),
        "v": cache["v"].at[page, off].set(v_new),
        "pos": cache["pos"].at[page, off].set(jnp.where(live, pos, -1)),
    }
    return out


def paged_decode_attention(q, cache, pos, page_map, *, cap=0.0, kvq=None):
    """Single-token attention against a PAGED cache: gather every leaf
    through the page-index vector (kernels/kv_dequant.gather_pages) into
    the contiguous [B, P*ps, ...] per-sequence view, then run the exact
    slot-pool read path on it.  Because the gathered view places absolute
    position p at index p (page_map is in table order) and invalid entries
    carry pos = -1 (trash page / unwritten offsets), the masked partials
    are bitwise identical to :func:`decode_attention` over a slot row
    holding the same tokens — the correctness bar for --paged serving."""
    B, H, Dh = q.shape
    if kvq is not None and _is_quantized_cache(cache):
        feat = cache["k_packed"].shape[-1] * (32 // kvq.bits)
        K = feat // Dh
        k_cache = kv_dequant.dequant_pages(
            cache["k_packed"], cache["k_scales"], page_map, kvq, feat
        )
        v_cache = kv_dequant.dequant_pages(
            cache["v_packed"], cache["v_scales"], page_map, kvq, feat
        )
        S_c = k_cache.shape[1]
        k_cache = k_cache.reshape(B, S_c, K, Dh)
        v_cache = v_cache.reshape(B, S_c, K, Dh)
    else:
        k_cache = kv_dequant.gather_pages(cache["k"], page_map)
        v_cache = kv_dequant.gather_pages(cache["v"], page_map)
    pos_arr = kv_dequant.gather_pages(cache["pos"], page_map)  # [B, P*ps]
    m, l, pv = decode_attention_partial(
        q, k_cache, v_cache, pos_arr, pos, cap=cap, window=0
    )
    o = combine_partials(m, l, pv, None)
    return o.reshape(B, H, Dh).astype(q.dtype)
