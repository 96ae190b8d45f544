"""Decoder-only LM assembled from blocks.py: init, train loss, prefill,
decode.  Covers dense / moe / ssm / hybrid / vlm families; the enc-dec
(audio) family lives in seq2seq.py with the same building blocks.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.qtensor import QuantizedTensor
from repro.models import blocks
from repro.models.layers import init_norm, linear, norm, softcap

NO_CONSTRAIN = lambda x, kind: x


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def init_embed(key, cfg) -> jnp.ndarray:
    return jax.random.normal(key, (cfg.vocab_size, cfg.d_model),
                             jnp.float32) * 0.02


def init_lm_head(key, cfg) -> jnp.ndarray:
    return jax.random.normal(key, (cfg.vocab_size, cfg.d_model),
                             jnp.float32) * cfg.d_model**-0.5


def init_params(key, cfg) -> dict:
    """Random f32 params.  Keys: split(key, 3) -> embed, stack, lm_head
    (models/quantize.init_quantized_params streams the same keys)."""
    ks = jax.random.split(key, 3)
    p = {
        "embed": init_embed(ks[0], cfg),
        "stack": blocks.init_stack(ks[1], cfg),
        "final_norm": init_norm(cfg.d_model, cfg.norm_type),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = init_lm_head(ks[2], cfg)
    return p


def count_params(cfg, active_only: bool = False) -> int:
    if cfg.encoder_decoder:
        from repro.models import seq2seq

        shapes = jax.eval_shape(lambda: seq2seq.init_params(jax.random.PRNGKey(0), cfg))
    else:
        shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        n = math.prod(leaf.shape)
        if active_only and any(
            getattr(k, "key", None) in ("w_gate", "w_up", "w_down")
            and "ffn" in str(path)
            and cfg.n_experts
            and len(leaf.shape) == 4  # (n_periods, E, in, out)
            for k in path
        ):
            n = n * cfg.top_k // cfg.n_experts
        total += n
    return total


def head_matrix(params):
    """[V, D] output projection (tied embedding or lm_head; maybe quantized)."""
    return params.get("lm_head", params["embed"])


def logits_from_hidden(params, h, cfg):
    """h [..., D] -> logits [..., V] (softcapped for gemma2)."""
    w = head_matrix(params)
    if isinstance(w, QuantizedTensor):
        # QT stores [V, D] == transposed head; cfg.matmul_mode routes it
        # through the fused dequant-GEMM like every other matrix
        out = linear(h, w, mode=cfg.matmul_mode)
    else:
        out = jnp.einsum("...d,vd->...v", h, w.astype(h.dtype))
    return softcap(out, cfg.final_logit_softcap)


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------

def embed_inputs(params, batch_inputs, cfg):
    if cfg.input_kind == "frames":
        return batch_inputs.astype(jnp.bfloat16)  # stub frontend: embeddings in
    emb = params["embed"]
    if isinstance(emb, QuantizedTensor):
        from repro.core.qtensor import dequantize_tensor

        emb = dequantize_tensor(emb)
    return emb.astype(jnp.bfloat16)[batch_inputs]


def backbone_seq(params, inputs, cfg, *, constrain=NO_CONSTRAIN, q_pad=None,
                 write_cache=False, cache_len=None, remat=False,
                 pad_mask=None):
    """``pad_mask`` [B,S] (True = real token) flows to the MoE router's
    capacity accounting only (models/moe.py) — the serving path passes it
    for bucket-padded prefills so MoE archs bucket safely."""
    x = embed_inputs(params, inputs, cfg)
    x = constrain(x, "residual")
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x, caches, aux = blocks.apply_stack_seq(
        params["stack"], x, cfg,
        constrain=constrain, positions=positions, q_pad=q_pad,
        write_cache=write_cache, cache_len=cache_len, remat=remat,
        pad_mask=pad_mask,
    )
    x = norm(params["final_norm"], x, cfg.norm_type)
    return x, caches, aux


def backbone_chunk(params, inputs, workspace, chunk_start, cfg, *,
                   constrain=NO_CONSTRAIN):
    """One chunk of a chunked prefill: run the backbone over C
    consecutive prompt rows starting at TRACED absolute position
    ``chunk_start``, against a dense bf16 ``workspace`` (init_caches of
    the cfg.with_kv_quant(16) twin, batch 1, bucketed prompt length)
    holding every earlier chunk's K/V.  Returns (normed hidden [B,C,D],
    updated workspace).

    Per-row ops (embed, norms, projections, RoPE, FFN) are row-wise
    identical to ``backbone_seq`` and the chunk attention is bitwise
    equal to flash_attention for workspace lengths <= one KV chunk
    (models/attention.prefill_chunk_attention), so the final chunk's
    rows — and the tokens sampled from them — match a plain prefill
    (pinned by tests/test_serving.py's chunked golden test)."""
    x = embed_inputs(params, inputs, cfg)
    x = constrain(x, "residual")
    C = x.shape[1]
    positions = chunk_start + jnp.arange(C, dtype=jnp.int32)
    x, workspace = blocks.apply_stack_prefill_chunk(
        params["stack"], x, workspace, positions, cfg, constrain=constrain,
    )
    x = norm(params["final_norm"], x, cfg.norm_type)
    return x, workspace


def loss_fn(params, tokens, labels, cfg, *, constrain=NO_CONSTRAIN, q_pad=None,
            loss_chunk: int = 512, remat: bool = True):
    """Mean next-token cross entropy (+ MoE aux). Labels = tokens shifted,
    -1 = masked.  Logits are formed per sequence-chunk under jax.checkpoint
    so the [B,S,V] tensor never materializes (gemma2: V=256k)."""
    h, _, aux = backbone_seq(params, tokens, cfg, constrain=constrain,
                             q_pad=q_pad, remat=remat)
    B, S, D = h.shape
    loss_chunk = min(loss_chunk, S)
    n_chunks = S // loss_chunk

    def chunk_loss(h_c, y_c):
        logits = logits_from_hidden(params, h_c, cfg).astype(jnp.float32)
        logits = constrain(logits, "logits")
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(y_c, 0)[..., None], axis=-1
        )[..., 0]
        mask = (y_c >= 0).astype(jnp.float32)
        return jnp.sum((lse - ll) * mask), jnp.sum(mask)

    hc = h[:, : n_chunks * loss_chunk].reshape(B, n_chunks, loss_chunk, D)
    yc = labels[:, : n_chunks * loss_chunk].reshape(B, n_chunks, loss_chunk)

    def body(carry, xs):
        tot, cnt = carry
        l, c = jax.checkpoint(chunk_loss)(xs[0], xs[1])
        return (tot + l, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(
        body, (0.0, 0.0), (hc.swapaxes(0, 1), yc.swapaxes(0, 1))
    )
    loss = tot / jnp.maximum(cnt, 1.0)
    if cfg.n_experts:
        loss = loss + cfg.router_aux_weight * aux / max(1, cfg.n_layers)
    return loss


def prefill(params, inputs, cfg, *, constrain=NO_CONSTRAIN, q_pad=None,
            cache_len=None):
    """Process a prompt; returns (last-token logits, caches).  `cache_len`
    reserves decode room (defaults to the prompt length)."""
    h, caches, _ = backbone_seq(
        params, inputs, cfg, constrain=constrain, q_pad=q_pad, write_cache=True,
        cache_len=cache_len,
    )
    logits = logits_from_hidden(params, h[:, -1], cfg)
    return logits, caches


def decode_step(params, token, caches, pos, cfg, *, constrain=NO_CONSTRAIN,
                decode_attn=blocks.local_decode_attn):
    """One decoding step. token [B] (or [B,D] frames); pos is a traced
    scalar (all rows at the same position) or a vector [B] of per-row
    positions (continuous batching over per-slot caches; -1 = idle row).
    Returns (logits [B,V], new caches)."""
    if cfg.input_kind == "frames":
        x = token.astype(jnp.bfloat16)
    else:
        x = embed_inputs(params, token, cfg)
    x, new_caches = blocks.apply_stack_decode(
        params["stack"], x, caches, pos, cfg,
        constrain=constrain, decode_attn=decode_attn,
    )
    x = norm(params["final_norm"], x, cfg.norm_type)
    logits = logits_from_hidden(params, x, cfg)
    return logits, new_caches


def init_caches(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16,
                *, per_slot: bool = False):
    """Decode-cache pytree for `cfg`.  `dtype` is the dense k/v (and scale)
    dtype; when cfg.kv_bits < 16 the attention leaves are packed codes +
    per-block scales instead (kernels/kv_dequant.py layout) — callers
    never branch on this, the cache entry points dispatch internally."""
    return blocks.init_stack_cache(cfg, batch, cache_len, dtype, per_slot=per_slot)
