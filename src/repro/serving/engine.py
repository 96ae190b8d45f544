"""Static-batch inference engine: the paper's deployment target (16-bit
activations, k-bit weights).

A generate() call takes a batch of same-length prompts, prefills the
sequence-shardable KV caches once, then runs jit-compiled single-token
decode steps with greedy or temperature sampling and per-sequence EOS
masking.  Weights may be a quantized tree (models/quantize.py) — the
engine is agnostic; quantization shows up only as smaller param leaves
and the in-layer dequant.  Passing ``plan=`` (a precision/plan.py
PrecisionPlan) quantizes a RAW tree at construction — the mixed-
precision serving entry point.

This is the STATIC path: one shared scalar position, batching by prompt
length, the whole batch retires together.  It doubles as the numerical
oracle for the continuous-batching subsystem (server.py + kvcache.py +
scheduler.py), which serves mixed-length asynchronous request streams
over a slot pool with per-row positions — see docs/serving.md for the
slot/scheduler design and when to prefer each path.

cfg.kv_bits < 16 is honored here too (the scalar-pos branches of the
same cache entry points): an Engine at kv_bits=16 is the bf16-cache
oracle the quantized serve is toleranced against, and an Engine at the
SAME kv_bits must be token-identical to the Server — cache quantization
is per token-row, so batching composition still cannot change outputs.

Passing ``sharder=`` (models/sharding.Sharder) serves on a mesh: params
stay wherever the caller placed them, caches are re-placed onto their
sequence-sharded layout right after prefill, decode attention goes
through the sharder's shard_map flash-decoding (packed k-bit caches
included), and eligible quantized matmuls run column-parallel inside
``sharder.tp_scope()``.  ``sharding.check_decode_capability`` is the one
gate for the quantized×sharded combination.
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import blocks, lm
from repro.models.sharding import check_decode_capability
from repro.serving.telemetry import NOOP, record_quant_health, record_tree_bits

#: stated per-token logit tolerance of a k-bit KV cache vs the bf16-cache
#: oracle (tiny family, float codebook, block 64) — the acceptance bound
#: used by benchmarks/serve_bench.py and tests/test_kvquant.py, and the
#: number documented in docs/serving.md.
KV_LOGIT_TOL = {8: 0.2, 4: 1.0}


def kv_oracle_logit_gap(params, cfg_q, prompts, n_steps, *, sharder=None):
    """Teacher-forced per-token logit gap of cfg_q's k-bit KV cache vs
    the bf16-cache oracle.

    Rolls the bf16-cache model greedily over `prompts` [B, S], then
    replays the SAME token sequence through the k-bit cache — a
    deterministic comparison, unlike free-running token matching, which
    flips on near-ties.  Returns (max |logit gap| over all steps
    including prefill, greedy-agreement fraction).

    With a ``sharder``, the k-bit replay runs through the SEQUENCE-
    SHARDED decode path (placed params are the caller's business; the
    oracle rollout stays single-device) — so a mesh serve is gated
    against the same single-device bf16 oracle as the unsharded one,
    with the sharded numerics actually in the loop."""
    import numpy as np

    cfg16 = cfg_q.with_kv_quant(16)
    cache_len = prompts.shape[1] + n_steps
    if sharder is not None:
        cache_len = sharder.pad_cache_len(cache_len)
    B = prompts.shape[0]

    def rollout(c, force=None, shard=False):
        kw, place, decode_kw = {}, lambda x: x, {}
        scope = contextlib.nullcontext
        if shard:
            kw = dict(constrain=sharder.constrain, q_pad=sharder.head_pad())
            place = lambda caches: jax.device_put(
                caches, sharder.cache_spec_tree(caches, B))
            decode_kw = dict(
                constrain=sharder.constrain,
                decode_attn=sharder.decode_attn_fn(B, cache_len))
            # quantized weights route through the TP matmul dispatch so
            # the gate exercises the same fused/dequant shard_map shapes
            # the served path uses
            scope = sharder.tp_scope
        with scope():
            logits, caches = lm.prefill(params, jnp.asarray(prompts), c,
                                        cache_len=cache_len, **kw)
        caches = place(caches)
        toks, logs = [], [np.asarray(logits, np.float32)]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        for t in range(n_steps - 1):
            feed = tok if force is None else jnp.asarray(force[t])
            with scope():
                logits, caches = lm.decode_step(
                    params, feed, caches, jnp.int32(prompts.shape[1] + t), c,
                    **decode_kw)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(np.asarray(tok))
            logs.append(np.asarray(logits, np.float32))
        return np.stack(toks), np.stack(logs)

    toks16, logs16 = rollout(cfg16)
    toksq, logsq = rollout(cfg_q, force=toks16, shard=sharder is not None)
    gap = float(np.abs(logs16 - logsq).max())
    agree = float((toks16 == toksq).mean())
    return gap, agree


def sample_token(logits, key, temperature):
    """Shared sampling semantics (static + continuous paths): greedy at
    temperature 0, categorical otherwise.  temperature broadcasts —
    scalar or per-row [B]."""
    temperature = jnp.asarray(temperature, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temperature[..., None], 1e-6)
    sampled = jax.random.categorical(key, scaled)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


class Engine:
    def __init__(self, params, cfg, *, max_seq_len: int, sharder=None,
                 eos_id: int | None = None, plan=None,
                 matmul_mode: str | None = None, telemetry=NOOP):
        if matmul_mode is not None:
            cfg = cfg.with_matmul_mode(matmul_mode)
        check_decode_capability(
            cfg, sharder, caller="the static Engine (serving/engine.py)"
        )
        self.telemetry = telemetry
        if plan is not None:
            from repro.models.quantize import quantize_tree

            # load-time quantization health: per-matrix bits + blockwise
            # qerr, measured on the raw tree before it is consumed
            record_quant_health(telemetry, params, cfg, plan=plan)
            params = quantize_tree(params, cfg, plan=plan)
        else:
            record_tree_bits(telemetry, params)
        if sharder is not None:
            # extra decode room so full-attention cache lengths divide
            # the seq-shard grid (ring windows may still fall back)
            max_seq_len = sharder.pad_cache_len(max_seq_len)
        self.params = params
        self.cfg = cfg
        self.max_seq_len = max_seq_len
        self.eos_id = eos_id
        self.sharder = sharder
        constrain = sharder.constrain if sharder is not None else lm.NO_CONSTRAIN
        q_pad = sharder.head_pad() if sharder is not None else None
        tp_scope = sharder.tp_scope if sharder is not None \
            else contextlib.nullcontext

        def prefill(params, prompts):
            with tp_scope():
                return lm.prefill(
                    params, prompts, cfg, constrain=constrain, q_pad=q_pad,
                    cache_len=max_seq_len,
                )

        self._prefill = jax.jit(prefill)

        def step(params, token, caches, pos, key, temperature, done):
            decode_attn = (
                sharder.decode_attn_fn(token.shape[0], max_seq_len)
                if sharder is not None else blocks.local_decode_attn
            )
            with tp_scope():
                logits, caches = lm.decode_step(
                    params, token, caches, pos, cfg,
                    constrain=constrain, decode_attn=decode_attn,
                )
            nxt = sample_token(logits, key, temperature)
            if self.eos_id is not None:
                nxt = jnp.where(done, self.eos_id, nxt)
                done = done | (nxt == self.eos_id)
            return nxt, caches, done

        self._step = jax.jit(step, donate_argnums=(2,))
        self._first = jax.jit(sample_token)

        # optional roofline attribution (serving/profiler.py) — host-side
        # only; the jitted programs above are identical with it on or off
        prof = getattr(telemetry, "profiler", None)
        self._prof = (prof.session(telemetry.registry,
                                   kv_bits=str(cfg.kv_bits),
                                   matmul_mode=cfg.matmul_mode)
                      if telemetry.enabled and prof is not None else None)

    def _place_caches(self, caches, batch: int):
        """Move the prefill-produced caches onto their sequence-sharded
        mesh layout so every decode step streams only local KV bytes."""
        s = self.sharder
        if s is None or s.mesh is None or s.replicate:
            return caches
        return jax.device_put(caches, s.cache_spec_tree(caches, batch))

    def generate(self, prompts: jnp.ndarray, max_new_tokens: int, *,
                 temperature: float = 0.0, key=None):
        """prompts [B, S] int32 -> tokens [B, max_new_tokens]."""
        B, S = prompts.shape
        assert S + max_new_tokens <= self.max_seq_len, "exceeds cache budget"
        if key is None:
            key = jax.random.PRNGKey(0)
        tel = self.telemetry
        pf_name = f"prefill[{B}x{S}]"
        if self._prof is not None:
            # cost extraction BEFORE t_start so the one-time AOT compile
            # never pollutes the timed window
            self._prof.ensure_costed(pf_name, self._prefill,
                                     (self.params, prompts))
        with tel.scope("prefill", step=0, slot=-1, prompt_len=S,
                       padded_len=S) as pf:
            logits, caches = self._prefill(self.params, prompts)
            caches = self._place_caches(caches, B)
            # the first token goes through the same temperature/categorical
            # path as decode steps (it used to be unconditionally greedy)
            key, sub = jax.random.split(key)
            tok = self._first(logits, sub, jnp.float32(temperature))
            if tel.enabled:
                # host-side fence at the dispatch boundary; the jitted
                # prefill/step programs are untouched (docs/observability.md)
                jax.block_until_ready(tok)
        if tel.enabled:
            t_tok = pf.t1
            if self._prof is not None:
                self._prof.observe(pf_name, t_tok - pf.t0)
            tel.observe("serve_prefill_seconds", t_tok - pf.t0)
            tel.observe("serve_ttft_seconds", t_tok - pf.t0)
            tel.inc("serve_prefills_total")
            tel.inc("serve_tokens_total", B)
        done = (tok == self.eos_id) if self.eos_id is not None else jnp.zeros((B,), bool)
        out = [tok]
        ds_name = f"decode_step[{B}]"
        for t in range(1, max_new_tokens):
            key, sub = jax.random.split(key)
            ds_args = (self.params, tok, caches, jnp.int32(S + t - 1), sub,
                       jnp.float32(temperature), done)
            if self._prof is not None:
                self._prof.ensure_costed(ds_name, self._step, ds_args)
            with tel.scope("decode_step", step=t, n_active=B,
                           batch_fill=1.0) as ds:
                tok, caches, done = self._step(*ds_args)
                if tel.enabled:
                    jax.block_until_ready(tok)
            if tel.enabled:
                if self._prof is not None:
                    self._prof.observe(ds_name, ds.t1 - ds.t0)
                tel.observe("serve_decode_step_seconds", ds.t1 - ds.t0)
                tel.observe("serve_itl_seconds", ds.t1 - t_tok)
                t_tok = ds.t1
                tel.inc("serve_decode_steps_total")
                tel.inc("serve_tokens_total", B)
            out.append(tok)
            if self.eos_id is not None and bool(jnp.all(done)):
                break
        return jnp.stack(out, axis=1)


_NLL_CACHE: dict = {}


def _nll_fn(cfg):
    if cfg not in _NLL_CACHE:

        @jax.jit
        def nll(params, toks, labels):
            return lm.loss_fn(params, toks, labels, cfg, remat=False,
                              loss_chunk=min(512, toks.shape[1])) * labels.size

        _NLL_CACHE[cfg] = nll
    return _NLL_CACHE[cfg]


def perplexity(params, cfg, tokens, *, batch_size: int = 8) -> float:
    """Held-out perplexity of (possibly quantized) params — the paper's
    preferred evaluation metric (§4: r=-0.94 vs zero-shot accuracy).
    The jitted evaluator is cached per config so sweeps over many quant
    settings recompile only when the pytree structure changes."""
    total, count = 0.0, 0
    nll = _nll_fn(cfg)
    n = tokens.shape[0]
    for i in range(0, n, batch_size):
        tb = tokens[i : i + batch_size]
        total += float(nll(params, tb[:, :-1], tb[:, 1:]))
        count += tb[:, 1:].size
    return float(jnp.exp(total / count))
