"""CPU virtual-mesh parity suite for the sharded quantized serving stack.

Every numeric check runs in a SUBPROCESS with 8 forced host devices
(XLA_FLAGS must not leak into this process — dryrun.py rule) on a 2x4
("data", "model") mesh, with a head-count-divisible tiny config so the
TP head sharding is exact.  The ladder mirrors the stack:

* bf16: sequence-sharded decode matches the single-device rollout to
  bf16 partial-combine noise — teacher-forced, the repo's standard
  deterministic criterion (free-running token comparison flips on
  near-ties of a random-init model; serving.kv_oracle_logit_gap doc).
* kv8/kv4: sharded packed-cache decode stays within the SAME
  serving.KV_LOGIT_TOL bound vs the single-device bf16-cache oracle
  that gates the unsharded quantized serve (teacher-forced).
* fused == dequant_einsum stays token-identical under TP (the
  column-parallel fused dequant-GEMM dispatch, kernels/ops).
* Engine == Server at the same mesh + kv_bits (static scalar-pos vs
  continuous per-slot sharded decode compose identically).
* ring-window caches that do not divide the shard grid take the
  replicated fallback — WARNED at setup (SeqShardFallbackWarning) and
  still numerically correct.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

# heavyweight: multi-device meshes on a CPU host; CI fast lane skips it
pytestmark = pytest.mark.slow

SRC = str(Path(__file__).resolve().parents[1] / "src")

_PRELUDE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, __SRC__)
    import dataclasses, json, warnings
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_arch
    from repro.configs import QuantConfig
    from repro.models import lm
    from repro.models.quantize import quantize_params
    from repro.models.sharding import Sharder, SeqShardFallbackWarning
    from repro.serving import Engine, Server, KV_LOGIT_TOL

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    # tiny-650k: 4 heads divide the 4-way model axis (tiny-160k's 2
    # would force a pathological feature-split head layout), and it is
    # in the tiny family KV_LOGIT_TOL was calibrated on
    cfg = get_arch("tiny-650k")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, Sp, S = 4, 8, 24
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, Sp), 0,
                              cfg.vocab_size)

    def lm_rollout(c, p, sharder, n_steps, force=None):
        '''prefill + decode_step rollout returning (tokens, logits) —
        the logit-level harness (Engine hides step logits).'''
        import contextlib
        kw = {}
        scope = contextlib.nullcontext
        if sharder is not None:
            kw = dict(constrain=sharder.constrain, q_pad=sharder.head_pad())
            scope = sharder.tp_scope  # what Engine/Server enter too

        def pf(p, t):
            with scope():
                return lm.prefill(p, t, c, cache_len=S, **kw)

        logits, caches = jax.jit(pf)(p, toks)
        if sharder is not None:
            caches = jax.device_put(
                caches, sharder.cache_spec_tree(caches, B))
            decode_attn = sharder.decode_attn_fn(B, S)

            def dec_fn(p, tok, cch, pos):
                with scope():
                    return lm.decode_step(
                        p, tok, cch, pos, c, constrain=sharder.constrain,
                        decode_attn=decode_attn)
        else:
            def dec_fn(p, tok, cch, pos):
                return lm.decode_step(p, tok, cch, pos, c)
        dec = jax.jit(dec_fn)
        outs, logs = [], [np.asarray(logits, np.float32)]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(np.asarray(tok))
        for t in range(n_steps - 1):
            feed = tok if force is None else jnp.asarray(force[:, t])
            logits, caches = dec(p, feed, caches, jnp.int32(Sp + t))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            outs.append(np.asarray(tok))
            logs.append(np.asarray(logits, np.float32))
        return np.stack(outs, 1), np.stack(logs, 1)
"""


def _run(body: str, timeout: int = 900) -> dict:
    script = (textwrap.dedent(_PRELUDE).replace("__SRC__", repr(SRC))
              + textwrap.dedent(body))
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=timeout)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_sharded_bf16_noise_bounded_and_kvq_logit_bounded():
    """bf16: sharded decode within partial-combine noise of the
    single-device rollout (teacher-forced).  kv8/kv4: sharded packed
    decode within KV_LOGIT_TOL of the single-device bf16 oracle."""
    out = _run("""
    sharder = Sharder(mesh, cfg, replicate_params_below=0)
    params_s = jax.device_put(params, sharder.param_spec_tree(params))
    n = 12
    res = {}

    tok_ref, logs_ref = lm_rollout(cfg, params, None, n)
    tok_sh, logs_sh = lm_rollout(cfg, params_s, sharder, n, force=tok_ref)
    res["bf16_logit_gap"] = float(np.abs(logs_ref - logs_sh).max())
    res["bf16_agree"] = float((tok_ref == tok_sh).mean())

    # teacher-forced: replay the bf16 oracle's tokens through the
    # SHARDED k-bit cache and bound every step's logits
    for bits in (8, 4):
        c = cfg.with_kv_quant(bits)
        tq, lq = lm_rollout(c, params_s, Sharder(mesh, c,
                                                 replicate_params_below=0),
                            n, force=tok_ref)
        res[f"kv{bits}_gap"] = float(np.abs(logs_ref - lq).max())
        res[f"kv{bits}_tol"] = KV_LOGIT_TOL[bits]
    print(json.dumps(res))
    """)
    assert out["bf16_logit_gap"] < 0.08, out
    for bits in (8, 4):
        assert out[f"kv{bits}_gap"] < out[f"kv{bits}_tol"], out


def test_fused_matches_dequant_under_tp():
    """The column-parallel fused dequant-GEMM dispatch is a pure
    performance knob on a mesh too: greedy tokens identical to the
    dequant_einsum oracle over a full quantized rollout."""
    out = _run("""
    qparams = quantize_params(
        params, QuantConfig(bits=4, dtype="float", block_size=64), cfg)
    sharder = Sharder(mesh, cfg, replicate_params_below=0)
    qp_s = jax.device_put(qparams, sharder.param_spec_tree(qparams))
    n = 12
    tf, lf = lm_rollout(cfg.with_matmul_mode("fused"), qp_s, sharder, n)
    # teacher-forced replay through the oracle mode: deterministic
    # step-by-step comparison (free-running flips on random-init ties)
    td, ld = lm_rollout(cfg.with_matmul_mode("dequant_einsum"), qp_s,
                        sharder, n, force=tf)
    # and through the SINGLE-DEVICE quantized oracle: a common-mode bug
    # in the shared TP shard_map shape (both modes wrong identically)
    # cannot hide behind the fused==dequant comparison
    t1, l1 = lm_rollout(cfg.with_matmul_mode("dequant_einsum"), qparams,
                        None, n, force=tf)
    print(json.dumps({
        "tokens_eq": bool((tf == td).all()),
        "logit_gap": float(np.abs(lf - ld).max()),
        "oracle_gap": float(np.abs(lf - l1).max()),
    }))
    """)
    assert out["tokens_eq"], out
    assert out["logit_gap"] < 0.05, out
    assert out["oracle_gap"] < 0.08, out


def test_engine_matches_server_on_mesh_kv4():
    """Static scalar-pos sharded decode (Engine) == continuous per-slot
    sharded decode (Server) at the same mesh + kv_bits: greedy tokens
    identical per request at matched batch shapes (batch-1 Engine vs
    single-slot Server — the two sharded cache-write/read flavors this
    PR adds, compared bitwise).  Across DIFFERENT batch compositions the
    mesh layouts differ and random-init near-ties flip, so the
    multi-slot mesh serve is gated by the oracle logit tolerance in
    benchmarks/serve_bench.py instead."""
    out = _run("""
    c = cfg.with_kv_quant(4)
    sharder = Sharder(mesh, c, replicate_params_below=0)
    params_s = jax.device_put(params, sharder.param_spec_tree(params))
    n = 10
    eng = Engine(params_s, c, max_seq_len=S, sharder=sharder)
    srv = Server(params_s, c, num_slots=1, max_seq_len=S, sharder=sharder)
    match = []
    for b in range(B):
        ref = np.asarray(eng.generate(toks[b:b + 1], n))[0]
        rid = srv.submit(np.asarray(toks[b]), n)
        res = srv.run_until_drained()
        match.append(res[rid] == list(ref))
    print(json.dumps({"match": match}))
    """)
    assert all(out["match"]), out


def test_ring_cache_falls_back_with_warning_and_stays_correct():
    """A ring-window cache shorter than the seq-shard grid takes the
    replicated local fallback: SeqShardFallbackWarning at setup (the
    hoisted decision — satellite regression) and numerics match the
    single-device rollout."""
    out = _run("""
    ring = dataclasses.replace(cfg, sliding_window=6)
    sharder = Sharder(mesh, ring, replicate_params_below=0)
    params_s = jax.device_put(params, sharder.param_spec_tree(params))
    n = 10
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        fn = sharder.decode_attn_fn(B, S)
        setup_warned = any(issubclass(w.category, SeqShardFallbackWarning)
                           for w in rec)
    tok_ref, logs_ref = lm_rollout(ring, params, None, n)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        tok_sh, logs_sh = lm_rollout(ring, params_s, sharder, n)
        rollout_warned = any(issubclass(w.category, SeqShardFallbackWarning)
                             for w in rec)
    print(json.dumps({
        "setup_warned": setup_warned,
        "rollout_warned": rollout_warned,
        "plan": {str(k): v for k, v in
                 sharder.seq_shard_plan(B, S).items()},
        "tokens_eq": bool((tok_ref == tok_sh).all()),
        "logit_gap": float(np.abs(logs_ref - logs_sh).max()),
    }))
    """)
    assert out["setup_warned"], out
    assert out["rollout_warned"], out
    assert out["plan"] == {"6": False}, out
    assert out["logit_gap"] < 0.08, out
