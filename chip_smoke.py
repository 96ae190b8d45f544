#!/usr/bin/env python3
"""Smoke run of the k-bit serving stack on TPU: qwen2-7b at full width and
depth, 4-bit float weights in blocks of 64 (the paper's recommendation)
and a 4-bit KV cache, with random weights made from ``--seed``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path on four chips

One chip, in order: require a TPU; turn on the persistent compile cache;
build the quantized model layer by layer; serve 8 requests in two prefill
buckets through the paged ``Server`` (4 slots, 32 new tokens each, a
cold run and a warm rerun that must repeat it token for token); compare
prefill logits of the fused Pallas path with the ``dequant_einsum``
oracle on the same weights; check the served decode step calls both
Pallas kernels (``tpu_custom_call``).

``--chips 4`` runs only the sharded path and its comparison: the same
weights column-parallel on a 1x4 mesh through the ``Sharder`` and the
slot pool (``--paged`` and ``--mesh`` exclude each other), against the
same requests on one device of the same process, plus a teacher-forced
logit comparison of the two decode paths.

Everything runs in this one process.  A failed phase exits non-zero
before the last line; a passing run ends with one JSON line:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times and rates printed here come from one short run and include
compilation: a smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import re
import sys
import time
import warnings
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-7b"
QUANT = dict(bits=4, dtype="float", block_size=64)
KV_BITS = 4
NUM_SLOTS, N_REQUESTS, MAX_NEW, PAGE_SIZE = 4, 8, 32, 16
#: prompt lengths alternate between these ranges: prefill buckets 512, 1024
PROMPT_RANGES = ((384, 512), (900, 1024))
#: logits of two paths that multiply the same bf16-rounded weights into
#: bf16 activations and differ only in accumulation order (tiles, shards,
#: the flash-decoding combine).  Three checks, each far from what a wrong
#: layout or scale gives (relative errors near 1, top-1 near 0):
#: * max |diff| over max |reference logit|;
#: * RMS of the diff over RMS of the reference logits.  28 layers of
#:   bf16 rounding add up: fused vs oracle at seed 0 measured 0.040 max
#:   and 0.037 RMS on a v5e;
#: * the share of positions whose argmax agrees.  Random-weight logits
#:   are near-Gaussian over 152k entries, so the argmax leads the
#:   runner-up by only ~0.2 sigma and rounding noise flips some positions.
LOGIT_MAX_REL_TOL = 0.1
LOGIT_RMS_REL_TOL = 0.05
TOP1_MIN = 0.5
#: decode steps read the kv4 cache: rounding noise pushes some cached
#: values across a 4-bit code boundary, a jump of a whole code step
#: (about 1/8 of the block's absmax), so decode-step logits of two paths
#: get looser max/RMS bounds — still far below a wrong layout's ~1
KV4_MAX_REL_TOL = 0.25
KV4_RMS_REL_TOL = 0.1
#: the four-chip teacher-forced comparison: prompts, prompt length, steps
TF_BATCH, TF_LEN, TF_STEPS = 4, 384, 8


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# phases shared by both modes


def require_tpu(n_chips: int):
    import jax

    devs = jax.devices()
    print(f"jax {jax.__version__}: {len(devs)} x {devs[0].device_kind} "
          f"(platform {devs[0].platform})", flush=True)
    check(devs[0].platform == "tpu",
          f"JAX finds no TPU (platform {devs[0].platform!r})")
    check(len(devs) >= n_chips, f"--chips {n_chips} but JAX sees "
          f"{len(devs)} device(s)")
    return devs


class CompileClock:
    """Sums JAX's own compile-phase durations (trace, lowering, backend
    compile) from its monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def build(cfg, seed: int):
    import jax

    from repro.configs import QuantConfig
    from repro.core.qtensor import QuantizedTensor
    from repro.models.quantize import bits_report, init_quantized_params

    qcfg = QuantConfig(**QUANT)
    t0 = time.perf_counter()
    params = init_quantized_params(jax.random.PRNGKey(seed), cfg, qcfg)
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    rep = bits_report(params)
    qts = [x for x in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(x, QuantizedTensor)]
    q_bytes = sum(a.nbytes for qt in qts for a in jax.tree.leaves(qt))
    q_params = sum(qt.n_params for qt in qts)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    print(f"build: {cfg.name} {qcfg.describe()} kv{cfg.kv_bits}, "
          f"{rep['quantized_params'] + rep['fp16_params']:,} params; "
          f"quantized matrices {8 * q_bytes / q_params:.3f} stored "
          f"bits/param, whole model {rep['avg_bits_per_param']:.3f} "
          f"bits/param (16-bit embedding and norms); {nbytes / 1e9:.3f} GB "
          f"on device; built in {dt:.1f} s", flush=True)
    return params


def make_prompts(vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for i in range(N_REQUESTS):
        lo, hi = PROMPT_RANGES[i % len(PROMPT_RANGES)]
        out.append(rng.integers(0, vocab, size=int(rng.integers(lo, hi + 1))))
    return out


def cache_len_for(prompts) -> int:
    n = max(len(p) for p in prompts) + MAX_NEW
    return -(-n // PAGE_SIZE) * PAGE_SIZE


def serve(server, prompts, vocab: int, label: str):
    """Submit every prompt, drain, check each request got exactly MAX_NEW
    in-vocabulary tokens.  Returns (token lists, seconds)."""
    ids = [server.submit(p, MAX_NEW) for p in prompts]
    steps0 = server.steps
    t0 = time.perf_counter()
    res = server.run_until_drained()
    dt = time.perf_counter() - t0
    outs = [res[i] for i in ids]
    for i, toks in zip(ids, outs):
        check(len(toks) == MAX_NEW, f"{label}: request {i} returned "
              f"{len(toks)} tokens, wanted {MAX_NEW}")
        check(all(0 <= t < vocab for t in toks),
              f"{label}: request {i} returned an out-of-vocabulary token")
    n = sum(len(t) for t in outs)
    print(f"{label}: {len(outs)} requests x {MAX_NEW} tokens in "
          f"{dt:.2f} s wall ({n / dt:.1f} tok/s), "
          f"{server.steps - steps0} engine steps", flush=True)
    return outs, dt


def compare_logits(test, ref, what: str, *, max_tol=LOGIT_MAX_REL_TOL,
                   rms_tol=LOGIT_RMS_REL_TOL) -> None:
    """Max and RMS of test - ref relative to ref, and argmax agreement over
    the last axis; fail above max_tol / rms_tol or below TOP1_MIN."""
    import numpy as np

    test = np.asarray(test, np.float32)
    ref = np.asarray(ref, np.float32)
    diff = test - ref
    err = float(np.abs(diff).max())
    scale = float(np.abs(ref).max())
    rel = err / max(scale, 1e-30)
    rms = float(np.sqrt(np.mean(diff ** 2) / max(np.mean(ref ** 2), 1e-30)))
    top1 = float((test.argmax(-1) == ref.argmax(-1)).mean())
    print(f"{what}: max |diff| {err:.5f} over max |logit| {scale:.4f} "
          f"(rel {rel:.5f}, tol {max_tol}); RMS rel {rms:.5f} "
          f"(tol {rms_tol}); top-1 agreement {top1:.4f} over "
          f"{test[..., 0].size} positions (min {TOP1_MIN})", flush=True)
    check(bool(np.isfinite(test).all()), f"{what}: non-finite logits")
    check(rel <= max_tol, f"{what}: max rel {rel:.5f} > {max_tol}")
    check(rms <= rms_tol, f"{what}: RMS rel {rms:.5f} > {rms_tol}")
    check(top1 >= TOP1_MIN, f"{what}: top-1 {top1:.4f} < {TOP1_MIN}")


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# one chip


def run_one_chip(devs, seed: int, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.analysis import audit
    from repro.configs.registry import get_arch
    from repro.models import lm
    from repro.serving import Server

    cfg = get_arch(ARCH).with_kv_quant(KV_BITS)
    params = build(cfg, seed)
    build_compile_s = clock.seconds
    prompts = make_prompts(cfg.vocab_size, seed + 1)

    server = Server(params, cfg, num_slots=NUM_SLOTS,
                    max_seq_len=cache_len_for(prompts), paged=True,
                    page_size=PAGE_SIZE)
    print(f"paged kv4 pool: {server.pool.allocator.n_usable} pages x "
          f"{PAGE_SIZE} tokens, {server.pool.kv_bytes()['total'] / 1e6:.1f}"
          " MB", flush=True)
    cold, t_cold = serve(server, prompts, cfg.vocab_size, "serve (cold)")
    serve_compile_s = clock.seconds - build_compile_s
    warm, t_warm = serve(server, prompts, cfg.vocab_size, "serve (warm)")
    check(warm == cold, "the warm rerun did not repeat the cold run's "
          "tokens")

    # the served decode step calls both Pallas kernels
    text = server.lower_decode().as_text()
    kernels = sorted(set(re.findall(r'kernel_name = "(\w+)"', text)))
    print(f"decode step: tpu_custom_call x {text.count('tpu_custom_call')},"
          f" kernels {kernels}", flush=True)
    check(audit.fused_signature_present(text),
          "no tpu_custom_call in the lowered decode step")
    check({"_qmatmul_kernel", "_dequant_kernel"} <= set(kernels),
          f"decode step lacks a Pallas kernel: {kernels}")

    # fused Pallas path vs the dequant_einsum oracle, same weights
    toks = jnp.asarray(prompts[0][None, :], jnp.int32)

    def logits_fn(mode):
        c = cfg.with_matmul_mode(mode)

        def f(p, t):
            h, _, _ = lm.backbone_seq(p, t, c)
            return lm.logits_from_hidden(p, h, c)

        return jax.jit(f)

    compare_logits(logits_fn("fused")(params, toks),
                   logits_fn("dequant_einsum")(params, toks),
                   f"prefill logits, fused Pallas vs dequant_einsum oracle "
                   f"({toks.shape[1]} tokens)")

    n_tok = N_REQUESTS * MAX_NEW
    print(f"smoke numbers (one short run, not a benchmark): compile "
          f"{build_compile_s:.1f} s in the build, {serve_compile_s:.1f} s "
          f"in the cold serve, {clock.seconds:.1f} s in all; cold serve "
          f"{t_cold:.2f} s; warm serve {t_warm:.2f} s = "
          f"{n_tok / t_warm:.1f} tok/s wall; peak_bytes_in_use "
          f"{peak_bytes(devs[0]):,}", flush=True)


# ---------------------------------------------------------------------------
# four chips


def rollout(params, cfg, prompts, n_steps: int, *, sharder=None,
            forced=None):
    """Prefill `prompts` [B, S] then decode n_steps, feeding `forced`
    tokens [n_steps, B] when given (greedy otherwise).  Returns (greedy
    tokens [n_steps + 1, B], logits [n_steps + 1, B, V] as f32 numpy)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm

    B, S = prompts.shape
    cache_len = S + n_steps
    kw, dec_kw, scope = {}, {}, contextlib.nullcontext
    if sharder is not None:
        cache_len = sharder.pad_cache_len(cache_len)
        kw = dict(constrain=sharder.constrain, q_pad=sharder.head_pad())
        dec_kw = dict(constrain=sharder.constrain,
                      decode_attn=sharder.decode_attn_fn(B, cache_len))
        scope = sharder.tp_scope

    def prefill(p, t):
        with scope():
            return lm.prefill(p, t, cfg, cache_len=cache_len, **kw)

    def decode(p, tok, c, pos):
        with scope():
            return lm.decode_step(p, tok, c, pos, cfg, **dec_kw)

    prefill = jax.jit(prefill)
    decode = jax.jit(decode, donate_argnums=2)
    logits, caches = prefill(params, prompts)
    if sharder is not None:
        caches = jax.device_put(caches, sharder.cache_spec_tree(caches, B))
    toks, logs = [], []
    for t in range(n_steps + 1):
        logs.append(np.asarray(logits, np.float32))
        toks.append(np.asarray(jnp.argmax(logits, -1)))
        if t == n_steps:
            break
        feed = toks[-1] if forced is None else forced[t]
        logits, caches = decode(params, jnp.asarray(feed, jnp.int32), caches,
                                jnp.int32(S + t))
    return np.stack(toks), np.stack(logs)


def run_four_chips(devs, seed: int, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_arch
    from repro.launch.mesh import make_mesh
    from repro.models.sharding import SeqShardFallbackWarning, Sharder
    from repro.serving import Server

    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    warnings.simplefilter("error", SeqShardFallbackWarning)
    cfg = get_arch(ARCH).with_kv_quant(KV_BITS)
    params = build(cfg, seed)                  # on device 0
    mesh = make_mesh((1, 4), ("data", "model"))
    sharder = Sharder(mesh, cfg, replicate_params_below=0)
    params_tp = jax.device_put(params, sharder.param_spec_tree(params))
    per_dev = defaultdict(int)
    for leaf in jax.tree.leaves(params_tp):
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    total = sum(a.nbytes for a in jax.tree.leaves(params))
    print("weights per device (addressable_shards): " + ", ".join(
        f"device {d}: {b / 1e9:.3f} GB" for d, b in sorted(per_dev.items()))
        + f" (unsharded model {total / 1e9:.3f} GB)", flush=True)
    check(len(per_dev) == 4 and min(per_dev.values()) >= total / 8,
          "the weights are not spread over all four devices")
    check(max(per_dev.values()) <= total / 2,
          "one device holds most of the weights")

    prompts = make_prompts(cfg.vocab_size, seed + 1)
    cache_len = cache_len_for(prompts)
    tp_server = Server(params_tp, cfg, num_slots=NUM_SLOTS,
                       max_seq_len=cache_len, sharder=sharder)
    kvb = tp_server.pool.kv_bytes()
    print(f"sharded kv4 slot pool: {kvb['total'] / 1e6:.1f} MB, "
          f"{kvb['per_device'] / 1e6:.1f} MB/device", flush=True)
    tp_out, t_tp = serve(tp_server, prompts, cfg.vocab_size,
                         "serve 1x4 mesh")
    one_server = Server(params, cfg, num_slots=NUM_SLOTS,
                        max_seq_len=cache_len)
    one_out, t_one = serve(one_server, prompts, cfg.vocab_size,
                           "serve one device")
    same = np.mean([a == b for x, y in zip(tp_out, one_out)
                    for a, b in zip(x, y)])
    first = np.mean([x[0] == y[0] for x, y in zip(tp_out, one_out)])
    print(f"served greedy tokens, mesh vs one device: {same:.4f} of "
          f"positions agree, first token {first:.4f} of requests "
          "(free-running: one near-tie flip changes the rest of a "
          "request; the logit check below is teacher-forced)", flush=True)

    tf = jnp.asarray(np.stack([p[:TF_LEN] for p in prompts[:TF_BATCH]]),
                     jnp.int32)
    toks_one, logs_one = rollout(params, cfg, tf, TF_STEPS)
    _, logs_tp = rollout(params_tp, cfg, tf, TF_STEPS, sharder=sharder,
                         forced=toks_one[:-1])
    compare_logits(logs_tp[0], logs_one[0],
                   f"prefill logits, 1x4 mesh vs one device ({TF_BATCH} "
                   f"prompts x {TF_LEN} tokens, last position)")
    compare_logits(logs_tp[1:], logs_one[1:],
                   f"teacher-forced decode logits over the kv4 cache, 1x4 "
                   f"mesh vs one device ({TF_STEPS} steps x {TF_BATCH} rows)",
                   max_tol=KV4_MAX_REL_TOL, rms_tol=KV4_RMS_REL_TOL)
    print(f"smoke numbers (one short run, not a benchmark): compile "
          f"{clock.seconds:.1f} s in all; mesh serve {t_tp:.2f} s, "
          f"one-device serve {t_one:.2f} s (both cold); peak_bytes_in_use "
          + ", ".join(f"device {d.id}: {peak_bytes(d):,}" for d in devs),
          flush=True)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its one-device "
                         "comparison (needs four chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the random weights and prompts")
    args = ap.parse_args(argv)
    try:
        devs = require_tpu(args.chips)
        from repro.utils.compile_cache import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}", flush=True)
        clock = CompileClock()
        if args.chips == 4:
            run_four_chips(devs, args.seed, clock)
        else:
            run_one_chip(devs, args.seed, clock)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
