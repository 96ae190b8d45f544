"""The dense family: a GQA decoder (Llama / Qwen2 block) served with
k-bit float weights and a k-bit float KV cache.  Its description of a
configuration, its seeded weights, the adaptor that hands them to the
program under test, and its plain reference.

A configuration file (``bench/configs/<name>.json``) holds the model as
it is run, under the keys of its published ``config.json``.  From it this
module derives the sizes the reference and the cost functions use, and
the program's ``ArchConfig`` (its registry entry plus overrides), which
must agree with those sizes.

The weights are drawn here, not by the program: one jitted call turns a
seed into packed 4-bit codes and bf16 block scales in the K-major layout
the program serves (``packed [K/8, N]`` uint32 words, code ``p`` of word
``w`` at bits ``4p..4p+3`` holding row ``8w + p``; ``scales [K/64, N]``),
plus bf16 embeddings and f32 norm scales and biases.  The reference reads
the same arrays through its own decode, so it takes nothing the program
made.

The reference, in ``jax.numpy``, follows the configuration file, not the
program: RMSNorm scaled by ``1 + scale``, q/k/v projections (with bias
where the file says so), rotary embeddings on the two halves of each
head, causal softmax attention, a SiLU-gated MLP, and an untied 4-bit
head.  Weights are the benchmark's packed codes and block scales,
decoded here with the paper's float data type (``float_codebook``).

The KV cache format is part of what is served: a prompt attends to its
own keys and values as computed, and every decoded position attends to
keys and values rounded to the cache's format (blockwise absmax over the
``n_kv_heads * head_dim`` features of a token, nearest code, bf16 scale),
which is what a prefill followed by decoding through the cache computes.

``precision="f32"`` computes in float32 at ``highest`` matmul precision.
``precision="fp8"`` is the control: every matmul operand rounded to
float8 e4m3 with a per-row (activations) or per-column (weights) absmax
scale, one step below the bf16 the configuration computes in.

The model is run layer by layer over a group of sequences padded to one
length, so that a group's shape, and its compiled program, is fixed per
cell.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    qkv_bias: bool
    w_bits: int
    w_ebits: int
    w_block: int
    kv_bits: int
    kv_ebits: int
    kv_block: int

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def matrices(self) -> dict:
        """Per-layer quantized matrices: name -> (K, N), y = x[.., K] @ W[K, N]."""
        D, F = self.d_model, self.d_ff
        return {"wq": (D, self.q_dim), "wk": (D, self.kv_dim),
                "wv": (D, self.kv_dim), "wo": (self.q_dim, D),
                "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}

    def matmul_params(self) -> int:
        """Parameters a token multiplies: every layer matrix and lm_head."""
        per_layer = sum(k * n for k, n in self.matrices().values())
        return self.n_layers * per_layer + self.d_model * self.vocab

    def decode_flops(self, rows: int, live: float) -> float:
        """Model FLOPs of a decode step over `rows` active rows whose live
        positions sum to `live`: 2 x the parameters a token multiplies x
        rows, plus attention, 4 x layers x query width x live."""
        return (2.0 * self.matmul_params() * rows
                + 4.0 * self.n_layers * self.q_dim * live)


def spec_from_config(cfg: dict) -> ModelSpec:
    q = cfg["quant"]
    w, kv = q["weights"], q["kv_cache"]
    if w["format"] != "float" or kv["format"] != "float":
        raise ValueError("only float-format weights and KV caches are described")
    scaling = cfg.get("rope_scaling")
    if scaling and "rope_scaling" not in cfg.get("assumed", {}):
        raise ValueError("RoPE scaling is applied by neither the program nor "
                         "the reference; a configuration that has it states "
                         "the departure under 'assumed'")
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("hidden_size must divide into the attention heads")
    return ModelSpec(
        name=cfg["name"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        qkv_bias=bool(cfg["qkv_bias"]),
        w_bits=w["bits"], w_ebits=w["exponent_bits"], w_block=w["block_size"],
        kv_bits=kv["bits"], kv_ebits=kv["exponent_bits"],
        kv_block=kv["block_size"])


def float_codebook(bits: int, exponent_bits: int) -> np.ndarray:
    """The paper's k-bit float data type (App. A): sign, E exponent bits
    with bias 2^(E-1)+1 and subnormals, no NaN/Inf, normalised to absmax 1
    and sorted (f32 [2^bits])."""
    E, M = exponent_bits, bits - 1 - exponent_bits
    bias = 2 ** (E - 1) + 1
    vals = []
    for s in (1.0, -1.0):
        for e in range(2 ** E):
            for m in range(2 ** M):
                f = m / 2 ** M
                vals.append(s * (2.0 ** (1 - bias) * f if e == 0
                                 else 2.0 ** (e - bias) * (1 + f)))
    vals = np.asarray(vals, np.float64)
    return np.sort(vals / np.abs(vals).max()).astype(np.float32)


def seed_key(seed: int):
    """A PRNG key for any seed below 2**64 (seeds may exceed 32 bits)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} out of range")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def make_weights(spec: ModelSpec, seed: int) -> dict:
    """Every weight of the model from `seed`, in one jitted call on the
    default device, in the dtypes they are served in."""
    cb = float_codebook(spec.w_bits, spec.w_ebits)
    cb_rms = float(np.sqrt(np.mean(cb.astype(np.float64) ** 2)))
    cpw, B, L = 32 // spec.w_bits, spec.w_block, spec.n_layers

    def packed_matrix(key, lead, K, N, std):
        kc, ks = jax.random.split(key)
        packed = jax.random.bits(kc, lead + (K // cpw, N), jnp.uint32)
        jitter = jnp.exp(0.25 * jax.random.normal(ks, lead + (K // B, N)))
        scales = (std / cb_rms * jitter).astype(jnp.bfloat16)
        return {"packed": packed, "scales": scales}

    def gen(key):
        ks = iter(jax.random.split(key, 32))
        stds = {"wq": spec.d_model ** -0.5, "wk": spec.d_model ** -0.5,
                "wv": spec.d_model ** -0.5, "wo": spec.q_dim ** -0.5,
                "w_gate": spec.d_model ** -0.5, "w_up": spec.d_model ** -0.5,
                "w_down": spec.d_ff ** -0.5}
        layers = {name: packed_matrix(next(ks), (L,), K, N, stds[name])
                  for name, (K, N) in spec.matrices().items()}
        for name in ("attn_norm", "mlp_norm"):
            layers[name] = 0.1 * jax.random.normal(next(ks), (L, spec.d_model))
        if spec.qkv_bias:
            for name, n in (("bq", spec.q_dim), ("bk", spec.kv_dim),
                            ("bv", spec.kv_dim)):
                layers[name] = 0.1 * jax.random.normal(next(ks), (L, n))
        return {
            "embed": jax.random.normal(next(ks), (spec.vocab, spec.d_model),
                                       jnp.bfloat16),
            "final_norm": 0.1 * jax.random.normal(next(ks), (spec.d_model,)),
            "lm_head": packed_matrix(next(ks), (), spec.d_model, spec.vocab,
                                     spec.d_model ** -0.5),
            "codebook": jnp.asarray(cb),
            "layers": layers,
        }

    if any(k % B or k % cpw or B % cpw
           for k, _ in spec.matrices().values()):
        raise ValueError("every reduction dim must tile whole blocks and words")
    return jax.jit(gen)(seed_key(seed))


# --------------------------------------------------------------------------
# the program under test
# --------------------------------------------------------------------------

def program_config(cfg: dict, spec: ModelSpec):
    """The program's ArchConfig for this configuration, checked against the
    benchmark's own sizes."""
    from repro.configs.registry import get_arch

    p = cfg["program"]
    arch = dataclasses.replace(get_arch(p["registry_arch"]), **p["overrides"])
    arch = arch.with_kv_quant(spec.kv_bits, block_size=spec.kv_block,
                              dtype="float")
    want = {"d_model": spec.d_model, "n_layers": spec.n_layers,
            "n_heads": spec.n_heads, "n_kv_heads": spec.n_kv_heads,
            "head_dim": spec.head_dim, "d_ff": spec.d_ff,
            "vocab_size": spec.vocab, "rope_theta": spec.rope_theta,
            "qkv_bias": spec.qkv_bias, "tie_embeddings": False,
            "family": "dense", "act": "silu", "norm_type": "rmsnorm",
            "sliding_window": 0, "attn_logit_softcap": 0.0,
            "final_logit_softcap": 0.0, "qk_norm": False,
            "post_block_norm": False}
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config disagrees with {cfg['name']}: {bad}")
    from repro.core.codebooks import PAPER_EXPONENT_BITS

    if PAPER_EXPONENT_BITS[spec.kv_bits] != spec.kv_ebits:
        raise ValueError("the program's KV float format has other exponent bits")
    return arch


def program_params(weights: dict, spec: ModelSpec) -> dict:
    """Wrap the benchmark's arrays in the program's parameter tree."""
    from repro.core.qtensor import QuantizedTensor

    L = spec.n_layers

    def qt(m, K, N, lead, transposed):
        return QuantizedTensor(
            packed=m["packed"], scales=m["scales"], means=None,
            codebook=jnp.broadcast_to(weights["codebook"], lead + (2 ** spec.w_bits,)),
            outlier_vals=None, outlier_idx=None, quant_shape=(N, K),
            bits=spec.w_bits, block_size=spec.w_block, dtype_name="float",
            centering=False, outlier_axis=-1, transposed=transposed,
            structured=True, orig_dtype="float32")

    lw = weights["layers"]
    mats = {n: {"w": qt(lw[n], K, N, (L,), True)}
            for n, (K, N) in spec.matrices().items()}
    if spec.qkv_bias:
        for n, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")):
            mats[n]["b"] = lw[b]
    layer = {
        "mixer_norm": {"scale": lw["attn_norm"]},
        "mixer": {n: mats[n] for n in ("wq", "wk", "wv", "wo")},
        "ffn_norm": {"scale": lw["mlp_norm"]},
        "ffn": {n: mats[n] for n in ("w_gate", "w_up", "w_down")},
    }
    return {
        "embed": weights["embed"],
        "stack": [layer],
        "final_norm": {"scale": weights["final_norm"]},
        "lm_head": qt(weights["lm_head"], spec.d_model, spec.vocab, (), False),
    }


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

_FP8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, precision):
    """a [..., K] @ w [K, N] in f32, or with fp8-rounded operands."""
    if precision == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.einsum("...k,kn->...n", a, w)


def decode_matrix(packed, scales, codebook, bits: int, block: int):
    """packed [K/cpw, N] uint32 + scales [K/block, N] -> f32 [K, N]."""
    cpw = 32 // bits
    shifts = jnp.arange(cpw, dtype=jnp.uint32) * bits
    codes = (packed[:, None, :] >> shifts[None, :, None]) & ((1 << bits) - 1)
    codes = codes.reshape(-1, packed.shape[-1])
    vals = jnp.asarray(codebook)[codes.astype(jnp.int32)]
    return vals * jnp.repeat(scales.astype(jnp.float32), block, axis=0)


def kv_round(x, codebook, block: int):
    """Round token rows x [..., feat] to the cache format and back."""
    feat = x.shape[-1]
    xb = x.reshape(x.shape[:-1] + (feat // block, block))
    absmax = jnp.maximum(jnp.max(jnp.abs(xb), axis=-1, keepdims=True), 1e-12)
    cb = jnp.asarray(codebook)
    bounds = (cb[:-1] + cb[1:]) / 2
    # nearest code, ties to the lower one
    codes = jnp.sum((xb / absmax)[..., None] > bounds, axis=-1)
    scale = absmax.astype(jnp.bfloat16).astype(jnp.float32)
    return (cb[codes] * scale).reshape(x.shape)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, pos, theta):
    """x [n, T, heads, hd]; pos [T]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, kq, vq, n_prompt, precision, q_block):
    """Causal GQA attention.  q [n,T,H,hd]; k,v,kq,vq [n,T,KV,hd];
    query rows at or past a sequence's prompt length read the rounded
    kq/vq, prompt rows the raw k/v."""
    n, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(n, T, KV, G, hd)
    if precision == "fp8":
        qg, k, v, kq, vq = (_fp8(a, -1) for a in (qg, k, v, kq, vq))
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * q_block, q_block, 1)
        qpos = i * q_block + jnp.arange(q_block)
        causal = kpos[None, :] <= qpos[:, None]                 # [B, T]
        decode = qpos[None, :] >= n_prompt[:, None]             # [n, B]

        def attend(kk, vv):
            s = jnp.einsum("nbkgd,ntkd->nkgbt", qb, kk) * hd ** -0.5
            s = jnp.where(causal, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            if precision == "fp8":
                p = _fp8(p, -1)
            return jnp.einsum("nkgbt,ntkd->nbkgd", p, vv)

        o = jnp.where(decode[:, :, None, None, None],
                      attend(kq, vq), attend(k, v))
        return o.reshape(n, q_block, H * hd)

    out = jax.lax.map(block, jnp.arange(T // q_block))          # [T/B, n, B, D]
    return jnp.moveaxis(out, 0, 1).reshape(n, T, H * hd)


@partial(jax.jit, static_argnames=("spec", "precision"))
def _layer(x, lw, n_prompt, *, spec: ModelSpec, precision: str):
    cb = float_codebook(spec.w_bits, spec.w_ebits)
    kv_cb = float_codebook(spec.kv_bits, spec.kv_ebits)
    W = {name: decode_matrix(lw[name]["packed"], lw[name]["scales"], cb,
                             spec.w_bits, spec.w_block)
         for name in spec.matrices()}
    n, T, _ = x.shape
    pos = jnp.arange(T)
    h = _rms(x, lw["attn_norm"], spec.eps)
    q, k, v = (_mm(h, W[m], precision) for m in ("wq", "wk", "wv"))
    if spec.qkv_bias:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    hd = spec.head_dim
    q = _rope(q.reshape(n, T, spec.n_heads, hd), pos, spec.rope_theta)
    k = _rope(k.reshape(n, T, spec.n_kv_heads, hd), pos, spec.rope_theta)
    v = v.reshape(n, T, spec.n_kv_heads, hd)
    kq = kv_round(k.reshape(n, T, -1), kv_cb, spec.kv_block).reshape(k.shape)
    vq = kv_round(v.reshape(n, T, -1), kv_cb, spec.kv_block).reshape(v.shape)
    o = _attention(q, k, v, kq, vq, n_prompt, precision, min(T, 256))
    x = x + _mm(o, W["wo"], precision)
    h = _rms(x, lw["mlp_norm"], spec.eps)
    g = _mm(h, W["w_gate"], precision)
    u = _mm(h, W["w_up"], precision)
    return x + _mm(jax.nn.silu(g) * u, W["w_down"], precision)


@partial(jax.jit, static_argnames=("spec", "precision", "chunk"))
def _head(h, final_norm, head, want, *, spec: ModelSpec, precision: str,
          chunk: int):
    """For hidden rows h [R, D]: the argmax token, the largest logit and
    the logits of the token ids `want` [R, k], with the head decoded in
    column chunks so that no [R, V] array is formed."""
    cb = float_codebook(spec.w_bits, spec.w_ebits)
    h = _rms(h, final_norm, spec.eps)
    R = h.shape[0]
    best = jnp.full((R,), -jnp.inf)
    arg = jnp.zeros((R,), jnp.int32)
    picked = jnp.zeros(want.shape, jnp.float32)
    for c0 in range(0, spec.vocab, chunk):
        c1 = min(spec.vocab, c0 + chunk)
        w = decode_matrix(head["packed"][:, c0:c1], head["scales"][:, c0:c1],
                          cb, spec.w_bits, spec.w_block)
        z = _mm(h, w, precision)
        m = jnp.max(z, axis=-1)
        a = jnp.argmax(z, axis=-1).astype(jnp.int32) + c0
        arg = jnp.where(m > best, a, arg)
        best = jnp.maximum(best, m)
        inside = (want >= c0) & (want < c1)
        got = jnp.take_along_axis(z, jnp.clip(want - c0, 0, c1 - c0 - 1), -1)
        picked = jnp.where(inside, got, picked)
    return arg, best, picked


def score(weights: dict, spec: ModelSpec, seqs, want, *, group: int,
          length: int, rows: int, precision: str = "f32"):
    """Next-token scores at the decoded positions of each sequence.

    ``seqs``: list of (tokens int [T_i <= length], n_prompt, n_out): the
    prompt and the served tokens but the last, so positions
    ``n_prompt - 1 .. n_prompt + n_out - 2`` predict the n_out served
    tokens.  ``want``: per sequence an int array [n_out, k] of token ids
    whose logits are read.  Sequences run `group` at a time, padded to
    `length`, with `rows` >= every n_out.  Returns per sequence numpy
    (argmax [n_out], max logit [n_out], logits of `want` [n_out, k])."""
    out = []
    k = want[0].shape[1]
    with jax.default_matmul_precision("highest"):
        for g0 in range(0, len(seqs), group):
            part = seqs[g0:g0 + group]
            toks = np.zeros((group, length), np.int32)
            n_prompt = np.full(group, length, np.int32)
            pick = np.zeros((group, rows), np.int32)
            ids = np.zeros((group, rows, k), np.int32)
            for i, (t, L, n_out) in enumerate(part):
                toks[i, :len(t)] = t
                n_prompt[i] = L
                pick[i, :n_out] = np.arange(L - 1, L - 1 + n_out)
                ids[i, :n_out] = want[g0 + i]
            x = weights["embed"][jnp.asarray(toks)].astype(jnp.float32)
            for li in range(spec.n_layers):
                lw = jax.tree.map(lambda a: a[li], weights["layers"])
                x = _layer(x, lw, jnp.asarray(n_prompt), spec=spec,
                           precision=precision)
            h = x[jnp.arange(group)[:, None], jnp.asarray(pick)]
            res = _head(h.reshape(group * rows, -1), weights["final_norm"],
                        weights["lm_head"],
                        jnp.asarray(ids.reshape(group * rows, k)), spec=spec,
                        precision=precision, chunk=min(spec.vocab, 16384))
            arg, best, picked = (np.asarray(r).reshape((group, rows) + r.shape[1:])
                                 for r in res)
            for i, (_, _, n_out) in enumerate(part):
                out.append((arg[i, :n_out], best[i, :n_out], picked[i, :n_out]))
    return out
