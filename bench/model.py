"""The benchmark's own description of a configuration, its seeded weights,
and the adaptor that hands them to the program under test.

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
"""

from __future__ import annotations

import dataclasses

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
    import jax

    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} out of range")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def make_weights(spec: ModelSpec, seed: int) -> dict:
    """Every weight of the model from `seed`, in one jitted call on the
    default device, in the dtypes they are served in."""
    import jax
    import jax.numpy as jnp

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
    import jax.numpy as jnp
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
