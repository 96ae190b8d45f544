"""Model-tree quantization: policy, bits accounting, noise-lens equivalence,
proxy quantization wiring (paper §3)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import QuantConfig
from repro.configs.registry import get_arch
from repro.core.qtensor import QuantizedTensor
from repro.models import lm
from repro.models.quantize import (
    bits_report,
    dequantize_params,
    quantize_params,
    residual_outliers,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("h2o-danube-3-4b").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_policy_quantizes_matrices_not_vectors(tiny):
    cfg, params = tiny
    qp = quantize_params(params, QuantConfig(bits=4), cfg)
    leaves = jax.tree_util.tree_leaves_with_path(
        qp, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )
    kinds = {}
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        kinds[key] = isinstance(leaf, QuantizedTensor)
    assert any("wq" in k and v for k, v in kinds.items())
    assert any("w_down" in k and v for k, v in kinds.items())
    assert not any("norm" in k and v for k, v in kinds.items())
    assert not any("embed" in k and v for k, v in kinds.items())  # default off


def test_serving_equals_noise_lens(tiny):
    """Quantized-tree forward == dense forward on dequantized weights."""
    cfg, params = tiny
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    for qc in [QuantConfig(bits=4, dtype="float"),
               QuantConfig(bits=3, dtype="int", outlier_pct=0.05),
               QuantConfig(bits=5, dtype="quantile", centering=True)]:
        qp = quantize_params(params, qc, cfg)
        h, _, _ = lm.backbone_seq(qp, toks, cfg)
        ql = lm.logits_from_hidden(qp, h, cfg).astype(jnp.float32)
        dq = dequantize_params(qp)
        h2, _, _ = lm.backbone_seq(dq, toks, cfg)
        dl = lm.logits_from_hidden(dq, h2, cfg).astype(jnp.float32)
        assert float(jnp.max(jnp.abs(ql - dl))) < 0.02, qc


def test_bits_accounting(tiny):
    cfg, params = tiny
    qp = quantize_params(params, QuantConfig(bits=4, block_size=64), cfg)
    rep = bits_report(qp)
    assert rep["quantized_params"] > 0
    assert rep["fp16_params"] > 0  # embeddings + norms
    # quantized fraction pays 4.25 bits; overall between 4.25 and 16
    assert 4.25 < rep["avg_bits_per_param"] < 16
    rep8 = bits_report(quantize_params(params, QuantConfig(bits=8), cfg))
    assert rep8["avg_bits_per_param"] > rep["avg_bits_per_param"]


def test_proxy_outliers_pay_extra_bits(tiny):
    cfg, params = tiny
    q0 = bits_report(quantize_params(params, QuantConfig(bits=3), cfg))
    q2 = bits_report(
        quantize_params(params, QuantConfig(bits=3, outlier_pct=0.02), cfg)
    )
    assert q2["avg_bits_per_param"] > q0["avg_bits_per_param"]


def test_proxy_improves_3bit_quality(tiny):
    """Planted outlier dims: proxy quantization must reduce error (Fig. 4)."""
    cfg, params = tiny
    # plant outlier columns in the producing weights -> large hidden dims
    def plant(tree):
        out = jax.tree_util.tree_map_with_path(
            lambda p, x: x.at[..., ::97].multiply(12.0)
            if "w_down" in jax.tree_util.keystr(p) and x.ndim >= 2
            else x,
            tree,
        )
        return out

    planted = plant(params)
    j = residual_outliers(planted, cfg, 0.05)
    assert j is not None and j.shape[-1] == max(1, round(cfg.d_model * 0.05))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab_size)
    h, _, _ = lm.backbone_seq(planted, toks, cfg)
    ref = lm.logits_from_hidden(planted, h, cfg).astype(jnp.float32)

    errs = {}
    for pct in (0.0, 0.05):
        qp = quantize_params(planted, QuantConfig(bits=3, dtype="int",
                                                  outlier_pct=pct), cfg)
        h, _, _ = lm.backbone_seq(qp, toks, cfg)
        ql = lm.logits_from_hidden(qp, h, cfg).astype(jnp.float32)
        errs[pct] = float(jnp.mean(jnp.abs(ql - ref)))
    assert errs[0.05] < errs[0.0], errs


def test_quantized_moe_and_ssm_trees():
    for name in ("phi3.5-moe-42b-a6.6b", "mamba2-130m", "jamba-v0.1-52b"):
        cfg = get_arch(name).reduced()
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        qp = quantize_params(params, QuantConfig(bits=4), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        h, _, _ = lm.backbone_seq(qp, toks, cfg)
        logits = lm.logits_from_hidden(qp, h, cfg)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32)))), name
        if cfg.n_experts:
            # expert stacks quantized with E batch dim
            ffn = qp["stack"][0]["ffn"] if name != "jamba-v0.1-52b" else qp["stack"][1]["ffn"]
            assert isinstance(ffn["w_gate"], QuantizedTensor)
            assert not isinstance(ffn["router"], jnp.ndarray.__class__) or True


def test_dequantize_params_respects_original_dtype(tiny):
    """Regression: dequantize_params used to hardcode float32 out; a
    bf16 tree must round-trip to bf16 (QuantizedTensor records the
    quantizer's input dtype as orig_dtype), and an f32 tree to f32."""
    cfg, params = tiny
    for dt in (jnp.bfloat16, jnp.float32):
        cast = jax.tree.map(
            lambda a: a.astype(dt) if a.dtype == jnp.float32 else a, params
        )
        qp = quantize_params(cast, QuantConfig(bits=4), cfg)
        back = dequantize_params(qp)
        for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(cast),
            jax.tree_util.tree_leaves_with_path(back),
        ):
            assert jax.tree_util.keystr(pa) == jax.tree_util.keystr(pb)
            assert a.shape == b.shape, pa
            assert a.dtype == b.dtype, (pa, a.dtype, b.dtype)


@pytest.mark.parametrize("arch,reduce", [("tiny-650k", False),
                                         ("qwen2-7b", True)])
@pytest.mark.parametrize("qcfg", [
    QuantConfig(bits=4, dtype="float", block_size=64),
    QuantConfig(bits=3, dtype="quantile", block_size=32,
                quantize_embedding=True),
], ids=["float4-b64", "quantile3-b32-embed"])
def test_streamed_build_equals_dense_quantize(arch, reduce, qcfg):
    """init_quantized_params builds layer by layer (lm_head and a
    quantized embedding in row chunks — a tiny chunk_bytes forces many)
    yet yields the tree quantize_params(lm.init_params(...)) yields: same
    structure, metadata, codes, scales and codebooks, bit for bit.  The
    reference is compiled too: XLA's fused and op-by-op random normals
    can differ in the last ulp.  A dense embedding is kept in bf16, the
    dtype the forward reads it in."""
    from repro.models.quantize import init_quantized_params

    cfg = get_arch(arch)
    cfg = cfg.reduced() if reduce else cfg
    key = jax.random.PRNGKey(3)
    ref = jax.jit(lambda k: quantize_params(lm.init_params(k, cfg), qcfg,
                                            cfg))(key)
    got = init_quantized_params(key, cfg, qcfg, chunk_bytes=4096)
    if not isinstance(ref["embed"], QuantizedTensor):
        assert got["embed"].dtype == jnp.bfloat16
        ref["embed"] = ref["embed"].astype(jnp.bfloat16)
    leaves_r, tree_r = jax.tree_util.tree_flatten(ref)
    leaves_g, tree_g = jax.tree_util.tree_flatten(got)
    assert tree_r == tree_g
    for a, b in zip(leaves_r, leaves_g):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bool(jnp.all(a == b))


def test_streamed_build_rejects_proxy_quantization():
    from repro.models.quantize import init_quantized_params

    with pytest.raises(ValueError, match="outlier_pct"):
        init_quantized_params(jax.random.PRNGKey(0), get_arch("tiny-160k"),
                              QuantConfig(bits=4, outlier_pct=0.02))
