"""Post-training quantization of a model parameter tree (the paper's
zero-shot setting: quantize weights directly, no data, no optimization).

Policy (paper §4): every parameter MATRIX is quantized to k-bit — attention
projections, FFN, SSM in/out projections, MoE expert matrices, lm_head.
Vectors (norms, biases, conv filters, SSM scalars) and the MoE router stay
16-bit; embeddings stay 16-bit by default (both switchable).

2-D weights [In, Out] are stored TRANSPOSED in the QuantizedTensor
([Out, In]) so quantization blocks run along the reduction dim — the
Pallas kernel layout (docs/quantization.md#packing-layout-corepackingpy);
the paper's bits accounting is unchanged by the layout.

Proxy quantization (§3, Eq. 2): producer-weight std picks the outlier
input dims kept in 16-bit.  Within-block producers are exact (w_down <-
w_up, wo <- wv with GQA group tiling); residual-stream consumers share one
model-wide outlier set J_residual from the mean producer std across layers
(emergent outliers are global across layers — Dettmers et al. 2022a); this
adaptation is documented in docs/quantization.md#proxy-quantization-
coreproxypy-modelsquantizepy.

Mixed precision: ``quantize_tree(params, cfg, qcfg=..., plan=...)`` is
the general entry point.  Every quantizable unit (one stored parameter
matrix, possibly scan-stacked over layers) has a stable slash-joined
name ("stack/0/mixer/wq", "stack/0/ffn/w_down", "lm_head", ...); a
``PrecisionPlan`` (precision/plan.py) maps unit names to per-matrix
QuantConfig overrides (bits/dtype/block_size/centering), with bits>=16
meaning "leave this matrix in 16-bit".  ``quantize_params`` is the
uniform special case.  Granularity note: scan-stacked weights share one
static bit-width across the layers stacked into a single leaf, so the
planning unit is (period position, module), not the individual layer —
docs/quantization.md#mixed-precision-plans-precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.core.proxy import outlier_indices_topk
from repro.core.qtensor import QuantizedTensor, quantize_tensor, to_structured

#: module names whose {"w": ...} consumes the residual stream [D -> *]
_RESIDUAL_CONSUMERS = {"wq", "wk", "wv", "w_gate", "w_up", "in_proj", "frame_proj"}


def _n_outliers(dim: int, pct: float) -> int:
    return max(1, int(round(dim * pct))) if pct > 0 else 0


def _quantize_matrix(w, qcfg: QuantConfig, outlier_idx=None):
    """w [..., In, Out] -> QT storing [..., Out, In], blocks along In."""
    wt = jnp.swapaxes(w, -1, -2)
    return to_structured(quantize_tensor(
        wt,
        bits=qcfg.bits,
        dtype=qcfg.dtype,
        block_size=qcfg.block_size,
        batch_dims=wt.ndim - 2,
        centering=qcfg.centering,
        exponent_bits=qcfg.exponent_bits,
        outlier_idx=outlier_idx,
        outlier_axis=-1,
        transposed=True,
    ))


def _producer_std(w) -> jnp.ndarray:
    """std over the input dim for each output unit; w [..., In, Out] -> [..., Out]."""
    return jnp.std(w.astype(jnp.float32), axis=-2)


def _bc(idx, batch_shape):
    if idx is None:
        return None
    return jnp.broadcast_to(idx, tuple(batch_shape) + idx.shape[-1:])


def residual_outliers(params: dict, cfg, pct: float):
    """Model-wide outlier dims of the residual stream -> [n_out] or None."""
    if pct <= 0:
        return None
    stds = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        if "w" in keys and any(k in ("w_down", "wo", "out_proj") for k in keys):
            if hasattr(leaf, "ndim") and leaf.ndim >= 2 and leaf.shape[-1] == cfg.d_model:
                stds.append(_producer_std(leaf).reshape(-1, cfg.d_model).mean(0))
    if not stds:
        return None
    mean_std = jnp.mean(jnp.stack(stds), axis=0)
    return outlier_indices_topk(mean_std, _n_outliers(cfg.d_model, pct))


def _module_outliers(name: str, module: dict, container: dict, cfg, qcfg, j_res):
    """Outlier input-dim indices for a dense module's weight (or None)."""
    if qcfg.outlier_pct <= 0:
        return None
    w = module["w"]
    batch_shape = w.shape[:-2]
    if name in _RESIDUAL_CONSUMERS and w.shape[-2] == cfg.d_model:
        return _bc(j_res, batch_shape)
    if name == "w_down" and "w_up" in container:
        std = _producer_std(container["w_up"]["w"])  # [..., F]
        return outlier_indices_topk(std, _n_outliers(w.shape[-2], qcfg.outlier_pct))
    if name == "wo" and "wv" in container:
        std = _producer_std(container["wv"]["w"])  # [..., K*Dh]
        if cfg.n_heads and cfg.n_kv_heads and cfg.n_heads != cfg.n_kv_heads:
            g = cfg.n_heads // cfg.n_kv_heads
            std = jnp.repeat(
                std.reshape(batch_shape + (cfg.n_kv_heads, cfg.head_dim)), g, axis=-2
            ).reshape(batch_shape + (cfg.n_heads * cfg.head_dim,))
        # map producer unit j to consumer input dim j (identity layout)
        return outlier_indices_topk(std, _n_outliers(w.shape[-2], qcfg.outlier_pct))
    if name == "lm_head" and w.shape[-2] == cfg.d_model:
        return _bc(j_res, batch_shape)
    return None


def quantize_unit(kind: str, w, qcfg: QuantConfig, outlier_idx=None):
    """Quantize ONE unit's weight the way the tree walk stores it.

    kind "matrix"/"moe": [..., In, Out] -> transposed QT, blocks along In.
    kind "lm_head"/"embed": [V, D] is already (out, in) kernel layout.
    The profiler (precision/profile.py) calls this too, so sensitivity
    scores are measured on exactly the storage layout that serves.
    """
    if kind in ("matrix", "moe"):
        return _quantize_matrix(w, qcfg, outlier_idx=outlier_idx)
    return to_structured(quantize_tensor(
        w, bits=qcfg.bits, dtype=qcfg.dtype,
        block_size=qcfg.block_size, batch_dims=0,
        centering=qcfg.centering, exponent_bits=qcfg.exponent_bits,
        outlier_idx=outlier_idx, outlier_axis=-1,
    ))


def _walk_units(params, cfg, base: QuantConfig, visit):
    """Recurse `params`, calling ``visit(name, kind, w, tree)`` on every
    quantizable unit; `visit` returns the replacement weight (or the
    original to leave it dense).  `name` is the stable slash-joined tree
    path, `kind` in {"matrix", "moe", "lm_head", "embed"}.  The `base`
    config only gates WHICH units are visited (lm_head/embed switches);
    per-unit bit-widths are the visitor's business."""

    def walk(tree, path):
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v, path + (str(i),)) for i, v in enumerate(tree))
        if not isinstance(tree, dict):
            return tree
        out = {}
        for name, val in tree.items():
            unit = "/".join(path + (name,))
            # dense module {"w": matrix, ("b": bias)}
            if (
                isinstance(val, dict)
                and "w" in val
                and hasattr(val["w"], "ndim")
                and val["w"].ndim >= 2
            ):
                q = dict(val)
                q["w"] = visit(unit, "matrix", val["w"], tree)
                out[name] = q
            # MoE expert stacks: raw arrays [n_p, E, In, Out]
            elif name in ("w_gate", "w_up", "w_down") and hasattr(val, "ndim") and val.ndim == 4:
                out[name] = visit(unit, "moe", val, tree)
            elif name == "lm_head" and base.quantize_lm_head and hasattr(val, "ndim"):
                out[name] = visit(unit, "lm_head", val, tree)
            elif name == "embed" and base.quantize_embedding and hasattr(val, "ndim"):
                out[name] = visit(unit, "embed", val, tree)
            else:
                out[name] = walk(val, path + (name,))
        return out

    return walk(params, ())


def _unit_outliers(kind, name, w, container, cfg, qcfg, j_res):
    """Proxy-quantization outlier indices for one unit (or None)."""
    if qcfg.outlier_pct <= 0:
        return None
    module = name.rsplit("/", 1)[-1]
    if kind == "matrix":
        return _module_outliers(module, {"w": w}, container, cfg, qcfg, j_res)
    if kind == "moe":
        if module == "w_down" and "w_up" in container:
            std = _producer_std(container["w_up"])
            return outlier_indices_topk(
                std, _n_outliers(w.shape[-2], qcfg.outlier_pct)
            )
        if j_res is not None and w.shape[-2] == cfg.d_model:
            return _bc(j_res, w.shape[:2])
        return None
    if kind == "lm_head":
        return j_res[None] if j_res is not None else None
    return None  # embed: input dim is the vocab, no residual outliers


def quantize_tree(params, cfg, *, qcfg: QuantConfig | None = None, plan=None):
    """Params tree -> same tree with weight matrices as QuantizedTensors.

    `qcfg` quantizes every unit uniformly; a `plan` (precision/plan.py)
    overrides bits/dtype/block_size/centering per unit name, with
    bits >= 16 leaving that matrix dense.  Residual-stream outlier sets
    (proxy quantization) are computed once from the BASE config's
    outlier_pct and shared by all units, exactly as in the uniform path.
    """
    if plan is None and qcfg is None:
        raise ValueError("quantize_tree needs qcfg and/or plan")
    base = qcfg if qcfg is not None else plan.default_config()
    if plan is not None and plan.arch and plan.arch != cfg.name:
        raise ValueError(
            f"plan was built for arch {plan.arch!r}, not {cfg.name!r} "
            "(rebuild with precision.build_plan, or clear plan.arch)"
        )
    j_res = residual_outliers(params, cfg, base.outlier_pct)
    visited: set = set()

    def visit(name, kind, w, container):
        visited.add(name)
        ucfg = base if plan is None else plan.config_for(name, base)
        if ucfg.bits >= 16:
            return w  # plan keeps this matrix dense 16-bit
        oidx = _unit_outliers(kind, name, w, container, cfg, ucfg, j_res)
        return quantize_unit(kind, w, ucfg, outlier_idx=oidx)

    out = _walk_units(params, cfg, base, visit)
    if plan is not None:
        unknown = sorted(set(plan.assignments) - visited)
        if unknown:
            raise ValueError(
                f"plan assigns units not present in this tree: {unknown} "
                f"(known units: {sorted(visited)}); a typo'd or stale plan "
                "would otherwise silently fall back to the default bits"
            )
    return out


def quantize_params(params, qcfg: QuantConfig, cfg):
    """Uniform quantization of a params tree (the paper's setting)."""
    return quantize_tree(params, cfg, qcfg=qcfg)


def init_quantized_params(key, cfg, qcfg: QuantConfig, *,
                          chunk_bytes: int = 256 << 20) -> dict:
    """``quantize_params(lm.init_params(key, cfg), qcfg, cfg)`` without
    ever holding the dense tree: the same keys, codes and scales, built
    one layer at a time, so the device peaks at the packed model plus one
    dense layer (a full-width model whose f32 tree outgrows the chip).

    Each layer of the period scan is initialised and quantized by one
    jitted call (a leading axis of 1 makes it the stacked unit's single
    item) and written into the preallocated stacked leaves in place.
    lm_head (and a quantized embedding) is drawn whole in f32 and
    quantized in row chunks of at most `chunk_bytes`: blocks never cross
    rows, so the codes and scales are those of the whole matrix.  A dense
    embedding is stored in bf16 — the dtype the forward reads it in
    (lm.embed_inputs).

    Proxy quantization (``outlier_pct > 0``) needs every layer's producer
    statistics at once and is rejected: build the dense tree for it."""
    from repro.models import blocks, lm
    from repro.models.layers import init_norm

    if qcfg.outlier_pct > 0:
        raise ValueError("init_quantized_params cannot stream proxy "
                         "quantization (outlier_pct > 0): the residual "
                         "outlier set spans every layer")
    ks = jax.random.split(key, 3)
    n_periods = cfg.n_layers // cfg.scan_period()
    put = jax.jit(
        lambda stacked, one, i: jax.tree.map(
            lambda s, o: jax.lax.dynamic_update_slice_in_dim(s, o, i, 0),
            stacked, one),
        donate_argnums=0)

    stack = []
    for j, (mixer, ffn) in enumerate(blocks.stack_schedule(cfg)):
        keys = jax.random.split(jax.random.fold_in(ks[1], j), n_periods)
        layer = jax.jit(lambda k, mixer=mixer, ffn=ffn: quantize_tree(
            jax.tree.map(lambda a: a[None],
                         blocks.init_layer(k, mixer, ffn, cfg)),
            cfg, qcfg=qcfg))
        stacked = None
        for i, k in enumerate(keys):
            one = layer(k)
            if stacked is None:
                stacked = jax.tree.map(
                    lambda a: jnp.zeros((n_periods,) + a.shape[1:], a.dtype),
                    one)
            stacked = put(stacked, one, i)
        stack.append(stacked)

    params = {"stack": stack,
              "final_norm": init_norm(cfg.d_model, cfg.norm_type)}
    if qcfg.quantize_embedding:
        params["embed"] = _quantized_random_rows(
            lm.init_embed, ks[0], cfg, "embed", qcfg, chunk_bytes)
    else:
        params["embed"] = jax.jit(
            lambda k: lm.init_embed(k, cfg).astype(jnp.bfloat16))(ks[0])
    if not cfg.tie_embeddings:
        if qcfg.quantize_lm_head:
            params["lm_head"] = _quantized_random_rows(
                lm.init_lm_head, ks[2], cfg, "lm_head", qcfg, chunk_bytes)
        else:
            params["lm_head"] = jax.jit(lm.init_lm_head,
                                        static_argnums=1)(ks[2], cfg)
    return params


def _quantized_random_rows(init_fn, key, cfg, kind: str, qcfg: QuantConfig,
                           chunk_bytes: int):
    """``quantize_unit(kind, init_fn(key, cfg), qcfg)`` for a [V, D]
    matrix, quantized in row chunks when that is exact: the codebook is
    static (not quantile) and whole blocks tile a row."""
    import dataclasses

    w = jax.jit(init_fn, static_argnums=1)(key, cfg)
    V, D = w.shape
    n = 1
    if qcfg.dtype != "quantile" and D % qcfg.block_size == 0:
        n = -(-w.nbytes // chunk_bytes)
        while V % n:
            n += 1
    rows = V // n
    part = jax.jit(lambda w, i: quantize_unit(
        kind, jax.lax.dynamic_slice_in_dim(w, i * rows, rows), qcfg))
    parts = [part(w, i) for i in range(n)]
    del w
    if n == 1:
        return parts[0]

    def cat(name):
        leaves = [getattr(p, name) for p in parts]
        return None if leaves[0] is None else jnp.concatenate(leaves, -1)

    return dataclasses.replace(parts[0], packed=cat("packed"),
                               scales=cat("scales"), means=cat("means"),
                               quant_shape=(V, D))


def quantizable_units(params, cfg, qcfg: QuantConfig | None = None) -> dict:
    """Enumerate the tree's quantizable units WITHOUT quantizing:
    {name: {"kind", "w", "n_params", "shape", "outlier_idx"}} — the
    planning universe of precision/profile.py, guaranteed to agree with
    quantize_tree because both run the same walk.  "outlier_idx" is the
    proxy-quantization index set the quantizer would use under `qcfg`
    (None when outlier_pct == 0), so sensitivity profiling measures the
    exact storage layout that serves."""
    base = qcfg if qcfg is not None else QuantConfig()
    j_res = residual_outliers(params, cfg, base.outlier_pct)
    units: dict = {}

    def visit(name, kind, w, container):
        units[name] = {
            "kind": kind,
            "w": w,
            "n_params": int(w.size),
            "shape": tuple(w.shape),
            "outlier_idx": _unit_outliers(kind, name, w, container, cfg,
                                          base, j_res),
        }
        return w

    _walk_units(params, cfg, base, visit)
    return units


def bits_report(qparams) -> dict:
    """Total-model-bits accounting over a quantized tree (paper's x-axis)."""
    q_bits = q_stored = 0.0
    q_params = fp_params = 0
    for leaf in jax.tree_util.tree_leaves(
        qparams, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    ):
        if isinstance(leaf, QuantizedTensor):
            bd = leaf.bits_breakdown()
            q_bits += bd.ideal_bits_per_param * leaf.n_params
            q_stored += bd.stored_bits_per_param * leaf.n_params
            q_params += leaf.n_params
        elif hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            fp_params += leaf.size
    total = q_bits + 16.0 * fp_params
    n = max(q_params + fp_params, 1)
    return {
        "quantized_params": q_params,
        "fp16_params": fp_params,
        "total_bits_ideal": total,
        "total_bits_stored": q_stored + 16.0 * fp_params,
        "avg_bits_per_param": total / n,
    }


def dequantize_params(qparams):
    """Round-trip a quantized tree back to dense weights (the "noise lens"):
    scaling-law evals run the ORIGINAL fp model code on these weights.
    Each leaf comes back in the dtype the quantizer saw (QuantizedTensor
    records it as ``orig_dtype``), so a bf16 tree round-trips to bf16."""
    from repro.core.qtensor import dequantize_tensor

    def one(leaf):
        if isinstance(leaf, QuantizedTensor):
            w = dequantize_tensor(leaf, out_dtype=jnp.dtype(leaf.orig_dtype))
            # transposed-stored matrices go back to [In, Out]; lm_head/embed
            # are stored untransposed ([V, D]) and must stay that way
            if leaf.transposed:
                return jnp.swapaxes(w, -1, -2)
            return w
        return leaf

    return jax.tree.map(
        one, qparams, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )
