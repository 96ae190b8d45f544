"""Sharding policy: maps model params / activations / caches onto the
production mesh ("pod", "data", "model").

Training / prefill
  * batch -> ("pod","data")  (DP across pods, DP+FSDP inside a pod)
  * weights: column-parallel over "model" (TP) + FSDP over "data"
    (GSPMD all-gathers per scan step == ZeRO-3); replicated across pods
  * attention: heads over "model".  Archs whose head count is not
    divisible by the TP degree (deepseek 56H, qwen2 28H) get ZERO-PADDED
    q-heads up to the next multiple of lcm(tp, kv) — 14% extra attention
    FLOPs, visible in the roofline's MODEL_FLOPS/HLO ratio, in exchange
    for exact-causal chunked attention and uniform head-TP (a
    context-parallel split would avoid the padding but costs an extra
    collective per layer).
  * MoE: experts over "model" (EP)

Decode
  * KV cache SEQUENCE-sharded over "model" (and over "data"/"pod" too when
    the batch is too small to fill them, e.g. long_500k batch=1); attention
    uses flash-decoding partials combined with psum inside shard_map — no
    kv-head divisibility constraints, cache memory scales with the mesh.
    k-bit caches (cfg.kv_bits in {4, 8}) shard the SAME way: the packed
    codes + per-block scales of a cached token are entirely feature-dim
    state, so splitting the slot axis never splits a code word — each
    shard append-quantizes the tokens it owns and dequantizes only its
    local slice before the masked partial math (kernels/kv_dequant.py).
  * quantized weights: packed/scale arrays sharded over their output-row
    dim on "model" == column-parallel (contiguous rows per chip); inside
    ``Sharder.tp_scope()`` the fused dequant-GEMM runs per shard on those
    local rows (kernels/ops.tp_dispatch_scope).
  * per-layer cache lengths that do not divide the seq-shard grid (e.g.
    tiny ring-window caches) fall back to replicated local attention —
    decided at decode_attn_fn SETUP time with a SeqShardFallbackWarning,
    never silently inside the traced body.

``check_decode_capability`` is the one gate for the quantized×sharded
combination (it replaced the early-PR duplicate rejections in
serving/engine.py and the in-body NotImplementedError here): it raises
only for genuinely unsupported configs and names the actual caller.

Without a mesh every method is a no-op, so model code is identical on CPU.
"""

from __future__ import annotations

import math
import warnings
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.qtensor import QuantizedTensor
from repro.kernels import kv_dequant
from repro.kernels.kv_dequant import kv_spec
from repro.models import attention as attn_mod

_COL_MODULES = {"wq", "wk", "wv", "w_gate", "w_up", "in_proj", "frame_proj", "router"}
_ROW_MODULES = {"wo", "w_down", "out_proj"}

#: the packed-cache leaves a k-bit KV cache carries instead of dense k/v
#: (kernels/kv_dequant.py layout); all are [.., B, S_c, feat-dim-state],
#: so they sequence-shard exactly like the dense leaves
_KV_CACHE_KEYS = ("k", "v", "k_packed", "k_scales", "v_packed", "v_scales")


class SeqShardFallbackWarning(UserWarning):
    """A per-layer cache length does not divide the sequence-shard grid:
    that layer decodes via replicated local attention (a full-cache
    gather per step) instead of sharded flash-decoding."""


def check_decode_capability(cfg, sharder, *,
                            caller: str = "the serving entry point") -> None:
    """THE capability gate for the quantized×sharded decode combination
    (single home of what used to be engine.check_sharded_kv_quant plus a
    ValueError/NotImplementedError pair in this module).

    Sequence-sharded decode now operates directly on the packed k-bit
    layout, so kv_bits×mesh is SERVED, not rejected.  Only genuinely
    unsupported configs raise — a feature row that cannot pack whole
    codes-per-word words (kv_layout), or a quantile KV codebook (kv_spec;
    streaming append-quantize needs a static codebook).  Cache lengths
    that do not divide the shard grid are NOT errors: decode_attn_fn
    falls back to replicated local attention per layer and says so with
    a SeqShardFallbackWarning at setup time.  The message names `caller`
    so Engine and Server users each see their own entry point."""
    try:
        kvq = kv_spec(cfg)  # raises for quantile codebooks / bad kv_bits
    except ValueError as e:
        raise ValueError(f"{e} (rejected at setup for {caller})") from e
    if kvq is None or sharder is None:
        return
    if getattr(sharder, "mesh", None) is None or sharder.replicate:
        return
    feat = cfg.n_kv_heads * cfg.head_dim
    try:
        kv_dequant.kv_layout(kvq, feat)
    except ValueError as e:
        raise ValueError(
            f"kv_bits={cfg.kv_bits} cannot serve {caller} on a mesh: {e}"
        ) from e


def _maybe(axis, dim_size, axis_size):
    """Use `axis` only if it divides the dim."""
    if axis is None:
        return None
    return axis if dim_size % axis_size == 0 else None


class Sharder:
    def __init__(self, mesh: Mesh | None, cfg, *, fsdp: bool = True,
                 replicate_params_below: int = 400_000_000):
        self.mesh = mesh
        self.cfg = cfg
        self.fsdp = fsdp
        if mesh is None:
            self.dp_axes = ()
            self.tp = None
            self.tp_size = 1
            self.dp_size = 1
            self.replicate = True
            return
        names = mesh.axis_names
        self.tp = "model"
        self.dp_axes = tuple(n for n in names if n != "model")
        self.tp_size = mesh.shape["model"]
        self.dp_size = math.prod(mesh.shape[n] for n in self.dp_axes)
        # small models: replicating weights beats TP overhead
        n_params = cfg.param_count()
        self.replicate = n_params * 2 < replicate_params_below
        self.fsdp_axis = "data" if (fsdp and not self.replicate) else None

    # -- helpers ---------------------------------------------------------
    def _ns(self, *spec):
        return NamedSharding(self.mesh, P(*spec))

    @property
    def dp(self):
        return self.dp_axes if self.dp_axes else None

    def head_pad(self) -> int:
        """q-head count padded so heads are TP- and GQA-divisible."""
        cfg = self.cfg
        if not cfg.n_heads:
            return 0
        if self.mesh is None or self.replicate:
            return cfg.n_heads
        K = max(cfg.n_kv_heads, 1)
        h = cfg.n_heads
        while h % K or h % self.tp_size:
            h += 1
        return h

    # -- activation constraints -------------------------------------------
    def constrain(self, x, kind: str):
        if self.mesh is None:
            return x
        tp = None if self.replicate else self.tp
        dp = self.dp
        spec = {
            "residual": (dp, None, None),
            "heads": (dp, None, tp, None),
            "kv_heads": (dp, None, None, None),
            "ffn_hidden": (dp, None, tp),
            "logits": (dp, None, tp),
            "expert_buffer": (tp, None, None),
            "expert_hidden": (tp, None, None),
            "moe_groups": (dp, None, None),       # [G,Tg,D] group-local tokens
            "expert_buffer4": (dp, tp, None, None),  # [G,E,C,D]
            "expert_hidden4": (dp, tp, None, None),
            "ssm_heads": (dp, None, tp, None),   # [B,S,H,P] SSD head shard
            "ssm_dt": (dp, None, tp),            # [B,S,H]
            "ssm_bc": (dp, None, None, None),    # [B,S,G,N] small, replicated
            "ssd_intra": (dp, None, None, None, tp),  # [B,n,Q,Q,H]
            "ssd_bn": (dp, None, None, tp, None),     # [B,n,Q,H,N]
        }.get(kind)
        if spec is None or len(spec) != x.ndim:
            return x
        # drop axes that do not divide
        fixed = tuple(
            _maybe(a, x.shape[i], self._axis_size(a)) for i, a in enumerate(spec)
        )
        return jax.lax.with_sharding_constraint(x, self._ns(*fixed))

    def _axis_size(self, a):
        if a is None:
            return 1
        if isinstance(a, tuple):
            return math.prod(self.mesh.shape[n] for n in a)
        return self.mesh.shape[a]

    # -- parameter specs ---------------------------------------------------
    def param_spec_tree(self, params):
        """NamedSharding tree for a (possibly quantized) params tree."""

        def spec_for(path, leaf):
            keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
            return self._leaf_spec(keys, leaf)

        return jax.tree_util.tree_map_with_path(
            spec_for, params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        )

    def _leaf_spec(self, keys, leaf):
        if isinstance(leaf, QuantizedTensor):
            return self._qt_spec(keys, leaf)
        if self.mesh is None:
            return None
        if self.replicate or leaf.ndim == 0:
            return self._ns()
        tp, fs = self.tp, self.fsdp_axis
        name = next((k for k in reversed(keys) if isinstance(k, str)), "")
        shape = leaf.shape

        if name in ("embed", "lm_head"):
            return self._ns(_maybe(tp, shape[0], self.tp_size),
                            _maybe(fs, shape[1], self._axis_size(fs)))
        if "ffn" in keys and name in ("w_gate", "w_up", "w_down") and leaf.ndim == 4:
            # MoE experts [n_p, E, In, Out] -> EP over model + FSDP on In
            return self._ns(None, _maybe(tp, shape[1], self.tp_size),
                            _maybe(fs, shape[2], self._axis_size(fs)), None)
        if name == "router":
            return self._ns()
        if name == "w" and leaf.ndim >= 2:
            owner = next(
                (k for k in reversed(keys[:-1]) if isinstance(k, str)), ""
            )
            lead = (None,) * (leaf.ndim - 2)
            i, o = shape[-2], shape[-1]
            if owner in _ROW_MODULES:
                return self._ns(*lead, _maybe(tp, i, self.tp_size),
                                _maybe(fs, o, self._axis_size(fs)))
            return self._ns(*lead, _maybe(fs, i, self._axis_size(fs)),
                            _maybe(tp, o, self.tp_size))
        if name == "b" and leaf.ndim >= 1:
            lead = (None,) * (leaf.ndim - 1)
            return self._ns(*lead, _maybe(tp, leaf.shape[-1], self.tp_size))
        if name == "conv_w" and leaf.ndim >= 2:
            lead = (None,) * (leaf.ndim - 2)
            return self._ns(*lead, None,
                            _maybe(tp, leaf.shape[-1], self.tp_size))
        return self._ns()

    def _qt_spec(self, keys, qt: QuantizedTensor):
        """Quantized leaves: output-row column-parallelism over `model`.
        Structured (K-major) storage shards its last dim, which indexes
        the output rows; flat storage shards the flat dim (contiguous
        rows) when it divides."""
        if self.mesh is None:
            return jax.tree.map(lambda _: None, qt)
        import dataclasses as _dc

        tp = None if self.replicate else self.tp
        nb = len(qt.batch_shape)
        # MoE expert stacks have TWO batch dims [n_p, E, ...]; dense stacked
        # weights have one [n_p, ...] and must NOT take the expert branch
        is_expert = nb == 2

        def leaf_spec(a, shardable=True, structured_leaf=False):
            if a is None:
                return None
            lead = [None] * a.ndim
            if is_expert:
                # [n_p, E, ...] -> shard experts over model (EP)
                if qt.batch_shape[-1] % self.tp_size == 0:
                    lead[nb - 1] = tp
                return self._ns(*lead)
            if shardable and tp is not None and a.ndim >= 1:
                out_rows = qt.quant_shape[0]
                divides = a.shape[-1] % self.tp_size == 0
                if out_rows % self.tp_size == 0 and (structured_leaf
                                                     or divides):
                    lead[-1] = tp
            return self._ns(*lead)

        st = qt.structured
        return _dc.replace(
            qt,
            packed=leaf_spec(qt.packed, structured_leaf=st),
            scales=leaf_spec(qt.scales, structured_leaf=st),
            means=leaf_spec(qt.means, structured_leaf=st),
            codebook=leaf_spec(qt.codebook, shardable=False),
            outlier_vals=leaf_spec(qt.outlier_vals, shardable=False),
            outlier_idx=leaf_spec(qt.outlier_idx, shardable=False),
        )

    # -- caches ------------------------------------------------------------
    def decode_plan(self, batch: int):
        """(batch_axes, seq_axes) for the KV cache at this batch size."""
        if self.mesh is None:
            return None, None
        usable = []
        rem = batch
        for a in self.dp_axes:
            if rem % self.mesh.shape[a] == 0:
                usable.append(a)
                rem //= self.mesh.shape[a]
        batch_axes = tuple(usable) or None
        # seq gets "model" plus any dp axis not absorbed by the batch
        seq_axes = tuple(a for a in self.mesh.axis_names if a not in usable)
        return batch_axes, seq_axes

    def cache_spec_tree(self, caches, batch: int, *, paged: bool = False):
        """Placement specs for a decode-cache tree.  ``paged=True`` places
        a PAGE-MAJOR pool (serving/pages.py: batch axis = physical pages,
        token axis = one page): pages spread over the batch axes like
        slots do, but the tiny intra-page token axis stays unsharded —
        sequence parallelism is over pages, not positions."""
        if self.mesh is None:
            return jax.tree.map(lambda _: None, caches)
        b_ax, s_ax = self.decode_plan(batch)
        tp = None if self.replicate else self.tp

        def spec(path, leaf):
            keys = [getattr(k, "key", None) for k in path]
            if any(k in _KV_CACHE_KEYS for k in keys):
                # dense [n_p, B, S, K, Dh] or packed/scales [n_p, B, S, X]:
                # the slot axis is dim 2 either way (packed layouts keep
                # all quantization state inside the token row)
                if paged:
                    return self._ns(None, b_ax, None,
                                    *((None,) * (leaf.ndim - 3)))
                s = _maybe(s_ax, leaf.shape[2], self._axis_size(s_ax))
                lead = (None,) * (leaf.ndim - 3)
                return self._ns(None, b_ax, s, *lead)
            if "pos" in keys:
                if leaf.ndim == 3:  # per-slot [n_p, B, S_c]
                    if paged:
                        return self._ns(None, b_ax, None)
                    s = _maybe(s_ax, leaf.shape[2], self._axis_size(s_ax))
                    return self._ns(None, b_ax, s)
                s = _maybe(s_ax, leaf.shape[1], self._axis_size(s_ax))
                return self._ns(None, s)
            if "state" in keys:  # [n_p, B, H, P, N]
                h = _maybe(tp, leaf.shape[2], self.tp_size)
                return self._ns(None, b_ax, h, None, None)
            if "conv" in keys:  # [n_p, B, cw-1, conv_dim]
                c = _maybe(tp, leaf.shape[3], self.tp_size)
                return self._ns(None, b_ax, None, c)
            return self._ns()

        return jax.tree_util.tree_map_with_path(spec, caches)

    # -- sharded decode attention ------------------------------------------
    def pad_cache_len(self, cache_len: int) -> int:
        """Round a cache budget UP so full-attention cache lengths divide
        any seq-shard grid this mesh can produce (depending on the batch
        split every axis may land in the seq set, so pad to the full mesh
        size).  Engine/Server apply this at setup — extra decode room,
        never less — leaving the fallback warning to genuinely
        non-dividing layers (ring windows shorter than the grid)."""
        if self.mesh is None or self.replicate:
            return cache_len
        n = self.mesh.size
        return -(-cache_len // n) * n

    def seq_shard_plan(self, batch: int, cache_len: int) -> dict[int, bool]:
        """Setup-time audit of the sequence-shard decision: maps every
        per-layer EFFECTIVE cache length this config will decode with
        (ring-window layers cap theirs at the window) to whether it
        divides the seq-shard grid.  False entries decode via replicated
        local attention — the hoisted version of what used to be a silent
        per-call branch inside the traced body."""
        if self.mesh is None or self.replicate:
            return {}
        from repro.models.blocks import _mixer_window

        _, s_ax = self.decode_plan(batch)
        s_size = self._axis_size(s_ax)
        plan: dict[int, bool] = {}
        for mixer, _ in self.cfg.layer_schedule():
            if not mixer.startswith("attn"):
                continue
            w = _mixer_window(mixer, self.cfg)
            eff = min(cache_len, w) if w else cache_len
            plan[eff] = eff % s_size == 0
        return plan

    def _warn_fallback(self, lengths, s_size) -> None:
        warnings.warn(
            f"cache length(s) {sorted(lengths)} do not divide the "
            f"{s_size}-way sequence-shard grid: those layers fall back "
            "to replicated local decode attention (a full-cache gather "
            "per step). Pad the cache budget / window to a multiple of "
            "the seq shards to keep them sharded.",
            SeqShardFallbackWarning,
            stacklevel=3,
        )

    def decode_attn_fn(self, batch: int, cache_len: int | None = None):
        """A decode_attn callable (blocks.apply_layer_decode signature):
        shard_map flash-decoding over the sequence-sharded cache — dense
        bf16 or packed k-bit (the kvq kwarg the blocks layer threads in),
        shared scalar positions (static Engine) or per-slot position
        vectors (continuous-batching Server).

        Cache lengths that do not divide the seq shards (e.g. tiny ring
        caches) fall back to replicated local attention; passing
        `cache_len` makes that decision HERE, at setup time, with a
        SeqShardFallbackWarning per offending length — layers whose
        length shows up later (no cache_len, or an unexpected shape)
        still warn at trace time, never silently."""
        if self.mesh is None or self.replicate:
            from repro.models.blocks import local_decode_attn

            return local_decode_attn

        b_ax, s_ax = self.decode_plan(batch)
        s_size = self._axis_size(s_ax)
        known: dict[int, bool] = {}
        if cache_len is not None:
            known = self.seq_shard_plan(batch, cache_len)
            bad = [L for L, ok in known.items() if not ok]
            if bad:
                self._warn_fallback(bad, s_size)

        def sharded_ok(S_total: int) -> bool:
            if S_total not in known:
                known[S_total] = S_total % s_size == 0
                if not known[S_total]:
                    self._warn_fallback([S_total], s_size)
            return known[S_total]

        def fn(q, k_new, v_new, cache, pos, *, cap, window, kvq=None):
            quant = kvq is not None and "k_packed" in cache
            ref = cache["k_packed"] if quant else cache["k"]
            if not sharded_ok(ref.shape[1]):
                from repro.models.blocks import local_decode_attn

                kw = {"kvq": kvq} if kvq is not None else {}
                return local_decode_attn(
                    q, k_new, v_new, cache, pos, cap=cap, window=window, **kw
                )
            return self._sharded_decode(
                q, k_new, v_new, cache, pos, cap=cap, window=window,
                kvq=kvq if quant else None, b_ax=b_ax, s_ax=s_ax,
            )

        return fn

    def _sharded_decode(self, q, k_new, v_new, cache, pos, *, cap, window,
                        kvq, b_ax, s_ax):
        """shard_map body shared by all four (dense|packed)×(scalar|vector
        pos) cache flavors: write the new token on the shard that owns its
        slot, dequantize the LOCAL slice when packed, take flash-decoding
        partials over it, psum-combine across the seq axes."""
        mesh = self.mesh
        keys = [k for k in _KV_CACHE_KEYS if k in cache]
        leaves = [cache[k] for k in keys]
        S_total = leaves[0].shape[1]
        per_slot = cache["pos"].ndim == 2
        pos_v = jnp.asarray(pos, jnp.int32)
        B, H, Dh = q.shape
        K = k_new.shape[-2]
        feat = K * Dh

        def local(q, k_new, v_new, pos_arr, pos, *lvs):
            Bl = q.shape[0]
            S_loc = lvs[0].shape[1]
            offset = _shard_offset(s_ax, mesh) * S_loc
            # the write semantics (idle rows, rings, append-quantize)
            # live next to their single-device twin in attention.py
            d, pos_arr = attn_mod.write_cache_local_window(
                dict(zip(keys, lvs)), pos_arr, k_new, v_new, pos,
                S_total=S_total, offset=offset, window=window, kvq=kvq,
            )
            if kvq is not None:
                k_loc = kv_dequant.dequant_rows(
                    d["k_packed"], d["k_scales"], kvq, feat
                ).reshape(Bl, S_loc, K, Dh)
                v_loc = kv_dequant.dequant_rows(
                    d["v_packed"], d["v_scales"], kvq, feat
                ).reshape(Bl, S_loc, K, Dh)
            else:
                k_loc, v_loc = d["k"], d["v"]
            m, l, pv = attn_mod.decode_attention_partial(
                q, k_loc, v_loc, pos_arr, pos, cap=cap, window=window
            )
            o = attn_mod.combine_partials(m, l, pv, s_ax)
            return (o.astype(q.dtype), pos_arr) + tuple(d[k] for k in keys)

        pos_arr_spec = P(b_ax, s_ax) if per_slot else P(s_ax)
        pos_spec = P(b_ax) if pos_v.ndim else P()
        leaf_specs = tuple(P(b_ax, s_ax) for _ in keys)
        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(b_ax), P(b_ax), P(b_ax), pos_arr_spec, pos_spec)
            + leaf_specs,
            out_specs=(P(b_ax), pos_arr_spec) + leaf_specs, check_vma=False,
        )(q, k_new, v_new, cache["pos"], pos_v, *leaves)
        new_cache = dict(zip(keys, out[2:]))
        new_cache["pos"] = out[1]
        return out[0].reshape(B, H, Dh), new_cache

    # -- tensor-parallel fused-GEMM scope ----------------------------------
    def tp_scope(self):
        """Context manager activating column-parallel fused dequant-GEMM
        dispatch (kernels/ops.tp_dispatch_scope) for everything traced
        inside — the serving jits enter it so eligible QuantizedTensor
        matmuls run per TP shard instead of falling back to whatever
        GSPMD makes of a pallas_call.  A no-op without a mesh or with
        replicated params."""
        import contextlib

        if self.mesh is None or self.replicate:
            return contextlib.nullcontext()
        from repro.kernels import ops

        return ops.tp_dispatch_scope(self.mesh, self.tp,
                                     dp_axes=self.dp_axes)


def _shard_offset(s_ax, mesh):
    """Linear index of this shard along the (possibly tuple) seq axes."""
    if isinstance(s_ax, str):
        return jax.lax.axis_index(s_ax)
    idx = 0
    for a in s_ax:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def no_sharder(cfg):
    return Sharder(None, cfg)
