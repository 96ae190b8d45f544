"""k-bit blockwise-quantized KV-cache layout: encode, dequant, Pallas kernel.

The serving argument is symmetric to the weights one (paper §2.1): at long
contexts the KV cache, not the weights, dominates the bytes streamed from
HBM per decoded token, so the same blockwise absmax + codebook machinery
(core/blockwise.py, core/codebooks.py, core/packing.py) is applied to every
cached token.  This module is the single definition of the packed layout;
models/attention.py builds cache pytrees from it and serving reuses those
unchanged (docs/quantization.md#the-k-bit-quantized-kv-cache).

Layout — each cached token row holds ``feat = n_kv_heads * head_dim``
features, chunked into blocks along that feature dim:

    packed  uint32 [..., S_c, feat // cpw]   cpw = 32 // bits codes per word
    scales  bf16   [..., S_c, feat // bs]    per-block absmax constants

``bs`` is ``kv_block_size`` clamped to the feature dim (tiny heads).
Words are PLANE-MAJOR: with ``W = feat // cpw`` words a row, code ``p``
of word ``w`` (bits ``p*bits`` up) is feature ``p * W + w``.  Shift/mask
plane ``p`` of a row's words is then features ``p*W .. p*W + W - 1`` in
order, so the kernel unpacks by whole-vreg shifts and a lane concatenate
of the planes, with no lane shuffles (an interleaved order, feature
``w * cpw + p``, has to scatter every plane across the lanes: that
relayout was ~85% of the kernel's vector ops).  ``pack_rows`` and
``unpack_rows`` are the only code that knows the order; weights keep
core/packing.py's interleaved order (kernels/qmatmul.py permutes x
instead).  Only
k in {4, 8} is supported: both pack exactly into 32-bit words, and they are
the paper's serving-relevant precisions.  Quantile codebooks are excluded —
the decode-step append-quantize is streaming and needs a static codebook.

A layout invariant the distributed path relies on: blocks and code words
run along the FEATURE dim only, never across tokens, so every byte of a
cached token (codes + scales) lives inside that token's row.  Slicing the
``S_c`` axis therefore yields a self-contained packed cache — this is what
lets models/sharding.py sequence-shard the packed leaves and call
``encode_rows``/``dequant_rows`` on shard-local slices unchanged.

Three read paths, one semantics:

  * ``dequant_rows_ref``    — pure jnp (gather) oracle; CPU / tests.
  * ``dequant_rows_pallas`` — Pallas TPU kernel: unpack (shift/mask
    planes) + a select tree over the 2**k codebook entries (no gathers,
    as kernels/qmatmul.py) + block-scale multiply, one row tile per grid
    step.  Streams k/16 of the bf16 cache bytes from HBM.
  * ``dequant_rows``        — dispatcher: the kernel on TPU, the oracle
    elsewhere (kernels/platform.py).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.core.codebooks import codebook_boundaries, make_codebook
from repro.kernels.platform import kernels_compiled, resolve_interpret


class KVQuantSpec(NamedTuple):
    """Hashable static description of a quantized KV cache (jit-safe)."""

    bits: int
    block_size: int
    dtype_name: str = "float"


def kv_spec(cfg) -> Optional[KVQuantSpec]:
    """The cache-quantization spec an ArchConfig asks for (None = bf16)."""
    bits = getattr(cfg, "kv_bits", 16)
    if bits is None or bits >= 16:
        return None
    if bits not in (4, 8):
        raise ValueError(f"kv_bits must be 4, 8 or 16, got {bits}")
    if cfg.kv_dtype == "quantile":
        raise ValueError("quantile codebooks cannot serve a streaming KV cache")
    return KVQuantSpec(
        bits=bits,
        block_size=cfg.kv_block_size,
        dtype_name=cfg.kv_dtype,
    )


def kv_layout(spec: KVQuantSpec, feat: int) -> tuple[int, int, int]:
    """(block_size, n_blocks, n_words) for a `feat`-wide token row.

    The block size is clamped to the feature dim and, if it does not
    divide, reduced to the gcd so blocks always tile the row exactly.
    """
    bs = min(spec.block_size, feat)
    if feat % bs:
        bs = math.gcd(bs, feat)
    cpw = packing.codes_per_word(spec.bits)
    if feat % cpw:
        raise ValueError(
            f"feature dim {feat} must divide into {cpw}-code words "
            f"(kv_bits={spec.bits})"
        )
    return bs, feat // bs, feat // cpw


def kv_codebook(spec: KVQuantSpec) -> jnp.ndarray:
    """Sorted static codebook for the cache's data type (f32 [2**bits])."""
    return jnp.asarray(make_codebook(spec.dtype_name, spec.bits))


# --------------------------------------------------------------------------
# encode (the append-quantize path) — pure jnp, runs inside the jitted
# decode/prefill steps, so the bf16 K/V of a new token never reaches HBM
# --------------------------------------------------------------------------

def encode_rows(x: jnp.ndarray, spec: KVQuantSpec):
    """Blockwise-quantize token rows x [..., feat] against the spec's
    codebook.  Returns (packed uint32 [..., n_words], scales bf16
    [..., n_blocks]).  Same math as core/blockwise.encode, restricted to
    exactly-tiling blocks so it vectorizes over any leading dims."""
    feat = x.shape[-1]
    bs, n_blocks, _ = kv_layout(spec, feat)
    xb = x.astype(jnp.float32).reshape(x.shape[:-1] + (n_blocks, bs))
    absmax = jnp.max(jnp.abs(xb), axis=-1)
    scales = jnp.maximum(absmax, 1e-12)
    normed = xb / scales[..., None]
    bounds = codebook_boundaries(kv_codebook(spec))
    # gather-free, as core/blockwise.encode
    codes = jnp.searchsorted(bounds, normed,
                             method="compare_all").astype(jnp.uint32)
    packed = pack_rows(codes.reshape(x.shape[:-1] + (feat,)), spec.bits)
    return packed, scales.astype(jnp.bfloat16)


def pack_rows(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack codes [..., feat] plane-major: code ``p`` of word ``w`` (bits
    ``p*bits`` up) is feature ``p * W + w``, ``W = feat // cpw``."""
    cpw = packing.codes_per_word(bits)
    c = codes.astype(jnp.uint32).reshape(codes.shape[:-1] + (cpw, -1))
    shifts = (jnp.arange(cpw, dtype=jnp.uint32) * bits)[:, None]
    # codes occupy disjoint bit ranges, so a sum equals the bitwise OR
    return jnp.sum(c << shifts, axis=-2, dtype=jnp.uint32)


def unpack_rows(words: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Inverse of ``pack_rows``: words [..., W] -> codes uint32 [..., W*cpw]."""
    cpw = packing.codes_per_word(bits)
    shifts = (jnp.arange(cpw, dtype=jnp.uint32) * bits)[:, None]
    c = (words[..., None, :] >> shifts) & jnp.uint32((1 << bits) - 1)
    return c.reshape(words.shape[:-1] + (cpw * words.shape[-1],))


# --------------------------------------------------------------------------
# dequant read paths
# --------------------------------------------------------------------------

def dequant_rows_ref(packed, scales, spec: KVQuantSpec, feat: int,
                     out_dtype=jnp.bfloat16):
    """Pure-jnp oracle: packed [..., W] + scales [..., NB] -> [..., feat]."""
    bs, n_blocks, _ = kv_layout(spec, feat)
    codes = unpack_rows(packed, spec.bits)
    vals = jnp.take(kv_codebook(spec), codes.astype(jnp.int32), axis=0)
    vals = vals.reshape(packed.shape[:-1] + (n_blocks, bs))
    vals = vals * scales[..., None].astype(jnp.float32)
    return vals.reshape(packed.shape[:-1] + (feat,)).astype(out_dtype)


#: rows of one unrolled step of the kernel.  A step bounds the select
#: tree's live values, which over a whole tile outgrow the scoped VMEM
#: (kv8 at any tile, kv4 past 512 rows at feat 512); on a v5e 64 rows read
#: fastest at feat 512 and within 4% of the fastest at feat 1024
_STEP_ROWS = 64
#: rows x features x codebook entries of one grid step: the unrolled
#: select trees of a tile, so its code and its VMEM blocks (~6 MiB at kv4)
#: stay bounded at any width; 2048 rows at feat 512, 1024 at feat 1024
_TILE_WORK = 1 << 24


def kv_tile_rows(spec: KVQuantSpec, feat: int) -> int:
    """Rows of one grid step of ``dequant_rows_pallas``: a power of two,
    at least 16, with rows x feat x 2**bits at most ``_TILE_WORK``.  Few,
    long grid steps amortize the ~0.35 us each grid step costs."""
    rows = _TILE_WORK // (feat * 2**spec.bits)
    return max(16, 1 << (rows.bit_length() - 1))


def _lookup(codes, cb_ref, bits: int):
    """codebook[codes] for codes uint32 [r, n] -> f32 [r, n], with no
    gather: a select tree on the code's bits, bit 0 at the leaves.  That
    is 2**bits - 1 selects and ``bits`` bit tests a value, against 2**bits
    compares and selects for a compare-select chain."""
    bit = [(codes & jnp.uint32(1 << k)) != 0 for k in range(bits)]

    def subtree(level, base):       # the entries base .. base + 2**level - 1
        if level == 0:
            return cb_ref[0, base]
        half = 2 ** (level - 1)
        return jnp.where(bit[level - 1], subtree(level - 1, base + half),
                         subtree(level - 1, base))

    return subtree(bits, 0)


def _expand_scales(s, bs: int):
    """Block scales s bf16 [r, NB] -> per-feature f32 [r, NB * bs] on the
    MXU, which this kernel leaves idle: s @ E with E[b, f] = (f // bs ==
    b) has one nonzero product per output, so it is exact."""
    n_blocks = s.shape[1]
    f = jax.lax.broadcasted_iota(jnp.int32, (n_blocks, n_blocks * bs), 1)
    b = jax.lax.broadcasted_iota(jnp.int32, (n_blocks, n_blocks * bs), 0)
    return jnp.dot(s, (f // bs == b).astype(s.dtype),
                   preferred_element_type=jnp.float32)


def _dequant_kernel(p_ref, s_ref, cb_ref, o_ref, *, bits, bs, dtype_name,
                    step_rows):
    """One row tile in unrolled steps of ``step_rows`` rows: unpack ->
    dequant -> scale multiply.

    Nothing crosses lanes: shift/mask plane ``p`` of the words [r, W]
    holds features ``p*W .. p*W + W - 1`` in order (plane-major words),
    so the planes concatenate along the lanes into the [r, feat] codes."""
    cpw = 32 // bits
    mask = jnp.uint32((1 << bits) - 1)
    for r0 in range(0, p_ref.shape[0], step_rows):
        rows = pl.ds(r0, step_rows)
        words = p_ref[rows, :]
        codes = jnp.concatenate(
            [(words >> jnp.uint32(p * bits)) & mask for p in range(cpw)],
            axis=1)
        if dtype_name == "int":
            half = float(2 ** (bits - 1) - 1)
            # via int32: Mosaic has no uint32 -> f32 cast (codes < 2**bits)
            vals = jnp.clip(
                codes.astype(jnp.int32).astype(jnp.float32) - half,
                -half, half) / half
        else:
            vals = _lookup(codes, cb_ref, bits)
        o_ref[rows, :] = (vals * _expand_scales(s_ref[rows, :], bs)
                          ).astype(o_ref.dtype)


def dequant_rows_pallas(packed, scales, spec: KVQuantSpec, feat: int, *,
                        tile_rows: int | None = None,
                        interpret: bool | None = None,
                        out_dtype=jnp.bfloat16):
    """Pallas dequant of flattened rows: packed [R, W], scales bf16 [R, NB]
    -> [R, feat].  ``tile_rows`` rows a grid step (None: ``kv_tile_rows``);
    rows are padded up to a tile multiple and sliced back.  ``interpret``
    None: compiled on TPU, interpreted elsewhere."""
    bs, n_blocks, n_words = kv_layout(spec, feat)
    R = packed.shape[0]
    if tile_rows is None:
        tile_rows = kv_tile_rows(spec, feat)
    tr = min(tile_rows, -(-max(R, 1) // 16) * 16)
    n_tiles = -(-R // tr)
    pad = n_tiles * tr - R
    if pad:
        packed = jnp.concatenate(
            [packed, jnp.zeros((pad, n_words), packed.dtype)])
        scales = jnp.concatenate(
            [scales, jnp.zeros((pad, n_blocks), scales.dtype)])
    cb2 = kv_codebook(spec).reshape(1, -1)
    kernel = functools.partial(
        _dequant_kernel, bits=spec.bits, bs=bs, dtype_name=spec.dtype_name,
        step_rows=math.gcd(_STEP_ROWS, tr),
    )
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tr, n_words), lambda i: (i, 0)),
            pl.BlockSpec((tr, n_blocks), lambda i: (i, 0)),
            pl.BlockSpec((1, 2**spec.bits), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tr, feat), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tr, feat), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=resolve_interpret(interpret),
        # names the call in the chip trace's op text (kernel_metadata)
        metadata={"kernel": "_dequant_kernel"},
    )(packed, scales, cb2)
    return out[:R]


def dequant_rows(packed, scales, spec: KVQuantSpec, feat: int, *,
                 out_dtype=jnp.bfloat16):
    """Dequantize [..., W]/[..., NB] leaves to [..., feat] values: the
    compiled Pallas kernel on TPU, the jnp oracle elsewhere."""
    if not kernels_compiled():
        return dequant_rows_ref(packed, scales, spec, feat, out_dtype=out_dtype)
    lead = packed.shape[:-1]
    flat = dequant_rows_pallas(
        packed.reshape((-1, packed.shape[-1])),
        scales.reshape((-1, scales.shape[-1])),
        spec, feat, out_dtype=out_dtype,
    )
    return flat.reshape(lead + (feat,))


def gather_pages(leaf: jnp.ndarray, page_map: jnp.ndarray) -> jnp.ndarray:
    """Gather a paged cache leaf through a page-index vector.

    ``leaf`` is page-major storage [n_pages, ps, ...] (any trailing dims:
    packed code words, scales, dense heads, or a pos array with none);
    ``page_map`` [B, P] holds each sequence's page ids in table order.
    Returns the CONTIGUOUS per-sequence view [B, P * ps, ...] in which
    absolute position p of sequence b lives at index p — i.e. exactly the
    slot-pool row layout, so every downstream consumer (dequant, the
    masked flash-decoding partials) runs unchanged on the gathered view.

    The layout invariant that makes this safe for packed caches: blocks
    and code words run along the FEATURE dim only (module docstring), so
    a page boundary on the token axis never splits quantization state —
    gather-then-dequant equals dequant-then-gather elementwise."""
    B, P = page_map.shape
    ps = leaf.shape[1]
    g = jnp.take(leaf, page_map.reshape(-1), axis=0)      # [B*P, ps, ...]
    return g.reshape((B, P * ps) + leaf.shape[2:])


def dequant_pages(packed, scales, page_map, spec: KVQuantSpec, feat: int, *,
                  out_dtype=jnp.bfloat16):
    """Dequantize a paged packed cache through a page-index vector:
    packed [n_pages, ps, W] + scales [n_pages, ps, NB] gathered via
    ``page_map`` [B, P] -> dense [B, P*ps, feat].  Bitwise equal to
    gathering a pre-dequantized cache because dequant is row-local."""
    return dequant_rows(
        gather_pages(packed, page_map), gather_pages(scales, page_map),
        spec, feat, out_dtype=out_dtype,
    )


def kv_stored_bytes_per_token(spec: Optional[KVQuantSpec], feat: int,
                              cache_dtype_bytes: int = 2) -> float:
    """HBM bytes one cached K *or* V token row occupies under the spec
    (scales included); the bf16 baseline when spec is None."""
    if spec is None:
        return float(feat * cache_dtype_bytes)
    bs, n_blocks, n_words = kv_layout(spec, feat)
    return float(n_words * 4 + n_blocks * 2)
