"""Fused k-bit dequantize + matmul Pallas TPU kernel.

The paper's premise: small-batch inference latency is proportional to the
bytes of weights streamed from HBM (§2.1).  This kernel therefore streams
PACKED k-bit codes (uint32 words) + 16-bit per-block scales into VMEM —
k/16 of the bf16 traffic — dequantizes tile-by-tile on the VPU, and feeds
the MXU.

Layout (K-major, matches models/quantize.py structured storage; see
docs/quantization.md#packing-layout-corepackingpy):
  x       [M, K]            activations (bf16/f32), columns PERMUTED per
                            K tile (see below; kernels/ops.qmatmul does it)
  packed  [K//cpw, N]       uint32, cpw = 32//bits codes per word along K
  scales  [K//B, N]         per-(K-block, column) absmax constants
  codebook[2**bits]         sorted data-type codebook (SMEM scalars)
  out     [M, N]            f32-accumulated, cast to x.dtype

The reduction dim runs down the sublanes and the output dim across the
lanes, so every block is (8, 128)-tiled at the serving shapes: a K tile
of ``bk`` codes is ``bk // cpw`` word rows and ``bk // B`` scale rows.
Word row w of a tile unpacks into ``cpw`` PLANES (shift/mask, no lane
shuffles): plane p holds the codes of K index ``w * cpw + p``.  The
dequantized planes are stacked along the sublanes (p-major), so the x
tile must list its columns in that order — ``permute_x`` does this once
per call on the (small) activation instead of interleaving the weight
tile in VMEM.

Grid (M/bm, N/bn, K/bk), K innermost with an f32 VMEM accumulator.

Dequantization on TPU (docs/quantization.md#kernels-kernels — no gather):
  * `int` data type: pure arithmetic (codes are affine in the value).
  * LUT types (float/dynamic/quantile): compare-accumulate select tree
    over the 2**bits codebook entries — vectorized VPU selects, no
    serializing gathers.  Fine for k <= 5 (<= 32 selects); for k in {6,8}
    prefer the int path or expect dequant-bound tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def permute_x(x: jnp.ndarray, bits: int, bk: int) -> jnp.ndarray:
    """Reorder x [M, K] columns within each bk tile into the kernel's
    plane-major order: column ``p * (bk // cpw) + w`` of a tile holds
    original column ``w * cpw + p``."""
    cpw = 32 // bits
    M, K = x.shape
    x4 = x.reshape(M, K // bk, bk // cpw, cpw)
    return jnp.swapaxes(x4, 2, 3).reshape(M, K)


def _dequant_codes(codes, cb_ref, bits: int, dtype_name: str):
    """codes uint32 [r, n] -> values f32 [r, n] (no gathers)."""
    if dtype_name == "int":
        half = float(2 ** (bits - 1) - 1)
        # via int32: Mosaic has no uint32 -> f32 cast (codes < 2**bits)
        v = codes.astype(jnp.int32).astype(jnp.float32) - half
        return jnp.clip(v, -half, half) / half
    vals = jnp.zeros(codes.shape, jnp.float32)
    for j in range(2**bits):
        vals = jnp.where(codes == j, cb_ref[j], vals)
    return vals


def _plane_scales(s, plane: int, *, cpw: int, block_size: int, rows: int):
    """Per-row scales [rows, n] of one plane from the tile's block scales
    s [rows * cpw // B, n].  When a word never straddles a block (B a
    multiple of cpw) every plane shares one sublane broadcast; otherwise
    (odd bit-widths, never fused on TPU) each row selects its block."""
    nsb, n = s.shape
    if block_size % cpw == 0:
        rep = block_size // cpw
        return jnp.broadcast_to(s[:, None, :], (nsb, rep, n)).reshape(rows, n)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)
    blk = (row * cpw + plane) // block_size
    out = jnp.zeros((rows, n), jnp.float32)
    for b in range(nsb):
        out = jnp.where(blk == b, s[b:b + 1, :], out)
    return out


def _qmatmul_kernel(x_ref, w_ref, s_ref, cb_ref, o_ref, acc_ref, *,
                    bits, block_size, dtype_name):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cpw = 32 // bits
    mask = jnp.uint32((1 << bits) - 1)
    words = w_ref[...]                                   # [bk//cpw, bn]
    s = s_ref[...].astype(jnp.float32)                   # [bk//B, bn]
    planes = []
    for p in range(cpw):
        codes = (words >> jnp.uint32(p * bits)) & mask
        vals = _dequant_codes(codes, cb_ref, bits, dtype_name)
        planes.append(vals * _plane_scales(
            s, p, cpw=cpw, block_size=block_size, rows=words.shape[0]))
    wt = jnp.concatenate(planes, axis=0)                 # [bk, bn]
    # round the weight tile to the activation dtype — the value the
    # dequant_einsum path multiplies (dequantize_tensor out_dtype=
    # x.dtype) — so matmul_mode stays a pure perf knob on TPU too (same
    # contract as ops.qmatmul_fused_jnp; see layers.linear).  bf16 x bf16
    # products are exact in the f32 accumulator.
    acc_ref[...] += jnp.dot(x_ref[...], wt.astype(x_ref.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def qmatmul_pallas(
    x: jnp.ndarray,
    packed: jnp.ndarray,
    scales: jnp.ndarray,
    codebook: jnp.ndarray,
    *,
    bits: int,
    block_size: int,
    dtype_name: str = "float",
    bm: int,
    bn: int,
    bk: int,
    interpret: bool,
) -> jnp.ndarray:
    """Tiled fused dequant-matmul over tile-aligned, x-permuted operands
    (kernels/ops.qmatmul pads and permutes).  x [M,K]; packed [K//cpw,N];
    scales [K//B,N]."""
    M, K = x.shape
    N = packed.shape[1]
    cpw = 32 // bits
    assert bk % cpw == 0 and bk % block_size == 0, (bk, cpw, block_size)
    assert K % bk == 0 and M % bm == 0 and N % bn == 0, (M, K, N, bm, bn, bk)
    assert packed.shape[0] * cpw == K, (packed.shape, K)

    kernel = functools.partial(
        _qmatmul_kernel, bits=bits, block_size=block_size,
        dtype_name=dtype_name,
    )
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // cpw, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // block_size, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # names the call in the chip trace's op text (kernel_metadata)
        metadata={"kernel": "_qmatmul_kernel"},
    )(x, packed, scales, codebook.astype(jnp.float32))
