"""Pure-jnp oracles for the Pallas kernels.

These define the semantics the kernels must match bit-for-bit (up to f32
accumulation order), and are also the execution path used on CPU and in
the dry-run (pallas_call cannot compile on the CPU backend outside
interpret mode — docs/quantization.md#kernels-kernels).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core import packing
from repro.core.codebooks import codebook_boundaries


class QMatmulOperand(NamedTuple):
    """Kernel-layout quantized weight for y = x @ W, W logical [K, N].

    Blocks run along the reduction dim K (per output column), matching the
    structured QuantizedTensor storage (models/quantize.py).  Storage is
    K-major — K down the rows, N across the columns, the TPU tiling of
    the Pallas kernel.  Each column packs word-aligned: for odd
    bit-widths its last word carries an inert zero tail, so
    ``packed.shape[0] == ceil(K / cpw)`` (== K // cpw exactly when cpw
    divides K).  ``k_dim`` is the stored
    (block-aligned) K; activations with fewer columns are zero-padded by
    the callers — the padded region dequantizes against real codes but
    multiplies zero activations, so it cannot contribute.
    """

    packed: jnp.ndarray    # uint32 [ceil(K / cpw), N]
    scales: jnp.ndarray    # bf16   [K // block, N]
    codebook: jnp.ndarray  # f32    [2**bits]
    bits: int
    block_size: int
    k_dim: int
    dtype_name: str = "float"


def dequantize_operand(op: QMatmulOperand, out_dtype=jnp.float32) -> jnp.ndarray:
    """Full dequantized W^T [N, K]."""
    codes = packing.unpack(op.packed.T, op.bits, op.k_dim)  # [N, K]
    vals = jnp.take(op.codebook, codes.astype(jnp.int32), axis=0)
    scales = jnp.repeat(
        op.scales.T.astype(jnp.float32), op.block_size, axis=1
    )[:, : op.k_dim]
    return (vals * scales).astype(out_dtype)


def qmatmul_ref(x: jnp.ndarray, op: QMatmulOperand) -> jnp.ndarray:
    """y = x @ W with on-the-fly dequantization; x [M, K<=k_dim] -> [M, N].

    A narrower x contracts against the leading x.shape[-1] stored columns
    (identical to zero-padding x to k_dim: for operands built by
    prepare_operand the tail columns are encodings of the K-alignment
    zero padding).  Anything wider than the storage is a caller bug."""
    K = x.shape[-1]
    assert K <= op.k_dim, (K, op.k_dim)
    wt = dequantize_operand(op, out_dtype=jnp.float32)[:, :K]
    return jnp.einsum(
        "mk,nk->mn", x.astype(jnp.float32), wt
    ).astype(x.dtype)


def quantize_blocks_ref(x_blocks: jnp.ndarray, codebook: jnp.ndarray):
    """Blockwise encode oracle: x [n_blocks, B] -> (codes int32, scales f32)."""
    absmax = jnp.max(jnp.abs(x_blocks), axis=1, keepdims=True)
    scales = jnp.maximum(absmax, 1e-12)
    normed = x_blocks / scales
    bounds = codebook_boundaries(codebook)
    codes = jnp.searchsorted(bounds, normed).astype(jnp.int32)
    return codes, scales[:, 0]
