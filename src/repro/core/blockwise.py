"""Block-wise k-bit quantization (paper Eq. 1, §2.3) — pure-JAX reference.

The tensor is viewed as a flat sequence, chunked into blocks of size B;
each block gets its own 16-bit absmax normalization constant
(+ optionally a 16-bit mean for distribution centering, App. B).
Encoding finds the nearest codebook value; because codebooks are sorted
we use searchsorted over the midpoint boundaries.  Its ``compare_all``
method counts the boundaries below each value in one fused elementwise
pass, where the default binary search is a loop of per-element gathers
from the boundary table.

This module is the semantic oracle for kernels/quantize.py and
kernels/qmatmul ref.py, and the implementation used on CPU.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.codebooks import codebook_boundaries


class BlockQuantized(NamedTuple):
    """Unpacked blockwise-quantized tensor (codes not yet bit-packed)."""

    codes: jnp.ndarray   # uint8 [n_blocks, block_size]
    scales: jnp.ndarray  # scale dtype (bf16) [n_blocks]
    means: jnp.ndarray | None  # bf16 [n_blocks] if centering else None


def _pad_to_blocks(flat: jnp.ndarray, block_size: int) -> jnp.ndarray:
    n = flat.shape[0]
    n_blocks = -(-n // block_size)
    pad = n_blocks * block_size - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(n_blocks, block_size)


def encode(
    x: jnp.ndarray,
    codebook: jnp.ndarray,
    block_size: int,
    *,
    centering: bool = False,
    scale_dtype=jnp.bfloat16,
) -> BlockQuantized:
    """Quantize tensor `x` blockwise against a sorted codebook."""
    blocks = _pad_to_blocks(jnp.ravel(x).astype(jnp.float32), block_size)
    if centering:
        means = jnp.mean(blocks, axis=1, keepdims=True)
        blocks = blocks - means
    else:
        means = None
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scales = jnp.maximum(absmax, 1e-12)
    normed = blocks / scales
    bounds = codebook_boundaries(codebook)
    codes = jnp.searchsorted(bounds, normed,
                             method="compare_all").astype(jnp.uint8)
    return BlockQuantized(
        codes=codes,
        scales=scales[:, 0].astype(scale_dtype),
        means=None if means is None else means[:, 0].astype(scale_dtype),
    )


def decode(
    q: BlockQuantized,
    codebook: jnp.ndarray,
    shape,
    *,
    out_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Dequantize back to `shape` (inverse of encode up to quantization error)."""
    vals = jnp.take(codebook, q.codes.astype(jnp.int32), axis=0)
    vals = vals * q.scales[:, None].astype(jnp.float32)
    if q.means is not None:
        vals = vals + q.means[:, None].astype(jnp.float32)
    n = 1
    for d in shape:
        n *= d
    return vals.reshape(-1)[:n].reshape(shape).astype(out_dtype)


def quantize_dequantize(
    x: jnp.ndarray,
    codebook: jnp.ndarray,
    block_size: int,
    *,
    centering: bool = False,
) -> jnp.ndarray:
    """Round-trip helper: the quantization 'noise lens' used in evals."""
    q = encode(x, codebook, block_size, centering=centering)
    return decode(q, codebook, x.shape, out_dtype=x.dtype)


def encode_chunked(x, codebook, block_size, *, chunk_blocks: int = 8192, **kw):
    """encode() in fixed-size chunks of blocks via lax.map — bounds peak
    memory for very large tensors (used when quantizing full checkpoints)."""
    flat = jnp.ravel(x).astype(jnp.float32)
    blocks = _pad_to_blocks(flat, block_size)
    n_blocks = blocks.shape[0]
    n_chunks = -(-n_blocks // chunk_blocks)
    pad = n_chunks * chunk_blocks - n_blocks
    if pad:
        blocks = jnp.concatenate([blocks, jnp.zeros((pad, block_size), blocks.dtype)])
    blocks = blocks.reshape(n_chunks, chunk_blocks, block_size)

    def one(chunk):
        return encode(chunk, codebook, block_size, **kw)

    q = jax.lax.map(one, blocks)
    codes = q.codes.reshape(-1, block_size)[:n_blocks]
    scales = q.scales.reshape(-1)[:n_blocks]
    means = None if q.means is None else q.means.reshape(-1)[:n_blocks]
    return BlockQuantized(codes=codes, scales=scales, means=means)
