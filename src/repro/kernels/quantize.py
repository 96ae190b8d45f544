"""Blockwise absmax quantization encode kernel (Pallas TPU).

Offline/checkpoint-load path: chunks a tensor's blocks through VMEM,
computes per-block absmax scales and nearest-codebook codes with a
compare-count (monotone codebook -> code = #boundaries below value), no
gathers and no sort.  Oracle: kernels/ref.py::quantize_blocks_ref and
core/blockwise.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quantize_kernel(x_ref, b_ref, codes_ref, scales_ref, *, n_bounds):
    x = x_ref[...].astype(jnp.float32)            # [tb, B]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12)
    normed = x / scale
    codes = jnp.zeros(x.shape, jnp.int32)
    for j in range(n_bounds):                     # 2**bits - 1 compares
        codes += (normed > b_ref[0, j]).astype(jnp.int32)
    codes_ref[...] = codes
    scales_ref[...] = scale.astype(scales_ref.dtype)


def quantize_blocks_pallas(
    x_blocks: jnp.ndarray,
    codebook: jnp.ndarray,
    *,
    tile_blocks: int = 256,
    interpret: bool = False,
):
    """x_blocks [n_blocks, B] -> (codes int32 [n_blocks, B], scales f32
    [n_blocks, 1]).  n_blocks must divide by tile_blocks (pad upstream)."""
    n_blocks, B = x_blocks.shape
    tile_blocks = min(tile_blocks, n_blocks)
    assert n_blocks % tile_blocks == 0
    bounds = ((codebook[:-1] + codebook[1:]) / 2.0).reshape(1, -1).astype(jnp.float32)
    n_bounds = bounds.shape[1]
    grid = (n_blocks // tile_blocks,)
    kernel = functools.partial(_quantize_kernel, n_bounds=n_bounds)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_blocks, B), lambda i: (i, 0)),
            pl.BlockSpec((1, n_bounds), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_blocks, B), lambda i: (i, 0)),
            pl.BlockSpec((tile_blocks, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, B), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        # names the call in the chip trace's op text (kernel_metadata)
        metadata={"kernel": "_quantize_kernel"},
    )(x_blocks, bounds)
