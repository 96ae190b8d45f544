"""Odd bit-widths (3/5/6) and last-block padding round-trips for
core/packing + core/qtensor — the storage corners a mixed-precision
plan exercises heavily (per-matrix k means every width appears, and
d_ff/head_dim shapes need not divide block_size or the packing word).

Kept hypothesis-free (test_packing.py skips wholesale without it)."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import packing
from repro.core.qtensor import (
    dequantize_tensor,
    quantization_error,
    quantize_tensor,
    to_structured,
)


@pytest.mark.parametrize("bits", [3, 5, 6])
def test_odd_bit_word_tail_roundtrip(bits):
    """Odd widths waste 32 % bits per word; lengths straddling the word
    boundary (cpw-1, cpw, cpw+1 codes) must round-trip exactly."""
    cpw = packing.codes_per_word(bits)
    for n in (1, cpw - 1, cpw, cpw + 1, 3 * cpw + 2):
        codes = jax.random.randint(
            jax.random.PRNGKey(n), (n,), 0, 2**bits
        ).astype(jnp.uint8)
        words = packing.pack(codes, bits)
        assert words.shape == (packing.packed_size(n, bits),)
        assert jnp.array_equal(packing.unpack(words, bits, n), codes)
        # the padded tail must stay inert: full-word unpack yields zeros
        full = packing.unpack(words, bits, words.shape[0] * cpw)
        assert jnp.all(full[n:] == 0)


@pytest.mark.parametrize("bits", [3, 5, 6])
def test_odd_bit_batched_roundtrip(bits):
    cpw = packing.codes_per_word(bits)
    n = 2 * cpw + 3  # not word-aligned
    codes = jax.random.randint(
        jax.random.PRNGKey(1), (5, n), 0, 2**bits
    ).astype(jnp.uint8)
    words = packing.pack(codes, bits)
    assert words.shape == (5, packing.packed_size(n, bits))
    assert jnp.array_equal(packing.unpack(words, bits, n), codes)


@pytest.mark.parametrize("bits", [3, 5, 6])
@pytest.mark.parametrize("shape", [(7, 37), (13, 50), (61,)])
def test_qtensor_last_block_padding(bits, shape):
    """Shapes whose element count does not divide block_size: the last
    block is zero-padded at encode and truncated at decode."""
    x = jax.random.normal(jax.random.PRNGKey(3), shape) * 1.7
    qt = quantize_tensor(x, bits=bits, dtype="float", block_size=16)
    assert qt.quant_shape == shape
    xr = dequantize_tensor(qt, out_dtype=jnp.float32)
    assert xr.shape == x.shape
    assert float(quantization_error(x, qt)) < 0.45


@pytest.mark.parametrize("bits", [3, 5, 6])
def test_qtensor_odd_bits_batched_stack(bits):
    """Scan-stacked items with a non-divisible flattened size (the
    stacked-weight case a plan assigns odd k to)."""
    xs = jax.random.normal(jax.random.PRNGKey(4), (3, 9, 21))
    qt = quantize_tensor(xs, bits=bits, dtype="int", block_size=32,
                         batch_dims=1)
    xr = dequantize_tensor(qt, out_dtype=jnp.float32)
    assert xr.shape == xs.shape
    for i in range(3):
        qi = quantize_tensor(xs[i], bits=bits, dtype="int", block_size=32)
        assert jnp.allclose(xr[i], dequantize_tensor(qi, out_dtype=jnp.float32))


def test_structured_storage_repacks_word_tails():
    """Odd bit-widths whose cols don't divide the packing word used to
    fall back to flat storage; to_structured now REPACKS them row-aligned
    (3-bit cpw=10 on a 64-col matrix), bit-identically to the flat
    layout, so every width can feed the fused dequant-GEMM.  Only cols
    that straddle quantization blocks still fall back."""
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 64))
    flat3 = quantize_tensor(x, bits=3, dtype="float", block_size=16)
    qt3 = to_structured(flat3)
    assert qt3.structured  # 64 % 10 != 0 -> row-aligned repack
    # K-major storage: each row's words run down a column
    assert qt3.packed.shape == (packing.packed_size(64, 3), 16)
    assert jnp.array_equal(
        dequantize_tensor(qt3, out_dtype=jnp.float32),
        dequantize_tensor(flat3, out_dtype=jnp.float32),
    )
    qt4 = to_structured(quantize_tensor(x, bits=4, dtype="float", block_size=16))
    assert qt4.structured      # 64 % 8 == 0 and 64 % 16 == 0: pure reshape


def test_structured_storage_falls_back_on_block_straddle():
    """cols % block_size != 0 means quantization blocks straddle rows —
    no row-structured layout exists; the flat storage must come back
    unchanged (and still dequantize correctly)."""
    x = jax.random.normal(jax.random.PRNGKey(6), (8, 40))
    flat = quantize_tensor(x, bits=4, dtype="float", block_size=16)
    qt = to_structured(flat)
    assert not qt.structured  # 40 % 16 != 0
    assert jnp.allclose(
        dequantize_tensor(qt, out_dtype=jnp.float32),
        dequantize_tensor(flat, out_dtype=jnp.float32),
    )
