"""QuantizedTensor: the pytree container for k-bit block-quantized params.

A QuantizedTensor stores a logical tensor of shape ``batch_shape +
quant_shape`` where each item along the batch dims (e.g. the layer axis of
a scan-stacked weight) is independently block-quantized:

  packed   uint32  [*B, n_words]      bit-packed codes (core/packing.py)
  scales   bf16    [*B, n_blocks]     per-block absmax constants
  means    bf16    [*B, n_blocks]?    per-block means (centering, App. B)
  codebook f32     [*B, 2^k]          sorted data-type codebook; batched so
                                      lax.scan over a stacked QT "just works"
                                      (and quantile codebooks are genuinely
                                      per-item)
  outlier_vals bf16 [*B, n_out, o]?   proxy-quantized 16-bit rows (Eq. 2)
  outlier_idx  int32[*B, n_out]?      input dims kept in 16-bit

Static metadata (pytree aux): quant_shape, bits, block_size, dtype name,
centering flag.  All leaves carry the same batch dims, so a stacked
QuantizedTensor can be scanned over layers directly.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import blockwise, packing
from repro.core.bits import BitsBreakdown, quantized_bits_per_param
from repro.core.codebooks import make_codebook, quantile_codebook


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["packed", "scales", "means", "codebook", "outlier_vals", "outlier_idx"],
    meta_fields=["quant_shape", "bits", "block_size", "dtype_name", "centering",
                 "outlier_axis", "transposed", "structured", "orig_dtype"],
)
@dataclasses.dataclass
class QuantizedTensor:
    packed: jnp.ndarray
    scales: jnp.ndarray
    means: Optional[jnp.ndarray]
    codebook: jnp.ndarray
    outlier_vals: Optional[jnp.ndarray]
    outlier_idx: Optional[jnp.ndarray]
    quant_shape: tuple
    bits: int
    block_size: int
    dtype_name: str
    centering: bool
    outlier_axis: int = 0
    transposed: bool = False
    #: structured storage (K-major): packed [*B, words_per_row, rows],
    #: scales [*B, cols//block, rows] — each logical row's codes and
    #: scales run DOWN a column.  2-D layouts that (a) shard by logical
    #: row under GSPMD without the 1-D<->2-D reshapes that force
    #: replication and (b) are exactly the fused dequant-GEMM kernel
    #: operand layout (kernels/qmatmul.py: reduction dim on the TPU's
    #: sublanes, output dim on its lanes).  Each row's codes are
    #: word-aligned, so words_per_row = ceil(cols / cpw) with the tail
    #: slots of the last word zero for odd bit-widths
    structured: bool = False
    #: dtype of the tensor handed to quantize_tensor, as a string (meta
    #: fields must hash); dequantize_params restores it
    orig_dtype: str = "float32"

    # -- convenience ----------------------------------------------------
    @property
    def batch_shape(self) -> tuple:
        return tuple(self.packed.shape[: -2 if self.structured else -1])

    @property
    def shape(self) -> tuple:
        return self.batch_shape + tuple(self.quant_shape)

    @property
    def n_params(self) -> int:
        return math.prod(self.shape)

    def bits_breakdown(self) -> BitsBreakdown:
        outlier_pct = 0.0
        if self.outlier_idx is not None:
            h = self.quant_shape[self.outlier_axis]
            outlier_pct = self.outlier_idx.shape[-1] / h
        return quantized_bits_per_param(
            self.bits,
            self.block_size,
            centering=self.centering,
            outlier_pct=outlier_pct,
        )


def _encode_one(x2d, codebook, bits, block_size, centering, scale_dtype):
    """Quantize one logical item (already flattened view ok). Returns leaves."""
    q = blockwise.encode(
        x2d, codebook, block_size, centering=centering, scale_dtype=scale_dtype
    )
    packed = packing.pack(q.codes.reshape(-1), bits)
    return packed, q.scales, q.means


def quantize_tensor(
    x: jnp.ndarray,
    *,
    bits: int,
    dtype: str = "float",
    block_size: int = 64,
    batch_dims: int = 0,
    centering: bool = False,
    exponent_bits: int | None = None,
    outlier_idx: jnp.ndarray | None = None,
    outlier_axis: int = 0,
    transposed: bool = False,
    scale_dtype=jnp.bfloat16,
) -> QuantizedTensor:
    """Quantize `x`; leading `batch_dims` axes are quantized independently.

    `outlier_idx` (proxy quantization): per-item indices into quant axis
    `outlier_axis` (0 = rows, -1 = last axis; the latter is the reduction
    dim of a transposed-stored weight); those slices are stored in 16-bit
    and zeroed before block quantization so they cannot pollute the absmax
    scales.
    """
    batch_shape = x.shape[:batch_dims]
    quant_shape = x.shape[batch_dims:]
    xb = x.reshape((-1,) + quant_shape)  # [B, *quant_shape]
    B = xb.shape[0]

    outlier_vals = None
    oidx = None
    if outlier_idx is not None:
        ax = outlier_axis % len(quant_shape)
        oidx = jnp.asarray(outlier_idx, jnp.int32).reshape(B, -1)
        take = jax.vmap(lambda w, j: jnp.take(w, j, axis=ax))
        outlier_vals = take(xb, oidx).astype(jnp.bfloat16)
        if ax == 0:
            zero = jax.vmap(lambda w, j: w.at[j].set(0.0))
        else:
            zero = jax.vmap(lambda w, j: w.at[..., j].set(0.0))
        xb = zero(xb, oidx)

    if dtype == "quantile":
        cb = jax.vmap(lambda t: quantile_codebook(t, bits))(xb)
    else:
        cb0 = make_codebook(dtype, bits, exponent_bits=exponent_bits)
        cb = jnp.broadcast_to(cb0, (B,) + cb0.shape)

    enc = jax.vmap(
        lambda t, c: _encode_one(t, c, bits, block_size, centering, scale_dtype)
    )
    packed, scales, means = enc(xb, cb)

    def unbatch(a):
        return None if a is None else a.reshape(batch_shape + a.shape[1:])

    return QuantizedTensor(
        packed=unbatch(packed),
        scales=unbatch(scales),
        means=unbatch(means),
        codebook=unbatch(cb),
        outlier_vals=unbatch(outlier_vals),
        outlier_idx=unbatch(oidx),
        quant_shape=tuple(quant_shape),
        bits=bits,
        block_size=block_size,
        dtype_name=dtype,
        centering=centering,
        outlier_axis=outlier_axis,
        transposed=transposed,
        orig_dtype=str(x.dtype),
    )


def to_structured(qt: QuantizedTensor) -> QuantizedTensor:
    """Reshape a 2-D-item QT into K-major structured storage (see class
    doc): packed [*B, words_per_row, rows], scales [*B, cols//block,
    rows].  Row-wise GSPMD sharding then works without 1-D<->2-D
    reshapes (which force involuntary replication), and the arrays are
    directly the fused dequant-GEMM kernel operands (kernels/ops.py).

    Requires cols divisible by the block size (blocks must not straddle
    rows).  When cols also divide the packing word the row-major words
    are a reshape away; otherwise (odd bit-widths: 3-bit cpw=10, 5-bit
    cpw=6, 6-bit cpw=5) the flat packing straddles rows and the codes are
    REPACKED row-aligned — each row gets ceil(cols/cpw) words with an
    inert zero tail, the same word-tail convention as core/packing on a
    single row."""
    if qt.structured or len(qt.quant_shape) != 2:
        return qt
    rows, cols = qt.quant_shape
    cpw = 32 // qt.bits
    if cols % qt.block_size:
        return qt  # flat fallback: blocks straddle rows
    b = qt.batch_shape
    if cols % cpw:
        codes = packing.unpack(qt.packed, qt.bits, rows * cols)
        packed = packing.pack(codes.reshape(b + (rows, cols)), qt.bits)
    else:
        packed = qt.packed.reshape(b + (rows, cols // cpw))

    def k_major(a):
        return None if a is None else jnp.swapaxes(
            a.reshape(b + (rows, -1)), -1, -2)

    return dataclasses.replace(
        qt,
        packed=k_major(packed),
        scales=k_major(qt.scales),
        means=k_major(qt.means),
        structured=True,
    )


def dequantize_tensor(qt: QuantizedTensor, out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Full dequantization back to the logical shape (incl. outlier scatter)."""
    quant_shape = tuple(qt.quant_shape)
    n = math.prod(quant_shape)
    batch_shape = tuple(qt.packed.shape[:-2]) if qt.structured else qt.batch_shape
    nb = len(batch_shape)

    def one_structured(a):
        rows, cols = quant_shape
        bs = qt.block_size
        codes = packing.unpack(a["packed"].T, qt.bits, cols)    # [rows, cols]
        vals = jnp.take(a["cb"], codes.astype(jnp.int32), axis=0)
        scales = a["scales"].T.astype(jnp.float32)              # [rows, cols/bs]
        w = vals.reshape(rows, cols // bs, bs) * scales[:, :, None]
        if a["means"] is not None:
            w = w + a["means"].T.astype(jnp.float32)[:, :, None]
        w = w.reshape(rows, cols)
        if a["oidx"] is not None:
            if qt.outlier_axis % 2 == 0:
                w = w.at[a["oidx"]].set(a["ovals"].astype(jnp.float32))
            else:
                w = w.at[..., a["oidx"]].set(a["ovals"].astype(jnp.float32))
        return w.astype(out_dtype)

    def one(a):
        if qt.structured:
            return one_structured(a)
        scales = a["scales"]
        codes = packing.unpack(a["packed"], qt.bits, scales.shape[-1] * qt.block_size)
        q = blockwise.BlockQuantized(
            codes=codes.reshape(scales.shape[-1], qt.block_size),
            scales=scales,
            means=a["means"],
        )
        w = blockwise.decode(q, a["cb"], (n,), out_dtype=jnp.float32).reshape(quant_shape)
        if a["oidx"] is not None:
            if qt.outlier_axis % len(quant_shape) == 0:
                w = w.at[a["oidx"]].set(a["ovals"].astype(jnp.float32))
            else:
                w = w.at[..., a["oidx"]].set(a["ovals"].astype(jnp.float32))
        return w.astype(out_dtype)

    def flat(a):
        # collapse batch dims to one mapped axis; None passes through (it is
        # an empty pytree subtree, so vmap simply ignores it)
        return None if a is None else a.reshape((-1,) + a.shape[nb:])

    args = dict(
        packed=flat(qt.packed),
        scales=flat(qt.scales),
        means=flat(qt.means),
        cb=flat(qt.codebook),
        ovals=flat(qt.outlier_vals),
        oidx=flat(qt.outlier_idx),
    )
    if not batch_shape:
        return one({k: (None if v is None else v[0]) for k, v in args.items()})
    out = jax.vmap(one)(args)
    return out.reshape(batch_shape + quant_shape)


def quantization_error(x: jnp.ndarray, qt: QuantizedTensor) -> jnp.ndarray:
    """RMS relative quantization error — used by tests and benchmarks."""
    w = dequantize_tensor(qt, out_dtype=jnp.float32)
    diff = w - x.astype(jnp.float32)
    return jnp.sqrt(jnp.mean(diff**2)) / (jnp.sqrt(jnp.mean(x.astype(jnp.float32) ** 2)) + 1e-12)
