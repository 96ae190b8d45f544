"""Quickstart: the paper's technique in 30 lines.

    PYTHONPATH=src python examples/quickstart.py

Quantizes a weight matrix at several precisions/data types with block-wise
absmax quantization (Dettmers & Zettlemoyer 2023, Eq. 1), shows the
accuracy/bits trade-off, and runs the fused dequant-matmul kernel path.
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.core import quantize_tensor, dequantize_tensor, quantization_error
from repro.core.bits import quantized_bits_per_param
from repro.kernels import ops

key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (1024, 512)) * 0.04  # a weight matrix
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 1024))  # activations

print(f"{'config':24s} {'bits/param':>10} {'rel err':>9} {'matmul err':>11}")
for bits, dtype in [(8, "int"), (4, "float"), (4, "quantile"), (3, "int")]:
    for block in (64, 1024):
        qt = quantize_tensor(w, bits=bits, dtype=dtype, block_size=block)
        err = float(quantization_error(w, qt))
        bpp = quantized_bits_per_param(bits, block).ideal_bits_per_param
        y_ref = x @ w
        y_q = x @ dequantize_tensor(qt, out_dtype=jnp.float32)
        merr = float(jnp.linalg.norm(y_q - y_ref) / jnp.linalg.norm(y_ref))
        print(f"{dtype}{bits}-b{block:<5d}{'':10s} {bpp:10.3f} {err:9.4f} {merr:11.4f}")

# the fused kernel path (Pallas: compiled on TPU, interpret mode elsewhere)
op = ops.prepare_operand(w, bits=4, dtype="float", block_size=64)
y_kernel = ops.qmatmul(x, op)
y_dense = x @ w
rel = float(jnp.linalg.norm(y_kernel - y_dense) / jnp.linalg.norm(y_dense))
print(f"\nfused 4-bit dequant-matmul kernel vs dense: rel err {rel:.4f}")
print("weight bytes streamed: "
      f"{op.packed.nbytes + op.scales.nbytes} vs bf16 {w.size * 2} "
      f"({(op.packed.nbytes + op.scales.nbytes) / (w.size * 2):.2f}x)")
