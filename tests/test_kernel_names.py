"""Every Pallas kernel carries its name into the compiled op.

A chip trace shows a Pallas call as a ``tpu_custom_call`` whose text ends
in ``frontend_attributes={kernel_metadata=...}``; ``pallas_call(...,
metadata={"kernel": <name>})`` fills that attribute, so a trace reader
can find a kernel by name and not only by its operands.  Exporting for
the TPU needs no chip: ``jax.export(..., platforms=["tpu"])`` lowers the
kernel on the CPU and the module text holds the attribute.  The operand
signature the benchmark's cost modules match on is pinned alongside.
"""

from __future__ import annotations

import functools
import json
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import kv_dequant as kd
from repro.kernels.qmatmul import qmatmul_pallas
from repro.kernels.quantize import quantize_blocks_pallas


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _qmatmul():
    fn = functools.partial(qmatmul_pallas, bits=4, block_size=64, bm=8,
                           bn=128, bk=512, interpret=False)
    args = (_sds((8, 512), jnp.bfloat16), _sds((64, 128), jnp.uint32),
            _sds((8, 128), jnp.bfloat16), _sds((16,), jnp.float32))
    sig = ("(tensor<8x512xbf16>, tensor<64x128xui32>, tensor<8x128xbf16>, "
           "tensor<16xf32>) -> tensor<8x128xbf16>")
    return fn, args, sig


def _dequant():
    spec = kd.KVQuantSpec(bits=4, block_size=64, dtype_name="float")

    def fn(p, s):
        return kd.dequant_rows_pallas(p, s, spec, 512, interpret=False)

    args = (_sds((256, 64), jnp.uint32), _sds((256, 8), jnp.bfloat16))
    sig = ("(tensor<256x64xui32>, tensor<256x8xbf16>, tensor<1x16xf32>) "
           "-> tensor<256x512xbf16>")
    return fn, args, sig


def _quantize():
    def fn(x, cb):
        return quantize_blocks_pallas(x, cb, interpret=False)

    args = (_sds((256, 64), jnp.float32), _sds((16,), jnp.float32))
    sig = ("(tensor<256x64xf32>, tensor<1x15xf32>) -> "
           "(tensor<256x64xi32>, tensor<256x1xf32>)")
    return fn, args, sig


def _kernel_metadata(text: str) -> list:
    """The decoded ``kernel_metadata`` attribute of each custom call
    (MLIR escapes the JSON's quotes and newlines as ``\\22``, ``\\0A``)."""
    raw = re.findall(r'kernel_metadata = "([^"]*)"', text)
    return [json.loads(re.sub(r"\\([0-9A-Fa-f]{2})",
                              lambda m: chr(int(m.group(1), 16)), r))
            for r in raw]


@pytest.mark.parametrize("name,build", [
    ("_qmatmul_kernel", _qmatmul),
    ("_dequant_kernel", _dequant),
    ("_quantize_kernel", _quantize),
])
def test_pallas_kernel_named_in_tpu_export(name, build):
    fn, args, sig = build()
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args) \
        .mlir_module()
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    assert len(calls) == 1, f"{name}: expected one tpu_custom_call"
    assert _kernel_metadata(calls[0]) == [{"kernel": name}]
    # naming the call leaves its operands and results as they were
    assert sig in calls[0], f"{name}: operand signature changed"
