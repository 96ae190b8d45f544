"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Interpret mode never checks the chip's (8, 128) block tiling or its VMEM
budget; the TPU compiler does, and it is installed here.  These tests
compile — without a chip — for a described ``v5e:2x2`` topology:

* ``ops.qmatmul`` (the fused dequant-GEMM) at qwen2-7b's ``w_up``
  (3584 -> 18944), ``w_down`` (18944 -> 3584) and ``lm_head``
  (3584 -> 152064) shapes, at 4-bit float (the paper's recommended
  weights) and 8-bit int, for a decode step (M = 8 slots) and a prefill
  bucket (M = 512);
* ``dequant_rows_pallas`` (the packed KV-cache read) at qwen2-7b's
  feature width (4 KV heads x 128 = 512) for kv4 and kv8.

Nothing runs: a pass says the chip's compiler takes the kernel, not that
it is fast or right on the chip (chip_smoke.py checks that).  The
topology is described inside a module fixture, never at import time, and
the persistent compile cache is off around these compiles (their
executables cannot be read back without a chip).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import kv_dequant as kd
from repro.kernels import ops
from repro.kernels.ref import QMatmulOperand


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


#: qwen2-7b's matrices that bound the kernel's tiling: (name, K, N)
QWEN2_7B_MATRICES = [
    ("w_up", 3584, 18944),
    ("w_down", 18944, 3584),
    ("lm_head", 3584, 152064),
]


@pytest.mark.parametrize("M", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("bits,dtype", [(4, "float"), (8, "int")])
@pytest.mark.parametrize("name,K,N", QWEN2_7B_MATRICES,
                         ids=[m[0] for m in QWEN2_7B_MATRICES])
def test_qmatmul_compiles_for_v5e(one_chip, name, K, N, bits, dtype, M):
    block = 64
    cpw = 32 // bits
    assert ops.pallas_fusable(bits, block, N, K), name

    def fused(x, packed, scales, codebook):
        op = QMatmulOperand(packed=packed, scales=scales, codebook=codebook,
                            bits=bits, block_size=block, k_dim=K,
                            dtype_name=dtype)
        return ops.qmatmul(x, op, interpret=False)

    compiled = jax.jit(fused).lower(
        _spec(one_chip, (M, K), jnp.bfloat16),
        _spec(one_chip, (K // cpw, N), jnp.uint32),
        _spec(one_chip, (K // block, N), jnp.bfloat16),
        _spec(one_chip, (2**bits,), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bits", [4, 8])
def test_kv_dequant_compiles_for_v5e(one_chip, bits):
    feat = 4 * 128            # qwen2-7b: 4 KV heads x head_dim 128
    rows = 4 * 1056           # 4 slots x a 1056-token cache
    spec = kd.KVQuantSpec(bits=bits, block_size=64, dtype_name="float")
    _, n_blocks, n_words = kd.kv_layout(spec, feat)
    compiled = jax.jit(
        lambda p, s: kd.dequant_rows_pallas(p, s, spec, feat,
                                            interpret=False)
    ).lower(
        _spec(one_chip, (rows, n_words), jnp.uint32),
        _spec(one_chip, (rows, n_blocks), jnp.bfloat16),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
