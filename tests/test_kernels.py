"""Pallas kernel vs pure-jnp oracle sweeps (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import pytest

from repro.core.codebooks import make_codebook
from repro.kernels import ops
from repro.kernels.ref import qmatmul_ref, quantize_blocks_ref

# every test here drives pallas_call in interpret mode
pytestmark = pytest.mark.kernel


SWEEP = [
    # (bits, dtype, M, K, N, block)
    (4, "float", 8, 256, 128, 64),
    (4, "int", 16, 512, 256, 128),
    (3, "int", 3, 320, 96, 64),
    (3, "float", 8, 640, 128, 64),
    (5, "dynamic", 8, 192, 64, 64),
    (5, "float", 4, 384, 128, 128),
    (8, "int", 8, 256, 128, 64),
    (4, "quantile", 8, 256, 128, 64),
]


@pytest.mark.parametrize("bits,dtype,M,K,N,block", SWEEP)
def test_qmatmul_kernel_matches_ref(bits, dtype, M, K, N, block):
    key = jax.random.PRNGKey(bits * 1000 + M)
    x = jax.random.normal(key, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32) * 0.05
    op = ops.prepare_operand(w, bits=bits, dtype=dtype, block_size=block)
    y_ref = qmatmul_ref(x, op)
    y_ker = ops.qmatmul(x, op, interpret=True)
    rel = float(jnp.max(jnp.abs(y_ker - y_ref))) / (
        float(jnp.max(jnp.abs(y_ref))) + 1e-9
    )
    assert rel < 2e-5, rel


@pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.bfloat16])
def test_qmatmul_input_dtypes(in_dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 256), jnp.float32).astype(in_dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (256, 128)) * 0.05
    op = ops.prepare_operand(w, bits=4, dtype="float", block_size=64)
    y_ref = qmatmul_ref(x, op)
    y_ker = ops.qmatmul(x, op, interpret=True)
    assert y_ker.dtype == in_dtype
    assert jnp.allclose(
        y_ker.astype(jnp.float32), y_ref.astype(jnp.float32), atol=0.25, rtol=0.05
    )


def test_qmatmul_ragged_shapes_padding():
    """M/K/N not tile-aligned: the wrapper pads and slices correctly."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (5, 200), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (200, 70)) * 0.1
    # K=200 not divisible by lcm(8,64)=64 -> pads to 256
    op = ops.prepare_operand(
        jnp.pad(w, ((0, 56), (0, 0))), bits=4, dtype="int", block_size=64
    )
    xp = jnp.pad(x, ((0, 0), (0, 56)))
    y_ref = qmatmul_ref(xp, op)
    y_ker = ops.qmatmul(xp, op, interpret=True)
    assert jnp.allclose(y_ker, y_ref, atol=1e-4)


def test_qmatmul_matches_model_linear_path():
    from repro.configs import QuantConfig
    from repro.models.layers import linear
    from repro.models.quantize import _quantize_matrix

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (256, 192)) * 0.05
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 256))
    qt = _quantize_matrix(w, QuantConfig(bits=4, dtype="float", block_size=64))
    y_kernel = ops.qmatmul(x, ops.operand_from_qtensor(qt), interpret=True)
    y_model = linear(x, qt)
    assert jnp.allclose(y_kernel, y_model.astype(jnp.float32), atol=2e-2)


@pytest.mark.parametrize("bits,dtype", [(4, "float"), (3, "int"), (5, "dynamic")])
def test_quantize_kernel_matches_ref(bits, dtype):
    cb = make_codebook(dtype, bits)
    x = jax.random.normal(jax.random.PRNGKey(bits), (2048,)) * 2
    c1, s1 = ops.quantize_blocks(x, cb, 64, interpret=True)
    c2, s2 = quantize_blocks_ref(x.reshape(-1, 64), cb)
    assert jnp.array_equal(c1, c2)
    assert jnp.allclose(s1, s2, rtol=1e-6)


def test_quantize_kernel_matches_core_blockwise():
    from repro.core import blockwise

    cb = make_codebook("float", 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (4096,))
    codes, scales = ops.quantize_blocks(x, cb, 64, interpret=True)
    q = blockwise.encode(x, cb, 64)
    assert jnp.array_equal(codes.astype(jnp.uint8), q.codes)


def test_pallas_route_is_a_stated_rule(monkeypatch):
    """Where the fused backend is the Pallas kernel (TPU), a matrix it
    cannot tile — odd bit-widths, dims off the (8, 128) grid — is not
    fused-eligible and takes the dequant path; qwen2-7b's matrices at
    4 and 8 bits are, whole and per TP shard.  Steered here by patching
    the backend choice; the compiles themselves are in
    test_tpu_compile.py."""
    from repro.configs import QuantConfig
    from repro.models.layers import resolve_matmul_mode
    from repro.models.quantize import _quantize_matrix

    def qt(K, N, bits):
        w = jnp.zeros((K, N), jnp.float32)
        return _quantize_matrix(w, QuantConfig(bits=bits, block_size=64))

    assert ops.qt_fused_eligible(qt(192, 96, 3))       # jnp backend: all
    monkeypatch.setattr(ops, "fused_backend", lambda: "pallas")
    assert ops.qt_fused_eligible(qt(512, 512, 4))
    assert ops.qt_fused_eligible(qt(512, 256, 8))
    assert not ops.qt_fused_eligible(qt(640, 256, 3))  # odd width
    assert not ops.qt_fused_eligible(qt(512, 96, 4))   # N off 128 lanes
    assert not ops.qt_fused_eligible(qt(192, 256, 4))  # K off the tile
    assert resolve_matmul_mode("auto", qt(640, 256, 5)) == "dequant_einsum"
    for K, N in [(3584, 3584), (3584, 512), (3584, 18944), (18944, 3584),
                 (3584, 152064)]:
        for bits in (4, 8):
            assert ops.pallas_fusable(bits, 64, N, K)
            assert ops.pallas_fusable(bits, 64, N // 4, K)  # 4-way TP
