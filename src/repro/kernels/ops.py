"""jit'd public wrappers around the Pallas kernels: operand preparation
(padding/alignment), QuantizedTensor interop, and dispatch between the
kernel (TPU), the gather-free jnp fused path (CPU serving), and the
pure-jnp reference oracle (semantics / dry-run).

The fused dequant-GEMM has three execution backends
(docs/quantization.md#the-fused-dequant-gemm-serving-path):

* ``pallas``  — kernels/qmatmul.py, the real TPU kernel (interpret mode
  off TPU, for parity tests only; interpret is orders of magnitude
  slower than jnp);
* ``jnp``     — :func:`qmatmul_fused_jnp`, a jit-friendly path with the
  kernel's VALUES (arithmetic dequant for ``int`` codebooks, codebook
  lookup for LUTs — XLA CPU vectorizes small-table gathers fine; the
  no-gather select tree is a TPU/VPU constraint, and is measurably
  slower on CPU) that dequantizes directly in ``[K, N]`` layout so the
  matmul hits XLA CPU's fast GEMM, and fences the dequantized tile with
  an optimization barrier so XLA cannot re-fuse the dequant chain into
  the dot (which re-evaluates it per output tile and is what makes the
  naive dequant+einsum slow);
* ``oracle``  — kernels/ref.py, the semantic ground truth.

``fused_backend()`` picks per platform (kernels/platform.py); the model
layer (models/layers.linear) routes QuantizedTensor matmuls here when
``cfg.matmul_mode`` resolves to fused.  On TPU a matrix the kernel cannot
tile (``pallas_fusable``: odd bit-widths, blocks narrower than eight
words, dims off the (8, 128) grid) is not fused-eligible and takes the
dequant path instead.
"""

from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import blockwise, packing
from repro.core.codebooks import make_codebook
from repro.core.qtensor import QuantizedTensor
from repro.kernels import qmatmul as qk
from repro.kernels import quantize as quantk
from repro.kernels.platform import kernels_compiled, resolve_interpret
from repro.kernels.ref import QMatmulOperand, qmatmul_ref


def prepare_operand(
    w: jnp.ndarray,
    *,
    bits: int,
    dtype: str = "float",
    block_size: int = 64,
    exponent_bits=None,
) -> QMatmulOperand:
    """Quantize a dense weight [K, N] into kernel layout (blocks along K,
    K-major storage: packed [ceil(Kb / cpw), N], scales [Kb // B, N]).

    K need not divide the block size or the packing word: the reduction
    dim is zero-padded to block alignment (zeros quantize to the exact-0
    code for the static codebooks, and the matmul wrappers zero-pad the
    activations to match), and each column's codes pack word-aligned with
    an inert tail for odd bit-widths."""
    K, N = w.shape
    # data-dependent (quantile) codebooks must see the REAL weights:
    # build before padding so artificial zeros don't skew the bins
    cb = make_codebook(dtype, bits, exponent_bits=exponent_bits, tensor=w)
    Kb = -(-K // block_size) * block_size
    if Kb != K:
        w = jnp.pad(w, ((0, Kb - K), (0, 0)))
    q = blockwise.encode(w.T, cb, block_size)  # blocks run along K per column
    codes = q.codes.reshape(N, Kb)
    packed = packing.pack(codes, bits).T       # word-aligned per column
    scales = q.scales.reshape(N, Kb // block_size).T
    return QMatmulOperand(
        packed=packed, scales=scales, codebook=cb,
        bits=bits, block_size=block_size, k_dim=Kb, dtype_name=dtype,
    )


#: Pallas tile caps: output columns, reduction rows and activation rows
_BN_MAX, _BK_MAX, _BM_MAX = 512, 2048, 256


def _k_unit(bits: int, block_size: int) -> int:
    """Smallest K tile whose x block (lanes), word block and scale block
    (sublanes) all sit on the TPU's (8, 128) tiling."""
    cpw = 32 // bits
    return math.lcm(128, 8 * cpw, 8 * block_size)


def _tile(n: int, unit: int, cap: int) -> int:
    """Largest multiple of `unit` dividing `n`, at most `cap`; `n` itself
    (a whole-extent block, always legal) when there is none."""
    t = min(cap, n) // unit * unit
    while t >= unit:
        if n % t == 0:
            return t
        t -= unit
    return n


def pallas_fusable(bits: int, block_size: int, n: int, k: int) -> bool:
    """Does the compiled Pallas kernel take a [K=k, N=n] operand at this
    width?  The rule: codes fill whole words (bits divides 32), a block
    spans whole words (a scale row expands by a sublane broadcast), N
    tiles by 128 lanes and K by ``_k_unit``.  Anything else — odd
    bit-widths among them — takes the dequant-einsum path on TPU."""
    if 32 % bits:
        return False
    return (block_size % (32 // bits) == 0 and n % 128 == 0
            and k % _k_unit(bits, block_size) == 0)


def qt_fused_eligible(qt) -> bool:
    """Can this QuantizedTensor be viewed as a fused-GEMM operand?

    Requires structured 2-D storage with no leading batch dims (a scan
    has already sliced the layer axis), no centering means and no proxy
    outlier rows — the kernel streams packed codes + scales only.  Where
    the fused backend is the Pallas kernel the matrix (its local shard
    inside a TP scope) must also be ``pallas_fusable``.  Ineligible QTs
    take the dequant-einsum path per matrix."""
    if not (
        isinstance(qt, QuantizedTensor)
        and qt.structured
        and len(qt.quant_shape) == 2
        and qt.packed.ndim == 2
        and qt.means is None
        and qt.outlier_idx is None
    ):
        return False
    if fused_backend() != "pallas":
        return True
    n, k = qt.quant_shape
    tp = current_tp_scope()
    if tp is not None and tp.tp_size > 1 and n % tp.tp_size == 0:
        n //= tp.tp_size
    return pallas_fusable(qt.bits, qt.block_size, n, k)


def operand_from_qtensor(qt: QuantizedTensor) -> QMatmulOperand:
    """View a 2-D QuantizedTensor storing [N, K] (transposed weights, or
    lm_head/embed which are natively (out, in)) as kernel operands.
    Structured QTs are already in kernel layout — any bit-width, column
    word tails included; flat ones are reshaped when aligned."""
    assert len(qt.quant_shape) == 2, "need [N, K] storage"
    N, K = qt.quant_shape
    cpw = 32 // qt.bits
    if qt.structured:
        assert qt.packed.ndim == 2, "batched QT: slice the batch dim first"
        packed, scales = qt.packed, qt.scales
    else:
        assert K % cpw == 0, "flat storage must align to the packing word"
        assert K % qt.block_size == 0, "flat storage must align to blocks"
        packed = qt.packed.reshape(N, K // cpw).T
        scales = qt.scales.reshape(N, K // qt.block_size).T
    return QMatmulOperand(
        packed=packed,
        scales=scales,
        codebook=qt.codebook,
        bits=qt.bits,
        block_size=qt.block_size,
        k_dim=K,
        dtype_name=qt.dtype_name,
    )


def fused_backend() -> str:
    """Default fused-GEMM backend for this process: the Pallas kernel on
    TPU, the gather-free jnp path everywhere else."""
    return "pallas" if kernels_compiled() else "jnp"


# --------------------------------------------------------------------------
# tensor-parallel dispatch scope
# --------------------------------------------------------------------------

class TPScope(NamedTuple):
    """One active TP dispatch scope: the mesh, the column-parallel axis,
    and the data axes rows of the activation may shard over."""

    mesh: object
    axis: str
    dp_axes: tuple = ()

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.axis]


#: active TPScopes, innermost last.  A trace-time stack, not device
#: state: models/sharding.Sharder.tp_scope() pushes one around the
#: serving jits so every fused_matmul traced inside runs column-parallel.
_TP_SCOPES: list = []


@contextlib.contextmanager
def tp_dispatch_scope(mesh, axis: str = "model", dp_axes=()):
    """While active, :func:`fused_matmul` runs column-parallel over `axis`:
    packed codes + scales stay sharded on their output-row dim and each
    shard runs the fused dequant-GEMM on its local rows inside a
    shard_map (the Pallas kernel is not GSPMD-partitionable, and the jnp
    path gets the same explicit per-shard execution so both backends
    compute bit-identical column-parallel tiles).  `dp_axes` lets the
    activation rows stay sharded over the data axes when they divide —
    without it every linear would all-gather x and compute the full
    global batch on every device."""
    _TP_SCOPES.append(TPScope(mesh, axis, tuple(dp_axes)))
    try:
        yield
    finally:
        _TP_SCOPES.pop()


def current_tp_scope():
    return _TP_SCOPES[-1] if _TP_SCOPES else None


def _row_part(tp: TPScope, m_rows: int):
    """Partition entry for the flattened activation rows [M, K]: the
    scope's data axes when M divides them (each shard then computes only
    its batch slice), None (replicated) otherwise.  Row partitioning
    cannot change any output element — each row's reduction is untouched
    — so this is purely a compute/comms-saving choice shared by BOTH
    matmul modes."""
    if tp.dp_axes:
        size = math.prod(tp.mesh.shape[a] for a in tp.dp_axes)
        if size > 1 and m_rows % size == 0:
            return tp.dp_axes
    return None


def tp_column_parallel_einsum(x, wt, tp: TPScope):
    """``y = x @ wt.T`` with wt [N, K] sharded on rows — the
    dequant-einsum oracle path under TP.  Runs inside the SAME explicit
    shard_map shape as :func:`_fused_matmul_tp` so the two matmul modes
    partition identically and greedy decode stays token-identical across
    them on a mesh (GSPMD left to its own devices partitions the two
    programs differently and the bf16 foldings drift)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    rows = _row_part(tp, x2.shape[0])

    def local(x2, wt_local):
        return jnp.einsum("mk,nk->mn", x2, wt_local)

    y = jax.shard_map(
        local, mesh=tp.mesh, in_specs=(P(rows), P(tp.axis)),
        out_specs=P(rows, tp.axis), check_vma=False,
    )(x2, wt)
    return y.reshape(lead + (y.shape[-1],))


def _fused_matmul_tp(x, op: QMatmulOperand, *, backend, interpret,
                     tp: TPScope):
    """Column-parallel fused dequant-GEMM: activation rows sharded over
    the data axes (when they divide), operand columns sharded over the
    TP axis, output sharded (rows, columns) accordingly."""
    lead = x.shape[:-1]
    x2 = _pad_x_to_k(x.reshape(-1, x.shape[-1]), op.k_dim)
    rows = _row_part(tp, x2.shape[0])

    def local(x2, packed, scales, codebook):
        lop = QMatmulOperand(
            packed=packed, scales=scales, codebook=codebook,
            bits=op.bits, block_size=op.block_size, k_dim=op.k_dim,
            dtype_name=op.dtype_name,
        )
        return _fused_matmul_local(x2, lop, backend=backend,
                                   interpret=interpret)

    y = jax.shard_map(
        local, mesh=tp.mesh,
        in_specs=(P(rows), P(None, tp.axis), P(None, tp.axis), P()),
        out_specs=P(rows, tp.axis), check_vma=False,
    )(x2, op.packed, op.scales, op.codebook)
    return y.reshape(lead + (y.shape[-1],))


def qmatmul_fused_jnp(x2: jnp.ndarray, op: QMatmulOperand) -> jnp.ndarray:
    """Fused path without Pallas: x2 [M, k_dim] @ W -> [M, N] in x2.dtype.

    Dequantizes straight from the K-major storage into [K, N] layout
    (never a [N, K] float transpose), applies scales via a blocked
    reshape, fences with an optimization barrier, and runs a single f32
    GEMM.  Mirrors kernel semantics: values and scales agree with the
    oracle bit-for-bit; only f32 accumulation order differs."""
    K = op.k_dim
    N = op.packed.shape[1]
    bits, bs = op.bits, op.block_size
    cpw = 32 // bits
    assert K % bs == 0, (K, bs)

    shifts = jnp.arange(cpw, dtype=jnp.uint32) * bits
    mask = jnp.uint32((1 << bits) - 1)
    c = ((op.packed[:, None, :] >> shifts[None, :, None]) & mask)
    c = c.reshape(-1, N)[:K]                            # [K, N] codes
    if op.dtype_name == "int":
        half = float(2 ** (bits - 1) - 1)
        vals = jnp.clip(c.astype(jnp.float32) - half, -half, half) / half
    else:
        vals = jnp.take(op.codebook.astype(jnp.float32),
                        c.astype(jnp.int32), axis=0)
    s = op.scales.astype(jnp.float32)                   # [K // bs, N]
    wt = (vals.reshape(K // bs, bs, N) * s[:, None, :]).reshape(K, N)
    # round the weight tile to the activation dtype — exactly the
    # transient dequantize_tensor(out_dtype=x.dtype) produces — so the
    # fused and dequant_einsum paths multiply IDENTICAL weight values
    # and greedy decode stays token-stable across modes (a no-op for
    # f32 activations; the golden tests in test_decode_consistency.py
    # pin this).  The barrier sits BETWEEN the down- and up-cast:
    # placed after, XLA folds convert(f32->bf16->f32) to identity and
    # the rounding silently disappears.
    wt = jax.lax.optimization_barrier(wt.astype(x2.dtype))
    wt = wt.astype(jnp.float32)
    y = x2.astype(jnp.float32) @ wt
    return y.astype(x2.dtype)


def _pad_x_to_k(x2: jnp.ndarray, k_dim: int) -> jnp.ndarray:
    K = x2.shape[-1]
    assert K <= k_dim, (K, k_dim)
    return jnp.pad(x2, ((0, 0), (0, k_dim - K))) if K < k_dim else x2


def qmatmul(
    x: jnp.ndarray,
    op: QMatmulOperand,
    *,
    interpret: bool | None = None,
):
    """y = x @ W via the Pallas kernel, x [..., K<=k_dim] -> [..., N].

    Picks (8, 128)-aligned tiles (``_tile``; a dim with no aligned
    divisor becomes one whole-extent block), pads M and pads K to the
    word/block alignment (including odd-bit word tails: the word-aligned
    column packing makes zero-padding the word axis exactly equivalent to
    packing zero-padded codes), and permutes x into the kernel's
    plane-major column order.  ``interpret`` None: compiled on TPU,
    interpreted elsewhere."""
    lead = x.shape[:-1]
    x2 = _pad_x_to_k(x.reshape(-1, x.shape[-1]), op.k_dim)
    M, K = x2.shape
    N = op.packed.shape[1]
    bits, bs = op.bits, op.block_size
    cpw = 32 // bits

    align = math.lcm(cpw, bs)
    Kp = -(-K // align) * align
    bk = _tile(Kp, _k_unit(bits, bs), _BK_MAX)
    bn = _tile(N, 128, _BN_MAX)
    bm = min(_BM_MAX, 8 * (-(-M // 8)))
    Mp = -(-M // bm) * bm

    xp = jnp.pad(x2, ((0, Mp - M), (0, Kp - K)))
    packed, scales = op.packed, op.scales
    if packed.shape[0] != Kp // cpw:
        packed = jnp.pad(packed, ((0, Kp // cpw - packed.shape[0]), (0, 0)))
    if scales.shape[0] != Kp // bs:
        scales = jnp.pad(scales, ((0, Kp // bs - scales.shape[0]), (0, 0)))

    y = qk.qmatmul_pallas(
        qk.permute_x(xp, bits, bk), packed, scales, op.codebook,
        bits=bits, block_size=bs, dtype_name=op.dtype_name,
        bm=bm, bn=bn, bk=bk, interpret=resolve_interpret(interpret),
    )
    return y[:M].reshape(lead + (N,))


def _fused_matmul_local(
    x: jnp.ndarray,
    op: QMatmulOperand,
    *,
    backend: str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Single-shard fused dequant-GEMM body (also the per-shard body the
    TP dispatch runs inside its shard_map)."""
    if backend is None:
        backend = fused_backend()
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if backend == "pallas":
        return qmatmul(x, op, interpret=interpret)
    x2 = _pad_x_to_k(x2, op.k_dim)
    if backend == "jnp":
        y = qmatmul_fused_jnp(x2, op)
    elif backend == "oracle":
        y = qmatmul_ref(x2, op)
    else:
        raise ValueError(f"unknown fused backend {backend!r}")
    return y.reshape(lead + (y.shape[-1],))


def fused_matmul(
    x: jnp.ndarray,
    op: QMatmulOperand,
    *,
    backend: str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Backend-dispatched fused dequant-GEMM: x [..., K<=k_dim] -> [..., N].

    backend: "pallas" | "jnp" | "oracle" (None -> fused_backend()).
    interpret only applies to the pallas backend (None -> compiled on
    TPU, interpreted elsewhere).

    Inside a :func:`tp_dispatch_scope` (models/sharding.Sharder.tp_scope)
    the matmul runs column-parallel: operands whose output-column count
    divides the TP degree keep packed/scales sharded on `model` and hit
    the per-shard body inside a shard_map; others run the single-shard
    body and let GSPMD place them."""
    tp = current_tp_scope()
    if tp is not None and op.packed.ndim == 2:
        if tp.tp_size > 1 and op.packed.shape[1] % tp.tp_size == 0:
            return _fused_matmul_tp(x, op, backend=backend,
                                    interpret=interpret, tp=tp)
    return _fused_matmul_local(x, op, backend=backend, interpret=interpret)


def quantize_blocks(
    x: jnp.ndarray,
    codebook: jnp.ndarray,
    block_size: int,
    *,
    interpret: bool | None = None,
):
    """Blockwise encode of a flat tensor -> (codes [n_blocks, B], scales)
    through the Pallas encode kernel (oracle: ref.quantize_blocks_ref)."""
    flat = jnp.ravel(x).astype(jnp.float32)
    n_blocks = -(-flat.shape[0] // block_size)
    pad = n_blocks * block_size - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    xb = flat.reshape(n_blocks, block_size)
    tile = 256
    while n_blocks % tile:
        tile //= 2
    codes, scales = quantk.quantize_blocks_pallas(
        xb, codebook, tile_blocks=max(tile, 1),
        interpret=resolve_interpret(interpret),
    )
    return codes, scales[:, 0]
