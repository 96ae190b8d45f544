"""The fused k-bit dequant-GEMM (Pallas, ``kernels/qmatmul.py``).

Its call takes the activations x [M, K] (K permuted within tiles),
packed codes [K/cpw, N] uint32, block scales [K/B, N] and the codebook
[2^bits] f32, and returns y [M, N] in the activations' dtype.  The work
is the GEMM, 2*M*K*N; the bytes are each operand once and the result.
"""

from bench.xspace import nbytes


def match(op):
    if (op["target"] != "tpu_custom_call" or len(op["operands"]) != 4
            or len(op["out"]) != 1):
        return None
    (xd, x), (wd, w), (sd, s), (cd, cb) = op["operands"]
    if (wd != "u32" or cd != "f32" or len(x) != 2 or len(w) != 2
            or len(s) != 2 or len(cb) != 1):
        return None
    (M, K), (Kw, N) = x, w
    if op["out"][0][1] != (M, N) or s[1] != N or K % Kw or K % s[0]:
        return None
    return {"M": M, "K": K, "N": N, "cpw": K // Kw, "block": K // s[0],
            "x_dtype": xd, "scale_dtype": sd, "codebook": cb[0]}


def cost(c):
    M, K, N = c["M"], c["K"], c["N"]
    flops = 2.0 * M * K * N
    b = (nbytes(c["x_dtype"], (M, K)) + K // c["cpw"] * N * 4
         + nbytes(c["scale_dtype"], (K // c["block"], N)) + 4 * c["codebook"]
         + nbytes(c["x_dtype"], (M, N)))
    return flops, float(b)
