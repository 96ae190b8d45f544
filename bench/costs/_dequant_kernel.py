"""The k-bit KV-cache dequant read (Pallas, ``kernels/kv_dequant.py``).

Its call takes packed token rows [R, W] uint32, their block scales
[R, NB] and the codebook [1, 2^bits] f32, and writes the rows [R, F]
(F = W * cpw features) in bf16.  It does no matrix work: its least time
is its bytes, the packed rows and scales read once and the rows written.
"""

from bench.xspace import nbytes


def match(op):
    if op["target"] != "tpu_custom_call" or len(op["operands"]) != 3:
        return None
    (pd, p), (sd, s), (cd, cb) = op["operands"]
    if pd != "u32" or cd != "f32" or len(p) != 2 or len(s) != 2 or len(cb) != 2:
        return None
    if len(op["out"]) != 1 or len(op["out"][0][1]) != 2:
        return None
    od, (R, F) = op["out"][0]
    if p[0] != R or s[0] != R or cb[0] != 1 or F % p[1]:
        return None
    return {"R": R, "W": p[1], "NB": s[1], "F": F, "scale_dtype": sd,
            "out_dtype": od, "codebook": cb[1]}


def cost(c):
    R = c["R"]
    b = (R * c["W"] * 4 + nbytes(c["scale_dtype"], (R, c["NB"]))
         + 4 * c["codebook"] + nbytes(c["out_dtype"], (R, c["F"])))
    return 0.0, float(b)
