"""Share of its roofline that the fused dequant-GEMM reached in the traced
window: the least time of its calls (costs/_qmatmul_kernel.py) over their
device time (%)."""


def read(ctx):
    k = (ctx.trace or {}).get("kernels", {}).get("_qmatmul_kernel")
    return 100.0 * k["least_s"] / k["time_s"] if k and k["time_s"] > 0 else None
