"""Share of its roofline that the KV-cache dequant kernel reached in the
traced window: the least time of its calls (costs/_dequant_kernel.py,
bytes-bound) over their device time (%)."""


def read(ctx):
    k = (ctx.trace or {}).get("kernels", {}).get("_dequant_kernel")
    return 100.0 * k["least_s"] / k["time_s"] if k and k["time_s"] > 0 else None
