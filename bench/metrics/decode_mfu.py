"""Model FLOPs of the traced decode steps over their device time at the
chip's peak (%).  A step's FLOPs are the count that the configuration's
family gives for its active rows and their live positions
(``spec.decode_flops``, ``bench/families/``).  Device time is that of the
decode program's runs in the trace (``jit_step_paged``)."""


def read(ctx):
    mod = (ctx.trace or {}).get("modules", {}).get("jit_step_paged")
    steps = ctx.decode_steps
    if not mod or not steps or mod["time_s"] <= 0:
        return None
    flops = [ctx.spec.decode_flops(rows, live) for rows, live in steps]
    per_step = sum(flops) / len(flops)
    return 100.0 * per_step * mod["calls"] / (
        mod["time_s"] * ctx.peaks["bf16_flops_per_s"])
