"""Model FLOPs of the traced decode steps over their device time at the
chip's peak (%).  A step's FLOPs are 2 x the parameters every token
multiplies x its active rows, plus attention: 4 x layers x query width
x the live positions of its rows.  Device time is that of the decode
program's runs in the trace (``jit_step_paged``)."""


def read(ctx):
    mod = (ctx.trace or {}).get("modules", {}).get("jit_step_paged")
    steps = ctx.decode_steps
    if not mod or not steps or mod["time_s"] <= 0:
        return None
    s = ctx.spec
    flops = [2.0 * s.matmul_params() * rows
             + 4.0 * s.n_layers * s.q_dim * live for rows, live in steps]
    per_step = sum(flops) / len(flops)
    return 100.0 * per_step * mod["calls"] / (
        mod["time_s"] * ctx.peaks["bf16_flops_per_s"])
