"""Mean time of the window's decode steps, dispatch to fence (the
program's ``decode_step`` spans, ms)."""


def read(ctx):
    d = [e["t1"] - e["t0"] for e in ctx.spans("decode_step")]
    return 1e3 * sum(d) / len(d) if d else None
