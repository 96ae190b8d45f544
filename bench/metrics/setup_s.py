"""Process start to the opening of the measured window: imports, weights,
compiling or loading every program the traffic uses, and the traffic's
own warm-up."""


def read(ctx):
    return ctx.setup_s
