"""Median of every gap between consecutive tokens of a request that ends
in the window, over all requests (ms)."""

import numpy as np


def read(ctx):
    g = ctx.stats["gaps"]
    return 1e3 * float(np.percentile(g, 50)) if g else None
