"""Mean share of the slot pool that is decoding, over the window's decode
steps (the ``batch_fill`` of the program's ``decode_step`` spans)."""

import numpy as np


def read(ctx):
    f = [e["attrs"]["batch_fill"] for e in ctx.spans("decode_step")]
    return float(np.mean(f)) if f else None
