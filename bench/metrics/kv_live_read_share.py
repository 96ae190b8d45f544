"""Share of the decode steps' KV read that holds tokens: the positions
live in the active rows over the positions the read covers, summed over
the window's decode steps (``kv_live`` and ``kv_read`` of the program's
``decode_step`` spans)."""


def read(ctx):
    a = [e["attrs"] for e in ctx.spans("decode_step") if "kv_read" in e["attrs"]]
    read_ = sum(x["kv_read"] for x in a)
    return sum(x["kv_live"] for x in a) / read_ if read_ else None
