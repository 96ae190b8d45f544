"""Mean host time of the window's server steps (ms): each ``step`` span's
length less the union of the spans inside it that wait on the device,
dispatch to fence (``decode_step``, ``prefill``, ``prefill_chunk``,
``restore``).  What is left is the host's part of the step: admission
bookkeeping, building the decode arguments and uploading the page table,
and the emit loop with its callbacks."""

_DEVICE = ("decode_step", "prefill", "prefill_chunk", "restore")


def read(ctx):
    steps = ctx.spans("step")
    if not steps:
        return None
    # all of them, not only those ending in the window: a step that
    # straddles the opening keeps the children it had before it
    inner = sorted((e["t0"], e["t1"]) for e in ctx.telemetry.tracer.events
                   if e["kind"] == "span" and e["name"] in _DEVICE)
    host = []
    for s in steps:
        t0, t1 = s["t0"], s["t1"]
        waited, edge = 0.0, t0
        for a, b in inner:
            if t0 <= a and b <= t1:
                waited += max(0.0, b - max(a, edge))
                edge = max(edge, b)
        host.append(t1 - t0 - waited)
    return 1e3 * sum(host) / len(host)
