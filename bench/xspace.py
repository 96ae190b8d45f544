"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

On a TPU the trace has one plane per chip (``/device:TPU:<i>``) whose
``XLA Modules`` line holds one event per program run and whose ``XLA
Ops`` line holds one event per HLO instruction, named by the
instruction's text (``%name = type opcode(operands), ...``), nested where
an instruction (a ``while`` over layers) contains others.  Host threads
are lines of ``/host:CPU``; the benchmark's own spans there are named
``bench/<what>``.  All event times are nanoseconds from the start of the
profile, on one clock.

Pallas kernels appear as ``tpu_custom_call`` instructions without their
kernel's name, so a kernel is recognised by the signature of its operands:
each ``bench/costs/<kernel>.py`` says which calls are its own and what
they cost.
"""

from __future__ import annotations

import re

_SHAPE = re.compile(r"\b(bf16|f16|f32|f64|u8|s8|u16|s16|u32|s32|u64|s64|pred|"
                    r"f8e4m3fn|f8e5m2)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "u8": 1, "s8": 1,
          "u16": 2, "s16": 2, "u32": 4, "s32": 4, "u64": 8, "s64": 8,
          "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shapes(text: str) -> list:
    return [(d, tuple(int(x) for x in s.split(",") if x))
            for d, s in _SHAPE.findall(text)]


def nbytes(dtype: str, shape: tuple) -> int:
    n = _BYTES[dtype]
    for d in shape:
        n *= d
    return n


def parse_op(name: str) -> dict:
    """Split an ``XLA Ops`` event name into the instruction's name, opcode,
    result shapes, operand shapes and custom-call target."""
    head, _, rest = name.partition(" = ")
    op = {"name": head.lstrip("%"), "opcode": None, "out": [], "operands": [],
          "target": None}
    m = re.search(r"\s([a-z][a-z0-9\-_]*)\(", rest)
    if m is None:
        return op
    op["opcode"] = m.group(1)
    op["out"] = _shapes(rest[:m.start()])
    args_end = rest.find("), ", m.end())
    args = rest[m.end():] if args_end < 0 else rest[m.end():args_end]
    op["operands"] = _shapes(args)
    t = re.search(r'custom_call_target="([^"]+)"', rest)
    op["target"] = t.group(1) if t else None
    return op


def op_family(op: dict) -> str:
    """An instruction's name without its numeric suffix (``fusion.12`` ->
    ``fusion``), for grouping."""
    return re.sub(r"(\.\d+)+$", "", op["name"])


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events) -> list:
    """Self time of each (start, end) event: its length minus that of the
    events nested directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_t = [e[1] - e[0] for e in events]
    stack = []
    for i in order:
        s, e = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            self_t[stack[-1]] -= e - s
        stack.append(i)
    return self_t


def reduce(pd, costs: dict, peaks: dict) -> dict:
    """Device time per kernel with its least time, per program, the busy
    union, the top operations by self time and the idle gaps by host span.
    Busy and idle are read over the stretch from the first to the last
    ``bench/`` span (the whole profile where there are none).

    ``pd``: ``jax.profiler.ProfileData``; ``costs``: kernel name -> module
    with ``match(op)`` and ``cost(shapes)``; ``peaks``: the device's row of
    the peaks table.  Busy and idle are averaged over the chips traced."""
    window_ns = None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            window_ns = st["profile_stop_time"] - st["profile_start_time"]
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if window_ns is None or not devices:
        raise ValueError("the trace has no TPU device plane or no profile window")

    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name.startswith("bench/"))
    # the window is the stretch the load generator ran inside the trace:
    # the profiler's own start and stop are not the system's idle time
    lo, hi = 0.0, float(window_ns)
    if host:
        lo, hi = min(h[1] for h in host), max(h[2] for h in host)

    kernels: dict = {}
    modules: dict = {}
    self_by: dict = {}
    busy_ns = 0.0
    gaps = []
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    nm = re.sub(r"\(\d+\)$", "", e.name)
                    m = modules.setdefault(nm, {"time_s": 0.0, "calls": 0})
                    m["time_s"] += e.duration_ns * 1e-9
                    m["calls"] += 1
            elif line.name == "XLA Ops":
                ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
        spans = [(s, e) for _, s, e in ops]
        for (name, s, e), st in zip(ops, _self_times(spans)):
            op = parse_op(name)
            fam = op_family(op)
            for kname, mod in costs.items():
                shapes = mod.match(op)
                if shapes is None:
                    continue
                flops, nb = mod.cost(shapes)
                k = kernels.setdefault(kname, {"time_s": 0.0, "least_s": 0.0,
                                               "calls": 0, "flops": 0.0,
                                               "bytes": 0.0})
                k["time_s"] += (e - s) * 1e-9
                k["least_s"] += max(flops / peaks["bf16_flops_per_s"],
                                    nb / peaks["hbm_bytes_per_s"])
                k["calls"] += 1
                k["flops"] += flops
                k["bytes"] += nb
                fam = kname
                break
            self_by[fam] = self_by.get(fam, 0.0) + st * 1e-9
        busy = _union((max(a, lo), min(b, hi)) for a, b in spans
                      if b > lo and a < hi)
        busy_ns += sum(b - a for a, b in busy)
        edge = lo
        for a, b in busy + [[hi, hi]]:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)

    idle: dict = {}
    for a, b in gaps:
        mid = (a + b) / 2
        inner = [h for h in host if h[1] <= mid <= h[2]]
        name = min(inner, key=lambda h: h[2] - h[1])[0] if inner else "outside bench spans"
        idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9 / len(devices)

    top = sorted(self_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / len(devices),
        "kernels": kernels,
        "modules": modules,
        "device_ops": [[n, t / len(devices)] for n, t in top],
        "idle_gaps": sorted(([n, t] for n, t in idle.items()),
                            key=lambda kv: -kv[1])[:10],
    }
