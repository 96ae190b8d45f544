"""The load generator: drives the program's ``Server`` on the wall clock.

Each request is submitted once its due time has passed, whatever the
server is doing; then ``Server.step()`` runs.  When nothing runs or
waits, the loop sleeps until the next due time.  Token times come from
the ``on_token`` callbacks on this loop's clock, and a request's time to
first token counts from its due time, so a stall delays every request
that falls due behind it.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np


class Log:
    """What one request saw: its due and submit times and token times."""

    __slots__ = ("due", "submit", "times", "tokens", "prompt", "max_new")

    def __init__(self, req, submit):
        self.due = req.due
        self.submit = submit
        self.prompt = req.prompt
        self.max_new = req.max_new
        self.times: list = []
        self.tokens: list = []


def drive(server, requests, *, t_open: float, t_close: float, drain_s: float,
          drain: bool, seeded=(), annotate=None, on_tick=None):
    """Run the traffic.  Times are seconds from the start of traffic.

    ``seeded`` requests (the pool's steady population) are submitted and
    admitted before the traffic starts.  Requests due before ``t_close``
    are submitted.  With ``drain`` the loop goes on after the close until
    every request due in the window has its first token, at most
    ``drain_s`` seconds; without, it stops at the close.
    ``annotate(name)`` gives a context around each of the loop's own calls
    (profiler spans in a traced run); ``on_tick(now)`` runs before each
    step.  Returns (logs by server id, t0 on the perf_counter clock, the
    time each step returned)."""
    ann = annotate or (lambda name: contextlib.nullcontext())
    clock = time.perf_counter
    logs: dict = {}

    def on_token(rid, tok):
        with ann("bench/on_token"):
            log = logs[rid]
            log.times.append(clock())
            log.tokens.append(tok)

    for req in seeded:
        rid = server.submit(req.prompt, req.max_new, on_token=on_token)
        logs[rid] = Log(req, clock())
    if seeded:
        server.step()

    window, ends = [], []
    t0 = clock()
    i, n = 0, len(requests)
    while True:
        now = clock() - t0
        while i < n and requests[i].due <= now and requests[i].due < t_close:
            req = requests[i]
            with ann("bench/submit"):
                rid = server.submit(req.prompt, req.max_new, on_token=on_token)
            logs[rid] = Log(req, clock())
            if req.due >= t_open:
                window.append(logs[rid])
            i += 1
        if now >= t_close:
            if not drain or now >= t_close + drain_s:
                break
            if all(g.times for g in window):
                break
        if on_tick is not None:
            on_tick(now)
        if not server.scheduler.drained:
            with ann("bench/step"):
                server.step()
            ends.append(clock() - t0)
            continue
        nxt = requests[i].due if i < n and requests[i].due < t_close else t_close
        if nxt <= now:
            if now >= t_close:
                break
            continue
        with ann("bench/sleep"):
            time.sleep(min(nxt - now, 0.05))
    for g in logs.values():
        g.submit -= t0
        g.times = [t - t0 for t in g.times]
    return logs, t0, ends


def window_stats(logs: dict, t_open: float, t_close: float,
                 backlog: bool, ends=()) -> dict:
    """Readings of the window [t_open, t_close).  The requests it answers
    for are those due in it, or, for a backlog, those that emitted a
    token in it.  The token rate counts whole steps: the tokens emitted
    after the last step that returned by the opening, up to the last that
    returned before the close, over the time between those two returns."""
    if backlog:
        inside = [g for g in logs.values()
                  if any(t_open <= t < t_close for t in g.times)]
    else:
        inside = [g for g in logs.values() if t_open <= g.due < t_close]
    ttft = [g.times[0] - g.due for g in inside if g.times]
    e = np.asarray(ends, dtype=np.float64)
    a = float(e[e <= t_open].max()) if np.any(e <= t_open) else t_open
    b = float(e[e < t_close].max()) if np.any(e < t_close) else t_close
    gaps, n_tok, n_whole = [], 0, 0
    for g in logs.values():
        t = np.asarray(g.times)
        n_tok += int(np.sum((t >= t_open) & (t < t_close)))
        n_whole += int(np.sum((t > a) & (t <= b)))
        d = np.diff(t)
        end = t[1:]
        gaps.extend(d[(end >= t_open) & (end < t_close)].tolist())
    late = [g.submit - g.due for g in inside if g.submit >= 0]
    return {
        "attempted": len(inside),
        "failed": sum(1 for g in inside if not g.times),
        "ttft": ttft, "gaps": gaps, "tokens": n_tok,
        "tokens_per_s": n_whole / (b - a) if b > a else 0.0,
        "late_p50_ms": 1e3 * float(np.median(late)) if late else 0.0,
        "late_max_ms": 1e3 * float(np.max(late)) if late else 0.0,
    }
