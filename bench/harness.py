"""One run of one benchmark cell, from ``BENCHMARK.json`` and data files.

A cell names a configuration and a traffic mix; everything that belongs
to one of them sits in its own file, found by name under ``bench/``:

    configs/<config>.json    the model as run (model.py)
    traffic/<mix>.json       arrivals, lengths, pool and run phases
    checks/<cell>.json       the correctness sample and its limit
    metrics/<metric>.py      read(ctx) -> value or None, per metric
    costs/<kernel>.py        a kernel's operations and bytes (xspace.py)
    peaks.json               peak rates by device kind

A run builds the weights from the seed, warms up every prefill bucket the
traffic uses and the decode step, admits the pool's steady population
(``in_flight``), runs the traffic's warm-up, measures the window, reads peak memory, frees the server, and compares a sample of
what it served with the reference.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` turns on the program's telemetry and a
profiler window in the middle of the measured one, and reports its
per-layer metrics.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark file {path}")
    return json.loads(path.read_text())


def _load_module(kind: str, name: str):
    if not (BENCH / kind / f"{name}.py").is_file():
        raise FileNotFoundError(f"missing benchmark file {BENCH / kind / name}.py")
    return importlib.import_module(f"bench.{kind}.{name}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    check: dict
    end_to_end: list
    per_layer: list
    readers: dict
    costs: dict


def load_cell(bench: dict, name: str, data: Path = BENCH) -> Cell:
    """The cell `name` of `bench` (BENCHMARK.json) with every file it
    names, loaded; a missing file is an error."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = _load_json(data / "configs" / f"{w['config']}.json")
    mix = _load_json(data / "traffic" / f"{w['traffic']}.json")
    check = _load_json(data / "checks" / f"{name}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if mine(m) and m["moves"] in reported]
    readers = {m["name"]: _load_module("metrics", m["name"]) for m in e2e + layer}
    costs = {p.stem: _load_module("costs", p.stem)
             for p in sorted((BENCH / "costs").glob("*.py"))
             if not p.stem.startswith("__")}
    return Cell(name, w["chips"], config, mix, check, e2e, layer, readers,
                costs)


def peaks_for(kind: str) -> dict:
    table = _load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def _enable_compile_cache() -> None:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Context:
    """What a metric reader may read: the cell, the window's readings on
    the host clock, the program's telemetry spans in the window, the
    reduced device trace, and the decode steps traced."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def spans(self, name: str) -> list:
        """Telemetry spans of `name` that ended inside the window."""
        if self.telemetry is None:
            return []
        return [e for e in self.telemetry.tracer.events
                if e["kind"] == "span" and e["name"] == name
                and self.t_open <= e["t1"] < self.t_close]


def _warm_up(server, requests, vocab: int, done=frozenset()) -> set:
    """Compile (or load) every prefill bucket the requests use and the
    decode step: one short request per bucket not in `done`.  Returns the
    buckets warmed, with `done`."""
    from repro.serving.server import bucket_len

    pool = server.pool
    buckets = {bucket_len(len(r.prompt), minimum=max(8, pool.page_size),
                          cap=pool.cache_len) for r in requests}
    rng = np.random.default_rng(0)
    for b in sorted(buckets - done):
        server.submit(rng.integers(0, vocab, b - 1, dtype=np.int32), 2)
    server.run_until_drained()
    return buckets | done


def step_seconds(server, prompt_len: int, steps: int = 4) -> float:
    """Median wall time of a decode step, on programs already compiled."""
    server.submit(np.zeros(prompt_len, np.int32), steps + 2)
    server.step()
    t = []
    for _ in range(steps):
        t0 = time.perf_counter()
        server.step()
        t.append(time.perf_counter() - t0)
    server.run_until_drained()
    return float(np.median(t))


def in_flight(mix: dict, server, seed: int, vocab: int, prompt_len: int) -> list:
    """The pool's steady population (traffic.in_flight), sized by the
    mix: every slot, or by Little's law the rate times a request's mean
    lifetime, its mean output length at the decode step measured here
    (on a prompt of `prompt_len`, a length already warmed up)."""
    from bench import traffic

    size = mix["in_flight"]
    if size == "slots":
        n = server.pool.num_slots
    elif size == "little":
        n = round(mix["arrival"]["rate_per_s"] * traffic.mean_output(mix)
                  * step_seconds(server, prompt_len))
    else:
        raise ValueError(f"unknown in_flight {size!r}")
    return traffic.in_flight(mix, seed, n, vocab)


def serve(cell: Cell, seed: int, seconds: float, trace: bool, *,
          require_chip: bool = True, t_start: float | None = None):
    """Build the cell from the seed, warm it up and run its traffic; then
    read peak memory and free the server.  Returns (Context, logs by
    request, the weights)."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    dev = jax.devices()[0]
    if require_chip and (dev.platform != "tpu" or jax.device_count() < cell.chips):
        raise NoChip(f"found {jax.device_count()} {dev.platform} device(s); "
                     f"{cell.name} needs {cell.chips} TPU chip(s)")
    peaks = peaks_for(dev.device_kind) if require_chip else None
    if require_chip:
        _enable_compile_cache()

    from bench import loop, model, traffic
    from repro.serving.server import Server
    from repro.serving.telemetry import NOOP, Telemetry

    spec = model.spec_from_config(cell.config)
    cfg = model.program_config(cell.config, spec)
    mix = cell.mix
    weights = model.make_weights(spec, seed)
    requests = traffic.schedule(mix, seed, seconds, spec.vocab)
    tel = Telemetry() if trace else NOOP
    pool = mix["pool"]
    server = Server(model.program_params(weights, spec), cfg,
                    num_slots=pool["num_slots"], max_seq_len=pool["max_seq_len"],
                    page_size=pool["page_size"], paged=True, eos_id=None,
                    telemetry=tel)
    warm = _warm_up(server, requests, spec.vocab)
    seeded = in_flight(mix, server, seed, spec.vocab,
                       min(len(r.prompt) for r in requests))
    _warm_up(server, seeded, spec.vocab, warm)
    if trace:
        tel.reset()

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.perf_counter())
        if event == "/jax/core/compile/backend_compile_duration" else None)

    backlog = mix["arrival"]["process"] == "backlog"
    t_open = float(mix["warmup_s"])
    t_close = t_open + seconds
    prof = {"dir": None, "t0": None, "t1": None}
    annotate = None
    on_tick = None
    if trace:
        annotate = jax.profiler.TraceAnnotation
    if trace and require_chip:
        trace_s = min(float(mix["trace_s"]), seconds)
        t_on = t_open + (seconds - trace_s) / 2

        def on_tick(now):
            if prof["dir"] is None and now >= t_on:
                prof["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(prof["dir"], profiler_options=opts)
                prof["t0"] = time.perf_counter()
            elif prof["t1"] is None and prof["t0"] is not None \
                    and now >= t_on + trace_s:
                prof["t1"] = time.perf_counter()
                jax.profiler.stop_trace()

    logs, t0, ends = loop.drive(server, requests, t_open=t_open,
                                t_close=t_close, drain_s=float(mix["drain_s"]),
                                drain=not backlog, seeded=seeded,
                                annotate=annotate, on_tick=on_tick)
    if prof["t0"] is not None and prof["t1"] is None:
        prof["t1"] = time.perf_counter()
        jax.profiler.stop_trace()
    stats = loop.window_stats(logs, t_open, t_close, backlog, ends)
    peak = dev.memory_stats().get("peak_bytes_in_use") if require_chip else 0
    reduced = None
    if prof["dir"] is not None:
        reduced = _reduce_trace(prof["dir"], cell.costs, peaks)
    ctx = Context(cell=cell, spec=spec, seconds=seconds, stats=stats,
                  in_flight=len(seeded),
                  setup_s=t0 + t_open - t_start, peaks=peaks, trace=reduced,
                  telemetry=tel if trace else None,
                  t_open=t0 + t_open, t_close=t0 + t_close,
                  decode_steps=_decode_steps(tel, logs, t0, prof) if trace else [],
                  compiles_in_window=sum(t0 + t_open <= t < t0 + t_close
                                         for t in compiles),
                  device={"platform": dev.platform, "kind": dev.device_kind,
                          "count": jax.device_count(),
                          "memory_peak_bytes": int(peak or 0)})
    # the comparison runs after the window, with the program's state freed
    del server
    gc.collect()
    return ctx, list(logs.values()), weights


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        bench: dict | None = None, data: Path = BENCH,
        require_chip: bool = True, t_start: float | None = None,
        out=sys.stdout, err=sys.stderr) -> dict:
    """One run; prints and returns the result line."""
    from bench import check

    bench = bench if bench is not None else _load_json(ROOT / "BENCHMARK.json")
    cell = load_cell(bench, workload, data)
    ctx, logs, weights = serve(cell, seed, seconds, trace,
                               require_chip=require_chip, t_start=t_start)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        v = cell.readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    picked = check.sample(logs, seed, cell.check)
    got = check.readings(check.gaps(weights, ctx.spec, picked,
                                    cell.check["geometry"]))
    correct, compared = check.decide(got, cell.check["limits"])

    stats = ctx.stats
    result = {"correct": correct, "attempted": stats["attempted"],
              "failed": stats["failed"], "metrics": metrics,
              "device": dict(ctx.device)}
    if ctx.trace is not None:
        result["device"]["busy_s"] = ctx.trace["busy_s"]
        result["device"]["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["info"] = {"generator_late_p50_ms": stats["late_p50_ms"],
                      "generator_late_max_ms": stats["late_max_ms"],
                      "tokens_in_window": stats["tokens"],
                      "in_flight_at_start": ctx.in_flight,
                      "ttft_p95_s": float(np.percentile(stats["ttft"], 95))
                      if stats["ttft"] else None,
                      "itl_p95_ms": 1e3 * float(np.percentile(stats["gaps"], 95))
                      if stats["gaps"] else None,
                      "compiles_in_window": ctx.compiles_in_window,
                      "requests_sampled": len(picked),
                      "tokens_compared": got["tokens_compared"],
                      "tokens_off_greedy": got["tokens_off_greedy"]}
    result["check"] = compared
    print(f"requests attempted {stats['attempted']} failed {stats['failed']}, "
          f"generator late p50 {stats['late_p50_ms']:.3f} ms "
          f"max {stats['late_max_ms']:.3f} ms", file=err)
    for k, v in compared.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=err)
    print(json.dumps(result), file=out, flush=True)
    return result


def _reduce_trace(path: str, costs: dict, peaks: dict) -> dict:
    from jax.profiler import ProfileData

    from bench import xspace

    try:
        files = sorted(Path(path).rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"the profiler wrote no trace under {path}")
        return xspace.reduce(ProfileData.from_file(str(files[-1])), costs, peaks)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def _decode_steps(tel, logs, t0: float, prof: dict) -> list:
    """(rows, live positions summed over rows) of each decode step whose
    telemetry span lies inside the profiler window.  A row's live
    positions are its prompt plus the tokens it had emitted."""
    if prof["t0"] is None:
        return []
    spans = sorted((e["t0"], e["t1"], e["attrs"]["n_active"])
                   for e in tel.tracer.events
                   if e["kind"] == "span" and e["name"] == "decode_step")
    ends = np.asarray([s[1] for s in spans])
    live = np.zeros(len(spans))
    for g in logs.values():
        for j, t in enumerate(g.times[1:], start=1):
            k = np.searchsorted(ends, t0 + t, side="right") - 1
            if k >= 0:
                live[k] += len(g.prompt) + j
    return [(n, float(live[k])) for k, (a, b, n) in enumerate(spans)
            if prof["t0"] <= a and b <= prof["t1"]]


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    p = argparse.ArgumentParser(description="Run one benchmark cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    try:
        run(a.workload, a.seed, a.seconds, bool(a.trace), t_start=t_start)
    except NoChip as e:
        print(f"no measurement: {e}", file=sys.stderr)
        return 3
    return 0

