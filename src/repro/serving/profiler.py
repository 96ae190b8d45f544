"""Step profiler: roofline attribution for the jitted serving programs.

The serving telemetry (telemetry.py) measures *how long* each engine
step takes; this module says *how fast that is relative to the
hardware*.  A ``StepProfiler`` attached to a recording ``Telemetry``
(``Telemetry(profiler=StepProfiler())``) makes the Server/Engine do
three extra host-side things per jitted program:

1. **Cost the program once.**  On the first dispatch the program is
   AOT-lowered and compiled (``jitted.lower(*args).compile()``) and its
   per-call FLOP / HBM-byte budget extracted via
   ``utils/hlo.compiled_cost`` — XLA's ``cost_analysis()`` cross-checked
   against the trip-count-corrected HLO walk, the same cost model the
   launch dry-run manifests use.  This is one extra compile per program
   per profiled serve (a profiling cost, never paid by an unprofiled
   serve).
2. **Annotate the dispatch.**  Each dispatch runs inside a
   ``jax.profiler.TraceAnnotation("repro/<program>")`` scope, so a
   device timeline captured with ``jax.profiler.trace(...)`` shows the
   engine-step structure by name.
3. **Attribute the measured time.**  The wall time the serving code
   already measures (host-side, behind the existing
   ``block_until_ready`` fences — the jitted programs are byte-identical
   with the profiler on or off) is divided into the static cost:
   achieved FLOP/s, achieved HBM GB/s, and the achieved-vs-roofline
   fraction ``max(flops/peak, bytes/bw) / measured`` land in the
   ``profile_*`` gauge families, labelled per
   (program, kv_bits, matmul_mode).  Measured time is the fastest-half
   mean of the per-program ``profile_step_seconds`` histogram
   (benchmarks/common.timed_robust's estimator: noise only ever adds
   time).

Hardware peaks come from the ``device_kind`` table in ``launch/mesh.py``
(``device_peaks``); a device that is not in the table is an error, so a
roofline fraction always names the chip it was measured on.  Pass
``peak_flops=`` / ``hbm_bw=`` explicitly anywhere else (CPU tests do).

Usage (docs/observability.md#step-profiler):

    tel = Telemetry(profiler=StepProfiler())
    srv = Server(params, cfg, ..., telemetry=tel)
    ...serve...
    print(tel.profiler.format_summary())
    # or: launch/serve.py --profile --metrics-out metrics.prom
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

__all__ = ["StepProfiler", "ProgramCost", "null_annotation"]

_NULL_CTX = contextlib.nullcontext()


def null_annotation(name: str):
    """The no-profiler stand-in for ``session.annotation``: a shared
    reusable null context, so dispatch sites can unconditionally write
    ``with self._annot("decode_step"):``."""
    return _NULL_CTX


@dataclass
class ProgramCost:
    """Static per-call cost of one compiled program (utils/hlo.py)."""

    name: str
    flops: float
    hbm_bytes: float
    collective_bytes: float
    xla_flops: float
    xla_bytes_accessed: float
    compile_s: float

    def roofline_seconds(self, peak_flops: float, hbm_bw: float) -> float:
        """The roofline-predicted step time: the binding term of the
        compute/memory roofline at the configured peaks."""
        return max(self.flops / peak_flops, self.hbm_bytes / hbm_bw)


class _Session:
    """One serving instance's profiler view: a private cost cache plus
    the label set (kv_bits, matmul_mode, ...) its gauges carry.  Made by
    ``StepProfiler.session``; the Server/Engine hold one each so two
    instances sharing a profiler never mix their programs up."""

    def __init__(self, profiler: "StepProfiler", registry, labels: dict):
        self.profiler = profiler
        self.registry = registry
        self.labels = {k: str(v) for k, v in labels.items()}
        self.costs: dict[str, ProgramCost] = {}

    def annotation(self, name: str):
        """jax.profiler trace annotation for one dispatch — names the
        program on any device timeline being captured."""
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(f"repro/{name}")

    def ensure_costed(self, name, jitted, args) -> ProgramCost:
        """Cost `name` once: AOT lower+compile `jitted` at `args` and
        record its analytic FLOP/byte budget (static gauges included).
        Idempotent; a failed costing raises — a profiled serve never
        carries on unattributed."""
        if name in self.costs:
            return self.costs[name]
        from repro.utils.hlo import compiled_cost

        t0 = time.perf_counter()
        compiled = jitted.lower(*args).compile()
        cost = compiled_cost(compiled)
        pc = ProgramCost(name=name, compile_s=time.perf_counter() - t0,
                         **cost)
        self.costs[name] = pc
        lb = dict(self.labels, program=name)
        self.registry.gauge("profile_program_flops", **lb).set(pc.flops)
        self.registry.gauge("profile_program_hbm_bytes", **lb).set(
            pc.hbm_bytes)
        return pc

    def observe(self, name: str, dt: float) -> None:
        """Fold one measured dispatch (seconds, host fence to fence)
        into the per-program histogram and refresh the attribution
        gauges from the fastest-half mean so far."""
        lb = dict(self.labels, program=name)
        h = self.registry.histogram("profile_step_seconds", **lb)
        h.observe(dt)
        pc = self.costs.get(name)
        if pc is None:
            return
        t = h.fastest_mean(0.5)
        if not t > 0.0:
            return
        p = self.profiler
        self.registry.gauge("profile_achieved_flops_per_s", **lb).set(
            pc.flops / t)
        self.registry.gauge("profile_achieved_hbm_gbps", **lb).set(
            pc.hbm_bytes / t / 1e9)
        self.registry.gauge("profile_roofline_frac", **lb).set(
            pc.roofline_seconds(p.peak_flops, p.hbm_bw) / t)

    def summary(self) -> list[dict]:
        """One row per costed program with samples: measured fastest-half
        time and the attributed throughput/roofline numbers."""
        rows = []
        for name, pc in sorted(self.costs.items()):
            if pc is None:
                continue
            lb = dict(self.labels, program=name)
            h = self.registry.histogram("profile_step_seconds", **lb)
            if not h.count:
                continue
            t = h.fastest_mean(0.5)
            p = self.profiler
            rows.append({
                "program": name, **self.labels, "calls": h.count,
                "fastest_half_ms": t * 1e3,
                "flops": pc.flops, "hbm_bytes": pc.hbm_bytes,
                "achieved_gflops_s": pc.flops / t / 1e9,
                "achieved_hbm_gbps": pc.hbm_bytes / t / 1e9,
                "roofline_frac": pc.roofline_seconds(p.peak_flops,
                                                     p.hbm_bw) / t,
                "compile_s": pc.compile_s,
            })
        return rows


class StepProfiler:
    """Roofline-attribution profiler for the serving stack.  Holds the
    hardware peaks and the sessions; all state is host-side."""

    def __init__(self, *, peak_flops: float | None = None,
                 hbm_bw: float | None = None):
        if peak_flops is None or hbm_bw is None:
            from repro.launch.mesh import device_peaks

            peaks = device_peaks()
            peak_flops = peak_flops or peaks["flops_bf16"]
            hbm_bw = hbm_bw or peaks["hbm_bw"]
        self.peak_flops = float(peak_flops)
        self.hbm_bw = float(hbm_bw)
        self.sessions: list[_Session] = []

    def session(self, registry, **labels) -> _Session:
        s = _Session(self, registry, labels)
        self.sessions.append(s)
        return s

    def summary(self) -> list[dict]:
        return [row for s in self.sessions for row in s.summary()]

    def format_summary(self) -> str:
        rows = self.summary()
        if not rows:
            return "profiler: no costed programs observed"
        lines = ["profiler (fastest-half means; roofline at "
                 f"{self.peak_flops / 1e12:.0f} TFLOP/s, "
                 f"{self.hbm_bw / 1e9:.0f} GB/s):"]
        for r in rows:
            lines.append(
                f"  {r['program']:<22s} kv{r['kv_bits']:>2s}/"
                f"{r['matmul_mode']:<14s} {r['calls']:>5d} calls  "
                f"{r['fastest_half_ms']:8.3f} ms  "
                f"{r['achieved_gflops_s']:8.2f} GFLOP/s  "
                f"{r['achieved_hbm_gbps']:7.2f} GB/s  "
                f"roofline {r['roofline_frac']:.2e}"
                if "kv_bits" in r and "matmul_mode" in r else
                f"  {r['program']:<22s} {r['calls']:>5d} calls  "
                f"{r['fastest_half_ms']:8.3f} ms  "
                f"roofline {r['roofline_frac']:.2e}")
        return "\n".join(lines)
