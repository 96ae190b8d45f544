"""Serving telemetry subsystem (serving/telemetry.py + serving/trace.py).

Four layers of contract:

(a) the metric types themselves — exact percentile extraction against
    numpy, cumulative Prometheus buckets, counter/gauge semantics;
(b) scheduler/pool queue accounting observed THROUGH telemetry — queue
    depth and running gauges track submit/bind/retire exactly, EOS
    retirement frees occupancy;
(c) the zero-overhead guarantee — the default NOOP recorder costs an
    attribute check, and (the acceptance criterion) greedy serves are
    TOKEN-IDENTICAL with a recording Telemetry vs the no-op: all timing
    is host-side at dispatch boundaries, never inside jitted bodies;
(d) the trace schema — a live serve's event log validates, and
    malformed/ill-ordered logs are rejected with the offending index.

Quantization health riders: kv_bytes() logical/compression accounting,
the load-time per-matrix bits+qerr snapshot, and the append-quantize
probe (kv_probe_every) measuring real K/V roundtrip error without
changing tokens.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import QuantConfig
from repro.configs.registry import get_arch
from repro.data import synthetic
from repro.models import lm
from repro.precision import PrecisionPlan
from repro.serving import NOOP, Engine, Server, Telemetry, validate_events
from repro.serving.kvcache import SlotKVCache
from repro.serving.scheduler import Request, Scheduler
from repro.serving.telemetry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    record_quant_health,
)

CFG = get_arch("tiny-160k")


@pytest.fixture(scope="module")
def params():
    return lm.init_params(jax.random.PRNGKey(0), CFG)


def _prompts(batch, length, seed=1):
    return np.asarray(
        synthetic.ZipfMarkov(CFG.vocab_size).sample(
            jax.random.PRNGKey(seed), batch, length
        )
    )


# -------------------------------------------------------------------------
# (a) metric types
# -------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(7)
    xs = rng.lognormal(mean=-4.0, sigma=1.5, size=173)
    h = Histogram()
    for x in xs:
        h.observe(float(x))
    for p in (0, 10, 25, 50, 90, 99, 100):
        assert h.percentile(p) == pytest.approx(
            float(np.percentile(xs, p, method="linear")), rel=1e-12
        ), p
    assert h.mean == pytest.approx(float(xs.mean()))
    assert h.count == len(xs)
    # fastest half == numpy mean of the sorted lower half
    keep = len(xs) // 2
    assert h.fastest_mean(0.5) == pytest.approx(
        float(np.sort(xs)[:keep].mean())
    )


def test_histogram_buckets_and_edge_cases():
    h = Histogram(buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    # bisect_left: a sample exactly on a bound lands in that bound's
    # bucket (le semantics); the +Inf bucket catches the overflow
    assert h.bucket_counts == [2, 1, 1, 1]
    assert sum(h.bucket_counts) == h.count
    assert math.isnan(Histogram().percentile(50))
    with pytest.raises(ValueError):
        h.percentile(101)
    capped = Histogram(buckets=(1.0,), max_samples=3)
    for v in (5.0, 1.0, 3.0, 4.0):
        capped.observe(v)
    # drops the smallest: tails (the SLA signal) survive the cap
    assert capped._samples == [3.0, 4.0, 5.0]
    assert capped.count == 4  # aggregates never drop


def test_counter_and_gauge_semantics():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge()
    g.set(5)
    g.dec(3)
    g.inc(1)
    assert g.value == 3.0
    assert g.max == 5.0  # high-water survives the dips


def test_registry_labels_types_and_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("serve_tokens_total").inc(7)
    reg.gauge("kv_pool_bytes", kind="packed").set(100)
    reg.gauge("kv_pool_bytes", kind="logical").set(400)
    h = reg.histogram("serve_ttft_seconds")
    h.observe(0.003)
    h.observe(0.2)
    with pytest.raises(TypeError):
        reg.gauge("serve_tokens_total")  # declared + registered as counter
    with pytest.raises(TypeError):
        reg.counter("serve_ttft_seconds")  # declared as histogram
    txt = reg.prometheus_text()
    assert "# TYPE serve_tokens_total counter" in txt
    assert "serve_tokens_total 7" in txt
    assert 'kv_pool_bytes{kind="logical"} 400' in txt
    assert 'serve_ttft_seconds_bucket{le="+Inf"} 2' in txt
    assert "serve_ttft_seconds_count 2" in txt
    # cumulative le counts are monotone non-decreasing
    cum = [int(l.rsplit(" ", 1)[1]) for l in txt.splitlines()
           if l.startswith("serve_ttft_seconds_bucket")]
    assert cum == sorted(cum) and cum[-1] == 2
    d = reg.as_dict()
    assert d["serve_ttft_seconds"][""]["count"] == 2
    assert d["kv_pool_bytes"]["kind=packed"]["value"] == 100


# -------------------------------------------------------------------------
# (b) queue accounting through telemetry
# -------------------------------------------------------------------------

def test_scheduler_queue_gauges_track_lifecycle():
    tel = Telemetry()
    sch = Scheduler(telemetry=tel)
    depth = tel.registry.gauge("serve_queue_depth")
    running = tel.registry.gauge("serve_requests_running")
    reqs = [sch.submit(Request(prompt=[1, 2], max_new=2,
                               arrival_time=float(i)))
            for i in range(3)]
    assert depth.value == 3 and running.value == 0
    seen_depths = [depth.value]
    for slot, r in enumerate(reqs):
        sch.bind(r, slot, now=float(slot) + 1.0)
        seen_depths.append(depth.value)
    # monotone drain: each bind pops exactly one queued request
    assert seen_depths == [3, 2, 1, 0]
    assert running.value == 3 and running.max == 3
    for slot in range(3):
        sch.retire(slot, now=10.0)
    assert running.value == 0 and depth.value == 0
    assert tel.registry.counter("serve_requests_submitted_total").value == 3
    assert tel.registry.counter("serve_requests_retired_total").value == 3
    waits = tel.registry.histogram("serve_queue_wait_steps")
    assert waits.count == 3
    assert waits.percentile(100) == pytest.approx(1.0)  # bound - arrival


def test_eos_retirement_frees_occupancy(params):
    """Mid-stream EOS retirement must decrement the running/slot gauges
    (not just the end-of-serve drain)."""
    prompts = [_prompts(1, L, seed=30 + i)[0]
               for i, L in enumerate([6, 9, 7, 8])]
    dry = Server(params, CFG, num_slots=2, max_seq_len=24)
    dry_ids = [dry.submit(p, 8) for p in prompts]
    eos_id = dry.run_until_drained()[dry_ids[0]][1]  # 2nd token of req 0

    tel = Telemetry()
    srv = Server(params, CFG, num_slots=2, max_seq_len=24, eos_id=eos_id,
                 telemetry=tel)
    ids = [srv.submit(p, 8, arrival_time=1.0 * i)
           for i, p in enumerate(prompts)]
    res = srv.run_until_drained()
    reasons = [ev["attrs"]["reason"] for ev in tel.tracer.events
               if ev["name"] == "retire"]
    assert "eos" in reasons, reasons  # the dry-run token really fired
    assert len(res[ids[0]]) < 8  # retired early
    running = tel.registry.gauge("serve_requests_running")
    slots = tel.registry.gauge("serve_slots_active")
    assert running.value == 0 and slots.value == 0  # occupancy released
    assert running.max <= 2 and slots.max <= 2
    assert tel.registry.counter("serve_requests_retired_total").value == 4


# -------------------------------------------------------------------------
# (c) zero overhead + the golden token-identity acceptance test
# -------------------------------------------------------------------------

def test_noop_recorder_is_free():
    assert NOOP.enabled is False
    t0 = time.perf_counter()
    for _ in range(20_000):
        NOOP.inc("serve_tokens_total")
        NOOP.observe("serve_ttft_seconds", 0.1)
        NOOP.span("decode_step", 0.0, 1.0)
    dt = time.perf_counter() - t0
    assert dt < 0.5, f"no-op recorder cost {dt:.3f}s for 60k calls"
    # the default server wires NOOP: no registry is ever materialized
    assert NOOP.registry is None and NOOP.tracer is None


def test_noop_scope_is_shared_and_records_nothing():
    """NOOP.scope hands back one shared null context whatever it is
    asked for: no annotation, no clock read, no span."""
    s = NOOP.scope("step", step=3)
    assert s is NOOP.scope("decode_step", n_active=2) is NOOP.scope("emit")
    with s as sc:
        assert sc is None
    with s:     # reusable, and nested in itself
        with s:
            pass
    assert NOOP.tracer is None


def test_scope_records_a_span_with_body_attrs():
    """A live scope writes one Tracer span over its body on Telemetry.now,
    with the attrs given at open and those the body added."""
    tel = Telemetry()
    with tel.scope("step", step=7, a=1) as sc:
        t_in = tel.now()
        sc.attrs["produced"] = 4
    assert sc.t0 <= t_in <= sc.t1
    (ev,) = tel.tracer.events
    assert (ev["kind"], ev["name"], ev["step"], ev["request_id"]) \
        == ("span", "step", 7, None)
    assert (ev["t0"], ev["t1"]) == (sc.t0, sc.t1)
    assert ev["attrs"] == {"a": 1, "produced": 4}
    # a body that raises records no span
    with pytest.raises(RuntimeError):
        with tel.scope("emit"):
            raise RuntimeError("boom")
    assert len(tel.tracer.events) == 1


def test_greedy_tokens_identical_with_telemetry_on_vs_off(params):
    """THE acceptance criterion: a recording Telemetry must not change
    greedy outputs — all instrumentation is host-side, outside the
    jitted bodies."""
    lens, budgets = [12, 7, 10, 5], [8, 4, 6, 3]
    prompts = [_prompts(1, L, seed=40 + i)[0] for i, L in enumerate(lens)]

    def serve(telemetry):
        srv = Server(params, CFG, num_slots=2, max_seq_len=20,
                     telemetry=telemetry)
        ids = [srv.submit(p, m, arrival_time=1.5 * i)
               for i, (p, m) in enumerate(zip(prompts, budgets))]
        res = srv.run_until_drained()
        return [res[r] for r in ids]

    tel = Telemetry()
    assert serve(tel) == serve(NOOP)
    # and the recording run actually recorded
    d = tel.registry.as_dict()
    assert d["serve_ttft_seconds"][""]["count"] == len(lens)
    assert d["serve_tokens_total"][""] == sum(budgets)
    assert d["serve_batch_fill"][""]["count"] > 0
    assert d["serve_prefill_pad_frac"][""]["count"] == len(lens)

    # the paged kv4 server, whose step runs under every scope
    cfg4 = CFG.with_kv_quant(4)
    p4 = lm.init_params(jax.random.PRNGKey(0), cfg4)

    def serve_paged(telemetry):
        srv = Server(p4, cfg4, num_slots=2, max_seq_len=32, paged=True,
                     page_size=8, telemetry=telemetry)
        ids = [srv.submit(p, m, arrival_time=1.5 * i)
               for i, (p, m) in enumerate(zip(prompts, budgets))]
        res = srv.run_until_drained()
        return [res[r] for r in ids]

    tel_p = Telemetry()
    assert serve_paged(tel_p) == serve_paged(NOOP)
    assert {e["name"] for e in tel_p.tracer.events} >= {
        "step", "admit", "decode_args", "decode_step", "emit"}

    # static Engine: same contract
    ep = jnp.asarray(_prompts(3, 9, seed=50))
    tel_e = Telemetry()
    out_tel = Engine(params, CFG, max_seq_len=16,
                     telemetry=tel_e).generate(ep, 6)
    out_off = Engine(params, CFG, max_seq_len=16).generate(ep, 6)
    assert np.array_equal(np.asarray(out_tel), np.asarray(out_off))
    assert tel_e.registry.as_dict()["serve_decode_steps_total"][""] == 5
    validate_events(tel_e.tracer.events)


# -------------------------------------------------------------------------
# (d) trace schema
# -------------------------------------------------------------------------

def test_live_trace_validates_and_counts(params, tmp_path):
    from repro.serving import validate_jsonl

    tel = Telemetry()
    srv = Server(params, CFG, num_slots=2, max_seq_len=20, telemetry=tel)
    prompts = [_prompts(1, L, seed=60 + i)[0] for i, L in enumerate([6, 9, 7])]
    ids = [srv.submit(p, 4, arrival_time=0.5 * i)
           for i, p in enumerate(prompts)]
    srv.run_until_drained()
    stats = validate_events(tel.tracer.events)
    assert stats["requests"] == 3
    assert stats["decode_steps"] > 0
    # per request: submit, queue_wait span, prefill span, first+last
    # token events, retire
    names = [e["name"] for e in tel.tracer.events
             if e["request_id"] == ids[0]]
    assert names[0] == "submit" and names[-1] == "retire"
    assert "prefill" in names and "queue_wait" in names
    # round-trips through JSONL
    p = tel.tracer.write_jsonl(tmp_path / "trace.jsonl")
    assert validate_jsonl(p)["events"] == stats["events"]


def test_live_paged_trace_scopes_nest(params):
    """A paged server's trace validates, every scope sits inside its
    step, the step's device-bound children leave it a non-negative host
    time, and each decode step carries its KV read counts."""
    from repro.serving import to_chrome_trace

    tel = Telemetry()
    srv = Server(params, CFG, num_slots=2, max_seq_len=32, paged=True,
                 page_size=8, telemetry=tel)
    for i, L in enumerate([6, 9, 7]):
        srv.submit(_prompts(1, L, seed=80 + i)[0], 4, arrival_time=0.5 * i)
    srv.run_until_drained()
    events = tel.tracer.events
    assert validate_events(events)["decode_steps"] > 0
    spans = [e for e in events if e["kind"] == "span"]
    steps = [e for e in spans if e["name"] == "step"]
    assert len(steps) == srv.steps
    children = {"admit", "decode_args", "decode_step", "emit", "prefill"}
    for e in spans:
        if e["name"] not in children:
            continue
        owner = [s for s in steps if s["t0"] <= e["t0"] and e["t1"] <= s["t1"]]
        assert len(owner) == 1, f"{e['name']} lies inside no single step"
        if e["name"] != "prefill":
            assert owner[0]["step"] == e["step"]
    for s in steps:
        inner = [e for e in spans if e["name"] in ("decode_step", "prefill")
                 and s["t0"] <= e["t0"] and e["t1"] <= s["t1"]]
        assert s["t1"] - s["t0"] - sum(e["t1"] - e["t0"] for e in inner) >= 0
    for e in spans:
        if e["name"] == "decode_step":
            a = e["attrs"]
            assert a["kv_read"] == 2 * 32    # num_slots x pages x page_size
            assert 0 < a["kv_live"] <= a["kv_read"]
    assert sum(s["attrs"]["produced"] for s in steps) == 3 * 4
    # the server-step spans go on the engine track
    engine = {ev["name"] for ev in to_chrome_trace(events)["traceEvents"]
              if ev.get("ph") == "X" and ev["pid"] == 1}
    assert engine == {"step", "admit", "decode_args", "decode_step", "emit"}


def _ok_events():
    t = Telemetry()
    t.event("submit", 0.0, request_id=1, step=0)
    t.span("queue_wait", 0.0, 0.1, request_id=1, step=0, steps=0.0)
    t.span("prefill", 0.1, 0.2, request_id=1, step=0, slot=0,
           prompt_len=4, padded_len=8)
    t.event("token", 0.2, request_id=1, step=0, first=True)
    t.span("decode_step", 0.2, 0.3, step=1, n_active=1, batch_fill=0.5)
    t.event("retire", 0.3, request_id=1, step=2, n_tokens=2, reason="budget")
    return t.tracer.events


def test_trace_validator_accepts_and_rejects():
    ok = _ok_events()
    assert validate_events(ok)["requests"] == 1

    def corrupt(mutate, match):
        evs = [dict(e, attrs=dict(e["attrs"])) for e in _ok_events()]
        mutate(evs)
        with pytest.raises(ValueError, match=match):
            validate_events(evs)

    corrupt(lambda e: e[0].pop("t0"), "missing keys")
    corrupt(lambda e: e[0].update(v=99), "schema version")
    corrupt(lambda e: e[1].update(t1=-1.0), "ends before it starts")
    corrupt(lambda e: e[4].update(request_id=1), "must be null")
    corrupt(lambda e: e[4]["attrs"].pop("n_active"), "n_active")
    corrupt(lambda e: e[0].update(name="banana"), "unknown event name")
    corrupt(lambda e: e.insert(0, e[5].copy()), "retire before submit")
    corrupt(lambda e: e.append(dict(e[0])), "duplicate submit")
    corrupt(lambda e: e.append(dict(e[3], t0=9.9)), "after retire")
    # a retired request must have prefilled
    corrupt(lambda e: e.pop(2), "without a prefill")


def test_trace_validator_server_step_spans_are_batched():
    """step / admit / decode_args / emit validate as batched spans with a
    null request_id, like decode_step, and are refused with one."""
    t = Telemetry()
    t.span("step", 0.0, 1.0, step=0, produced=0)
    for name in ("admit", "decode_args", "emit"):
        t.span(name, 0.1, 0.2, step=0)
    assert validate_events(t.tracer.events)["spans"] == 4
    for name in ("step", "admit", "decode_args", "emit"):
        bad = Telemetry()
        bad.span(name, 0.0, 1.0, request_id=1, step=0)
        with pytest.raises(ValueError, match=f"{name} is batched"):
            validate_events(bad.tracer.events)


def _ok_sla_events():
    """A full v2 preemption lifecycle: chunked admission, preempt at
    step 3, spill, restore into a new slot, resume, retire."""
    t = Telemetry()
    t.event("submit", 0.0, request_id=1, step=0)
    t.span("queue_wait", 0.0, 0.1, request_id=1, step=0, steps=0.0)
    t.span("prefill_chunk", 0.1, 0.15, request_id=1, step=0, slot=0,
           chunk=0, chunk_start=0, chunk_len=8)
    t.span("prefill_chunk", 0.15, 0.2, request_id=1, step=1, slot=0,
           chunk=1, chunk_start=8, chunk_len=8)
    t.span("prefill", 0.1, 0.25, request_id=1, step=1, slot=0,
           prompt_len=12, padded_len=16, chunks=2)
    t.event("token", 0.25, request_id=1, step=1, first=True)
    t.span("decode_step", 0.25, 0.3, step=2, n_active=1, batch_fill=0.5)
    t.event("preempt", 0.3, request_id=1, step=3, slot=0, by=2, n_tokens=2)
    t.span("spill", 0.3, 0.32, request_id=1, step=3, slot=0,
           bytes_packed=256, bytes_logical=1024)
    t.span("restore", 0.4, 0.42, request_id=1, step=5, slot=1,
           bytes_packed=256)
    t.event("token", 0.45, request_id=1, step=6)
    t.event("retire", 0.5, request_id=1, step=7, n_tokens=4, reason="budget")
    return t.tracer.events


def test_trace_validator_v2_preemption_lifecycle():
    """The v2 counting rules: preempt/spill/restore must nest correctly
    and a preempted request emits nothing until restored."""
    assert validate_events(_ok_sla_events())["requests"] == 1

    def corrupt(mutate, match):
        evs = [dict(e, attrs=dict(e["attrs"])) for e in _ok_sla_events()]
        mutate(evs)
        with pytest.raises(ValueError, match=match):
            validate_events(evs)

    # event indices: 0 submit, 1 queue_wait, 2-3 prefill_chunk,
    # 4 prefill, 5 token, 6 decode_step, 7 preempt, 8 spill,
    # 9 restore, 10 token, 11 retire
    corrupt(lambda e: e[2]["attrs"].pop("chunk"), "chunk")
    corrupt(lambda e: e.insert(8, e.pop(9)), "restore before spill")
    corrupt(lambda e: e.insert(9, e.pop(10)), "token while preempted")
    corrupt(lambda e: e.insert(10, dict(e[8])), "spill without a preempt")
    corrupt(lambda e: e.insert(9, dict(e[7])), "nested preempt")
    corrupt(lambda e: e.__delitem__(slice(9, 11)), "retire while preempted")
    corrupt(lambda e: e.insert(2, e.pop(7)), "preempt before prefill")


# -------------------------------------------------------------------------
# quantization health riders
# -------------------------------------------------------------------------

def test_kv_bytes_logical_and_compression():
    pool16 = SlotKVCache(CFG, num_slots=2, cache_len=12)
    b16 = pool16.kv_bytes()
    assert b16["logical"] == b16["total"]  # bf16 cache stores bf16
    assert b16["compression"] == pytest.approx(1.0)
    tel = Telemetry()
    pool4 = SlotKVCache(CFG.with_kv_quant(4), num_slots=2, cache_len=12,
                        telemetry=tel)
    b4 = pool4.kv_bytes()
    assert b4["logical"] == b16["logical"]  # same logical tensor
    assert b4["compression"] == pytest.approx(b4["logical"] / b4["total"])
    assert b4["compression"] > 3.0  # the paper's >=3x bandwidth argument
    d = tel.registry.as_dict()
    assert d["kv_pool_bytes"]["kind=logical"]["value"] == b4["logical"]
    assert d["kv_pool_compression_x"][""]["value"] == \
        pytest.approx(b4["compression"])


def test_quant_health_snapshot_with_plan(params):
    from repro.models.quantize import quantizable_units

    units = sorted(quantizable_units(params, CFG))
    base = QuantConfig(bits=4, dtype="float", block_size=64)
    plan = PrecisionPlan(arch=CFG.name, default=dataclasses.asdict(base),
                         assignments={units[0]: {"bits": 8},
                                      units[1]: {"bits": 16}})
    tel = Telemetry()
    out = record_quant_health(tel, params, CFG, plan=plan)
    assert set(out) == set(units)
    bits = {k: v["value"]
            for k, v in tel.registry.as_dict()["quant_unit_bits"].items()}
    assert bits[f"unit={units[0]}"] > 8.0  # 8-bit codes + scale overhead
    assert bits[f"unit={units[1]}"] == 16.0
    qerr = tel.registry.as_dict()["quant_unit_qerr_rms"]
    assert qerr[f"unit={units[1]}"]["value"] == 0.0  # kept fp16: no error
    # 4-bit default: real but bounded blockwise error
    assert 0.0 < qerr[f"unit={units[2]}"]["value"] < 0.5
    assert record_quant_health(NOOP, params, CFG, plan=plan) == {}


@pytest.mark.slow
def test_kv_probe_measures_error_without_changing_tokens(params):
    cfg4 = CFG.with_kv_quant(4)
    prompts = [_prompts(1, L, seed=70 + i)[0] for i, L in enumerate([6, 9])]
    tel = Telemetry(kv_probe_every=1)
    srv = Server(params, cfg4, num_slots=2, max_seq_len=24, telemetry=tel)
    ids = [srv.submit(p, 4, arrival_time=0.5 * i)
           for i, p in enumerate(prompts)]
    res = srv.run_until_drained()
    d = tel.registry.as_dict()
    rms = d["kv_append_qerr_rms"][""]["value"]
    assert 0.0 < rms < 1.0  # 4-bit roundtrip: real, sub-catastrophic
    assert d["kv_append_qerr_max"][""]["value"] >= rms
    assert d["kv_probe_rows_total"][""] >= sum(len(p) for p in prompts)

    off = Server(params, cfg4, num_slots=2, max_seq_len=24)
    ids_off = [off.submit(p, 4, arrival_time=0.5 * i)
               for i, p in enumerate(prompts)]
    res_off = off.run_until_drained()
    assert [res[i] for i in ids] == [res_off[i] for i in ids_off]


@pytest.mark.slow
def test_launcher_writes_validating_artifacts(tmp_path, capsys):
    """launch/serve.py --metrics-out/--trace-out end to end: the local
    twin of the CI telemetry smoke."""
    from repro.launch import serve as serve_mod
    from repro.serving import validate_jsonl

    m, t = tmp_path / "metrics.prom", tmp_path / "trace.jsonl"
    serve_mod.main(["--arch", "tiny-160k", "--kv-bits", "4",
                    "--kv-probe-every", "2", "--num-requests", "3",
                    "--num-slots", "2", "--max-new", "4",
                    "--metrics-out", str(m), "--trace-out", str(t)])
    out = capsys.readouterr().out
    assert "telemetry: ttft p50" in out
    stats = validate_jsonl(t)
    assert stats["requests"] == 3
    txt = m.read_text()
    assert "# TYPE serve_ttft_seconds histogram" in txt
    assert "kv_append_qerr_rms" in txt
    assert "kv_pool_compression_x" in txt
