"""Performance observatory (profiler + Chrome traces + bench ledger).

Five layers of contract:

(a) Prometheus exposition hardening — # HELP/# TYPE lines from
    METRIC_FAMILIES and label-value escaping that survives adversarial
    values (backslash, quote, newline);
(b) the step profiler (serving/profiler.py) — AOT costing of a jitted
    program, idempotent/failure-sticky cost cache, roofline math, the
    profile_* gauge families landing in the exposition, and THE
    acceptance criterion: greedy serves are token-identical with the
    profiler on vs off (all attribution is host-side at the existing
    dispatch fences);
(c) the trace toolchain — flight-recorder truncation refuses validation
    with a clear diagnostic, the CLI exits 0/1, and the Chrome
    trace-event export is schema-valid with preempt->restore flow
    arrows on a real preempting serve;
(d) the bench regression ledger (benchmarks/ledger.py) — record schema
    round-trip, malformed records rejected, the committed repo-root
    baselines validate;
(e) scripts/bench_diff.py — clean against the real baselines, nonzero
    on a synthetically injected virtual-series regression, wall series
    report-only.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.data import synthetic
from repro.models import lm
from repro.serving import (
    NOOP,
    Engine,
    MetricsRegistry,
    Server,
    StepProfiler,
    Telemetry,
    to_chrome_trace,
    trace_stats,
    validate_events,
)
from repro.serving.profiler import ProgramCost, null_annotation

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))  # benchmarks/ is a repo-root package

from benchmarks import ledger  # noqa: E402

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


def _bench_diff():
    spec = importlib.util.spec_from_file_location(
        "bench_diff", ROOT / "scripts" / "bench_diff.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -------------------------------------------------------------------------
# (a) Prometheus exposition: HELP/TYPE + label escaping
# -------------------------------------------------------------------------

def test_prometheus_help_type_and_label_escaping():
    reg = MetricsRegistry()
    evil = 'quo"te\\back\nnewline'
    reg.gauge("profile_program_flops", program=evil, kv_bits="4").set(3.0)
    txt = reg.prometheus_text()
    assert "# HELP profile_program_flops " in txt
    assert "# TYPE profile_program_flops gauge" in txt
    # the adversarial value appears fully escaped, never raw
    escaped = evil.replace("\\", r"\\").replace('"', r"\"") \
                  .replace("\n", r"\n")
    assert f'program="{escaped}"' in txt
    # a raw newline inside a label value would split the sample line in
    # two; every non-comment line must carry a value
    for line in txt.splitlines():
        assert line.startswith("#") or len(line.split()) >= 2, line


def test_prometheus_histogram_families_keep_help():
    reg = MetricsRegistry()
    reg.histogram("profile_step_seconds", program="decode_step").observe(0.01)
    txt = reg.prometheus_text()
    assert "# TYPE profile_step_seconds histogram" in txt
    assert 'profile_step_seconds_bucket{' in txt


# -------------------------------------------------------------------------
# (b) step profiler
# -------------------------------------------------------------------------

def test_profiler_costs_attributes_and_exports():
    prof = StepProfiler(peak_flops=1e12, hbm_bw=1e11)
    reg = MetricsRegistry()
    sess = prof.session(reg, kv_bits="16", matmul_mode="auto")
    f = jax.jit(lambda a, b: a @ b)
    args = (jnp.ones((64, 64), jnp.float32), jnp.ones((64, 64), jnp.float32))
    pc = sess.ensure_costed("dot[64]", f, args)
    assert pc is not None
    assert pc.flops >= 2 * 64 * 64 * 64  # at least the dot itself
    assert pc.hbm_bytes > 0 and pc.compile_s > 0
    # idempotent: the cost cache returns the same object, no recompile
    assert sess.ensure_costed("dot[64]", f, args) is pc

    with sess.annotation("dot[64]"):
        jax.block_until_ready(f(*args))
    sess.observe("dot[64]", 1e-3)
    txt = reg.prometheus_text()
    for fam in ("profile_program_flops", "profile_program_hbm_bytes",
                "profile_achieved_flops_per_s", "profile_achieved_hbm_gbps",
                "profile_roofline_frac"):
        assert fam in txt, fam
    assert 'program="dot[64]"' in txt
    frac = reg.gauge("profile_roofline_frac", kv_bits="16",
                     matmul_mode="auto", program="dot[64]").value
    assert frac == pytest.approx(pc.roofline_seconds(1e12, 1e11) / 1e-3)

    rows = prof.summary()
    assert len(rows) == 1 and rows[0]["program"] == "dot[64]"
    assert rows[0]["calls"] == 1
    assert "dot[64]" in prof.format_summary()


def test_profiler_roofline_math_and_null_annotation():
    pc = ProgramCost(name="x", flops=2e9, hbm_bytes=1e8,
                     collective_bytes=0.0, xla_flops=0.0,
                     xla_bytes_accessed=0.0, compile_s=0.0)
    # compute-bound at these peaks: 2e9/1e12 = 2ms > 1e8/1e12 s
    assert pc.roofline_seconds(1e12, 1e12) == pytest.approx(2e-3)
    # memory-bound when bandwidth is the binding term
    assert pc.roofline_seconds(1e15, 1e9) == pytest.approx(0.1)
    with null_annotation("anything"):
        pass
    assert NOOP.profiler is None


def test_profiler_peaks_come_from_the_device_table():
    """Peaks are looked up by device_kind; a device missing from the
    table (the CPU here) is an error, never a borrowed v5e default."""
    from repro.launch.mesh import device_peaks

    v5e = device_peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        StepProfiler()
    prof = StepProfiler(peak_flops=1e12, hbm_bw=1e11)
    assert (prof.peak_flops, prof.hbm_bw) == (1e12, 1e11)


def test_profiler_failure_is_sticky_and_warns():
    prof = StepProfiler(peak_flops=1e12, hbm_bw=1e11)
    sess = prof.session(MetricsRegistry(), kv_bits="16", matmul_mode="auto")

    class Boom:
        def lower(self, *a):
            raise RuntimeError("no lowering today")

    # a profiled serve never carries on unattributed: the failure raises,
    # every time (nothing is cached for the program)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="no lowering today"):
            sess.ensure_costed("bad", Boom(), ())
    assert "bad" not in sess.costs
    sess.observe("bad", 1e-3)  # uncosted observe is histogram-only
    assert sess.summary() == []


def test_tokens_identical_with_profiler_on_vs_off(params):
    """THE acceptance criterion: attaching the profiler must not change
    greedy outputs — costing is AOT on a separate executable, timing is
    host-side behind the existing fences."""
    lens, budgets = [10, 6, 8], [6, 4, 5]
    prompts = [_prompts(1, L, seed=70 + i)[0] for i, L in enumerate(lens)]

    def serve(telemetry):
        srv = Server(params, CFG, num_slots=2, max_seq_len=18,
                     telemetry=telemetry)
        ids = [srv.submit(p, m, arrival_time=1.0 * i)
               for i, (p, m) in enumerate(zip(prompts, budgets))]
        res = srv.run_until_drained()
        return [res[r] for r in ids]

    # explicit peaks: the CPU is not in launch/mesh.DEVICE_PEAKS
    tel = Telemetry(profiler=StepProfiler(peak_flops=1e12, hbm_bw=1e11))
    assert serve(tel) == serve(NOOP)
    # the profiled run costed + attributed the real serving programs
    rows = tel.profiler.summary()
    names = {r["program"] for r in rows}
    assert "decode_step" in names
    assert any(n.startswith("prefill[") for n in names)
    assert all(r["roofline_frac"] > 0 for r in rows)
    assert "profile_roofline_frac" in tel.registry.prometheus_text()

    # static Engine: same contract
    ep = jnp.asarray(_prompts(2, 7, seed=80))
    tel_e = Telemetry(profiler=StepProfiler(peak_flops=1e12, hbm_bw=1e11))
    out_p = Engine(params, CFG, max_seq_len=14,
                   telemetry=tel_e).generate(ep, 5)
    out_off = Engine(params, CFG, max_seq_len=14).generate(ep, 5)
    assert np.array_equal(np.asarray(out_p), np.asarray(out_off))
    enames = {r["program"] for r in tel_e.profiler.summary()}
    assert f"decode_step[{ep.shape[0]}]" in enames


# -------------------------------------------------------------------------
# (c) trace toolchain: truncation, CLI, Chrome export
# -------------------------------------------------------------------------

def _lifecycle_events(tel=None):
    tel = tel or Telemetry()
    tel.event("submit", 0.0, request_id=1, step=0)
    tel.span("queue_wait", 0.0, 0.1, request_id=1, step=0, steps=0.0)
    tel.span("prefill", 0.1, 0.2, request_id=1, step=0, slot=0,
             prompt_len=4, padded_len=8)
    tel.event("token", 0.2, request_id=1, step=0, first=True)
    tel.span("decode_step", 0.2, 0.3, step=1, n_active=1, batch_fill=0.5)
    tel.event("retire", 0.3, request_id=1, step=2, n_tokens=2,
              reason="budget")
    return tel


def test_truncated_trace_fails_validation_with_diagnostic():
    tel = Telemetry(max_trace_events=4)
    _lifecycle_events(tel)  # 6 events -> 2 dropped off the head
    assert tel.tracer.dropped == 2
    ev = tel.tracer.export_events()
    assert ev[0]["name"] == "truncated"
    assert ev[0]["attrs"] == {"dropped": 2, "max_events": 4}
    with pytest.raises(ValueError, match="truncated"):
        validate_events(ev)
    with pytest.raises(ValueError, match="2 oldest events"):
        validate_events(ev)
    with pytest.raises(ValueError, match="raise max_events"):
        validate_events(ev)
    # an untruncated tracer exports no marker and validates
    ok = _lifecycle_events().tracer.export_events()
    assert all(e["name"] != "truncated" for e in ok)
    validate_events(ok)


def test_truncated_marker_survives_jsonl_roundtrip(tmp_path):
    from repro.serving import validate_jsonl

    tel = Telemetry(max_trace_events=4)
    _lifecycle_events(tel)
    p = tel.tracer.write_jsonl(tmp_path / "t.jsonl")
    with pytest.raises(ValueError, match="truncated"):
        validate_jsonl(p)


def test_trace_cli_exit_codes(tmp_path, capsys):
    from repro.serving import trace as trace_mod

    tel = _lifecycle_events()
    good = tel.tracer.write_jsonl(tmp_path / "good.jsonl")
    chrome = tmp_path / "chrome.json"
    assert trace_mod.main([str(good), "--stats", "--chrome",
                           str(chrome)]) == 0
    out = capsys.readouterr().out
    assert "ok: 6 events" in out
    assert "span:decode_step" in out and "event:submit" in out
    assert "chrome trace ->" in out
    ct = json.loads(chrome.read_text())
    assert ct["traceEvents"]

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 2, "kind": "span"}\n')
    assert trace_mod.main([str(bad)]) == 1
    assert "invalid trace" in capsys.readouterr().err

    assert trace_mod.main([str(tmp_path / "missing.jsonl")]) == 1
    assert "invalid trace" in capsys.readouterr().err

    notjson = tmp_path / "notjson.jsonl"
    notjson.write_text("{nope\n")
    assert trace_mod.main([str(notjson)]) == 1
    assert "not valid JSON" in capsys.readouterr().err


def test_chrome_trace_schema_and_tracks():
    ev = _lifecycle_events().tracer.export_events()
    ct = to_chrome_trace(ev)
    assert ct["otherData"]["trace_version"] == 2
    evs = ct["traceEvents"]
    assert all(e["ph"] in ("X", "i", "M", "s", "f") for e in evs)
    # engine track: decode_step on pid 1; request track: pid 2, tid=rid
    dec = [e for e in evs if e.get("name") == "decode_step"]
    assert dec and all(e["pid"] == 1 and e["ph"] == "X" for e in dec)
    pre = [e for e in evs if e.get("name") == "prefill"]
    assert pre and all(e["pid"] == 2 and e["tid"] == 1 for e in pre)
    # timestamps rebased to the earliest event, microseconds, dur >= 0
    assert min(e["ts"] for e in evs if e["ph"] != "M") == 0
    assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")
    # named tracks for Perfetto
    meta = {(e["pid"], e["name"]): e["args"]["name"]
            for e in evs if e["ph"] == "M"}
    assert meta[(1, "process_name")] == "engine"
    assert meta[(2, "process_name")] == "requests"
    assert meta[(2, "thread_name")] == "req 1"


def test_chrome_trace_of_preempting_serve(params):
    """SLA-style serve (priorities + preemption + chunked prefill)
    through the real Server; the exported Chrome trace must carry
    matched preempt->restore flow arrows.  Same known-preempting
    workload as test_serving.test_preemption_token_identical."""
    cfg = CFG.with_kv_quant(4)
    lens, budgets = [12, 10, 8, 6, 7], [20, 18, 4, 3, 4]
    prios = [1, 1, 0, 0, 0]
    arriv = [0.0, 0.0, 3.0, 4.0, 5.0]
    prompts = [_prompts(1, L, seed=80 + i)[0] for i, L in enumerate(lens)]

    tel = Telemetry()
    srv = Server(params, cfg, num_slots=2, max_seq_len=40, telemetry=tel,
                 prefill_chunk=8, max_preemptions=2)
    for p, m, a, pr in zip(prompts, budgets, arriv, prios):
        srv.submit(p, m, arrival_time=a, priority=pr)
    srv.run_until_drained()
    ev = tel.tracer.export_events()
    validate_events(ev)
    n_pre = sum(e["name"] == "preempt" for e in ev)
    assert n_pre >= 1, "workload never preempted; widen the trace"
    ct = to_chrome_trace(ev)
    starts = [e for e in ct["traceEvents"] if e["ph"] == "s"]
    finishes = [e for e in ct["traceEvents"] if e["ph"] == "f"]
    assert len(starts) == n_pre
    # every restored preemption closes its arrow with the matching id
    sids = {e["id"] for e in starts}
    assert finishes, "no restore flow event despite preemptions"
    assert all(e["id"] in sids for e in finishes)
    # chunked admissions show up on the request tracks
    assert any(e.get("name") == "prefill_chunk" and e["ph"] == "X"
               for e in ct["traceEvents"])
    # stats summarize the same trace
    st = trace_stats(ev)
    assert st["requests"]["count"] == len(prompts)
    assert st["requests"]["completed"] == len(prompts)


# -------------------------------------------------------------------------
# (d) bench ledger
# -------------------------------------------------------------------------

_META = dict(git_sha="abc123", jax_version="0.0.test", platform="cpu",
             device_kind="cpu", n_devices=1,
             created_at="2026-01-01T00:00:00+0000", args={})


def _series(value=10.0, clock="virtual", direction="lower", tol=0.0):
    return {"value": value, "unit": "steps", "clock": clock,
            "direction": direction, "tol": tol}


def test_ledger_record_roundtrip_and_append(tmp_path):
    rec = ledger.make_record({"s.steps": _series()}, meta=_META)
    p = tmp_path / "L.json"
    ledger.append(p, rec, "serve")
    led = ledger.load(p)
    assert led["schema"] == ledger.LEDGER_SCHEMA
    assert led["suite"] == "serve"
    assert led["runs"][0]["series"]["s.steps"]["value"] == 10.0
    ledger.append(p, rec, "serve")
    assert len(ledger.load(p)["runs"]) == 2


@pytest.mark.parametrize("mutate,needle", [
    (lambda r: r.pop("series"), "missing 'series'"),
    (lambda r: r["series"].clear(), "non-empty"),
    (lambda r: r["series"]["s.steps"].pop("clock"), "clock"),
    (lambda r: r["series"]["s.steps"].update(clock="cpu"), "virtual"),
    (lambda r: r["series"]["s.steps"].update(direction="up"), "direction"),
    (lambda r: r["series"]["s.steps"].update(tol=-1), "tol"),
    (lambda r: r["series"]["s.steps"].update(value=float("nan")), "finite"),
    (lambda r: r["meta"].update(git_sha=""), "git_sha"),
])
def test_ledger_rejects_malformed_records(mutate, needle):
    rec = copy.deepcopy(
        ledger.make_record({"s.steps": _series()}, meta=_META))
    mutate(rec)
    with pytest.raises(ValueError, match=needle):
        ledger.validate_record(rec)


def test_ledger_load_rejects_bad_files(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"schema": "nope", "suite": "serve",
                             "runs": [{}]}))
    with pytest.raises(ValueError, match="schema"):
        ledger.load(p)
    p.write_text(json.dumps({"schema": ledger.LEDGER_SCHEMA,
                             "suite": "what", "runs": [{}]}))
    with pytest.raises(ValueError, match="suite"):
        ledger.load(p)
    p.write_text(json.dumps({"schema": ledger.LEDGER_SCHEMA,
                             "suite": "serve", "runs": []}))
    with pytest.raises(ValueError, match="non-empty"):
        ledger.load(p)


def test_committed_baselines_validate():
    """ISSUE acceptance: BENCH_SERVE.json / BENCH_KERNELS.json exist at
    the repo root with >= 1 schema-valid record each."""
    for path, suite in ((ledger.SERVE_LEDGER, "serve"),
                        (ledger.KERNEL_LEDGER, "kernels")):
        led = ledger.load(path)
        assert led["suite"] == suite
        assert len(led["runs"]) >= 1
        series = led["runs"][-1]["series"]
        assert any(s["clock"] == "virtual" for s in series.values())
        meta = led["runs"][-1]["meta"]
        assert meta["jax_version"] and meta["device_kind"]


def test_series_extractors_normalize_bench_stats():
    sstats = {"kv4_steps": 89, "kv4_mean_latency_steps": 48.3,
              "kv4_batch_fill": 0.85, "kv4_ratio": 3.76,
              "kv4_logit_gap": 0.51, "tok_s_kv4": 1800.0,
              "kv4_ttft_p99_ms": 120.0, "kv4_itl_p50_ms": 1.6}
    ss = ledger.serve_series(sstats, 4)
    assert ss["serve.kv4_steps"]["clock"] == "virtual"
    assert ss["serve.kv4_steps"]["tol"] == 0.0
    assert ss["serve.kv4_logit_gap"]["tol"] > 0  # backend-numeric float
    assert ss["serve.tok_s_kv4"]["clock"] == "wall"
    kout = {"fused": {"int4": {"us_dequant_einsum": 100.0, "us_fused": 10.0,
                               "speedup": 10.0, "weight_bytes": 1245184,
                               "bytes_vs_bf16": 0.266}}}
    ks = ledger.kernel_series(kout)
    assert ks["kernel.int4_weight_bytes"]["clock"] == "virtual"
    assert ks["kernel.int4_us_fused"]["clock"] == "wall"
    # every extracted series is record-valid
    ledger.make_record({**ss, **ks}, meta=_META)


# -------------------------------------------------------------------------
# (e) bench_diff
# -------------------------------------------------------------------------

def _one_run_ledger(series, suite="serve"):
    return {"schema": ledger.LEDGER_SCHEMA, "suite": suite,
            "runs": [{"meta": _META, "series": series}]}


def test_bench_diff_gates_virtual_and_reports_wall(tmp_path):
    bd = _bench_diff()
    base = _one_run_ledger({
        "s.steps": _series(100.0),
        "s.tol_steps": _series(100.0, tol=0.05),
        "s.fill": _series(0.8, direction="higher"),
        "s.tok_s": _series(1000.0, clock="wall", direction="higher"),
    })
    # identical -> clean
    d = bd.diff_ledgers(base, copy.deepcopy(base))
    assert d["regressions"] == [] and d["improvements"] == []
    # regressions: more steps (tol 0), fill drop (higher-is-better)
    worse = copy.deepcopy(base)
    worse["runs"][0]["series"]["s.steps"]["value"] = 103.0
    worse["runs"][0]["series"]["s.fill"]["value"] = 0.7
    d = bd.diff_ledgers(base, worse)
    assert set(d["regressions"]) == {"s.steps", "s.fill"}
    # within tolerance band -> ok
    tol_ok = copy.deepcopy(base)
    tol_ok["runs"][0]["series"]["s.tol_steps"]["value"] = 104.0
    assert bd.diff_ledgers(base, tol_ok)["regressions"] == []
    # wall collapse never gates; improvement is counted, not flagged
    fast = copy.deepcopy(base)
    fast["runs"][0]["series"]["s.tok_s"]["value"] = 1.0
    fast["runs"][0]["series"]["s.steps"]["value"] = 90.0
    d = bd.diff_ledgers(base, fast)
    assert d["regressions"] == [] and d["improvements"] == ["s.steps"]
    # deleting a tracked virtual series IS a regression
    gone = copy.deepcopy(base)
    del gone["runs"][0]["series"]["s.steps"]
    assert "s.steps" in bd.diff_ledgers(base, gone)["regressions"]


def test_bench_diff_cli_zero_on_real_baseline_nonzero_on_injected(tmp_path,
                                                                  capsys):
    """ISSUE acceptance, against the actual committed baselines."""
    bd = _bench_diff()
    led = ledger.load(ledger.SERVE_LEDGER)
    cand = {"schema": led["schema"], "suite": led["suite"],
            "runs": [copy.deepcopy(led["runs"][-1])]}
    ok_p = tmp_path / "cand_ok.json"
    ok_p.write_text(json.dumps(cand))
    rep = tmp_path / "report.txt"
    assert bd.main(["--baseline", str(ledger.SERVE_LEDGER),
                    "--new", str(ok_p), "--report", str(rep)]) == 0
    assert "RESULT: ok" in rep.read_text()
    capsys.readouterr()

    bad = copy.deepcopy(cand)
    vname = next(n for n, s in bad["runs"][0]["series"].items()
                 if s["clock"] == "virtual" and s["tol"] == 0)
    bad["runs"][0]["series"][vname]["value"] *= 1.10
    bad_p = tmp_path / "cand_bad.json"
    bad_p.write_text(json.dumps(bad))
    assert bd.main(["--baseline", str(ledger.SERVE_LEDGER),
                    "--new", str(bad_p), "--report", str(rep)]) == 1
    text = rep.read_text()
    assert "REGRESSION" in text and vname in text
    capsys.readouterr()

    # self-check mode runs clean on the committed history
    assert bd.main([]) == 0
    capsys.readouterr()
    # suite mismatch / unreadable input fail closed
    assert bd.main(["--baseline", str(ledger.KERNEL_LEDGER),
                    "--new", str(ok_p)]) == 1
    assert bd.main(["--baseline", str(tmp_path / "nope.json"),
                    "--new", str(ok_p)]) == 1
    capsys.readouterr()
