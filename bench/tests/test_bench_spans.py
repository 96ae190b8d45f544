"""The readers of the server's own spans: ``step_host_ms`` (a step's host
time, less the spans inside it that wait on the device) and
``kv_live_read_share`` (live KV positions over those the decode read
covers), on hand-made spans and on a traced tiny run (CPU)."""

import io
import json
from pathlib import Path

import pytest

from bench import harness
from bench.metrics import kv_live_read_share, step_host_ms
from repro.serving.telemetry import Telemetry

DATA = Path(__file__).resolve().parent / "data"
MS = 1e-3


def _ctx(spans, t_open=0.0, t_close=1.0):
    """A Context whose telemetry holds `spans`: (name, t0, t1, attrs)."""
    tel = Telemetry()
    for name, t0, t1, attrs in spans:
        tel.span(name, t0, t1, step=0, **attrs)
    return harness.Context(telemetry=tel, t_open=t_open, t_close=t_close)


def _decode(t0, t1, read, live):
    return ("decode_step", t0, t1, {"n_active": 1, "batch_fill": 1.0,
                                    "kv_read": read, "kv_live": live})


def test_step_host_time_with_and_without_a_prefill():
    ctx = _ctx([
        # a decode-only step: 10 ms, of which 6 wait on the device
        ("step", 100 * MS, 110 * MS, {}),
        ("admit", 100 * MS, 101 * MS, {}),
        _decode(102 * MS, 108 * MS, 64, 16),
        ("emit", 108 * MS, 109 * MS, {}),
        # a step that admits: 2 ms of prefill inside admit, 5 of decode
        ("step", 110 * MS, 120 * MS, {}),
        ("admit", 110 * MS, 113.5 * MS, {}),
        ("prefill", 111 * MS, 113 * MS, {}),
        _decode(114 * MS, 119 * MS, 64, 32),
    ])
    assert step_host_ms.read(ctx) == pytest.approx((4 + 3) / 2)
    assert kv_live_read_share.read(ctx) == pytest.approx(48 / 128)


def test_nested_device_spans_count_once():
    # a restore inside a prefill's stretch (never both in practice) is
    # not subtracted twice: the union of the waits is
    ctx = _ctx([
        ("step", 0.0, 10 * MS, {}),
        ("prefill", 1 * MS, 5 * MS, {}),
        ("restore", 2 * MS, 4 * MS, {}),
        ("prefill_chunk", 4.5 * MS, 6 * MS, {}),
    ])
    assert step_host_ms.read(ctx) == pytest.approx(10 - 5)


def test_spans_straddling_the_window_edge():
    t_open, t_close = 1.0, 2.0
    ctx = _ctx([
        # ends before the opening: not read
        ("step", 0.90, 0.95, {}),
        _decode(0.91, 0.94, 100, 100),
        # opens before the window, ends in it: read, with the decode
        # step that ended before the opening subtracted from it
        ("step", 0.96, 1.01, {}),
        _decode(0.965, 0.995, 100, 20),
        # inside
        ("step", 1.50, 1.52, {}),
        _decode(1.505, 1.515, 100, 60),
        # ends at the close: not read (the window is [open, close))
        ("step", 1.99, 2.00, {}),
        _decode(1.991, 2.00, 100, 0),
    ], t_open, t_close)
    assert step_host_ms.read(ctx) == pytest.approx(1e3 * (0.02 + 0.01) / 2)
    # the decode step that ended before the opening is not in the share
    assert kv_live_read_share.read(ctx) == pytest.approx(60 / 100)


def test_no_spans_read_none():
    assert step_host_ms.read(_ctx([])) is None
    assert kv_live_read_share.read(_ctx([])) is None
    # a run without telemetry (--trace 0) has none either
    off = harness.Context(telemetry=None, t_open=0.0, t_close=1.0)
    assert step_host_ms.read(off) is None
    assert kv_live_read_share.read(off) is None
    # a program whose decode steps carry no KV counts reads none
    old = _ctx([("decode_step", 0.1, 0.2, {"n_active": 1,
                                           "batch_fill": 1.0})])
    assert kv_live_read_share.read(old) is None


def test_traced_tiny_run_reports_both():
    bench = json.loads((DATA / "bench_tiny.json").read_text())
    bench["per_layer"] += [
        {"name": "step_host_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "server host path",
         "moves": "itl_p50_ms"},
        {"name": "kv_live_read_share", "unit": "share", "better": "higher",
         "source": "program_span", "layer": "KV cache manager",
         "moves": "itl_p50_ms"},
    ]
    r = harness.run("tiny.chat", 11, 1.5, True, bench=bench, data=DATA,
                    require_chip=False, out=io.StringIO(),
                    err=io.StringIO())
    m = r["metrics"]
    assert r["correct"]
    assert m["step_host_ms"]["value"] > 0
    assert 0 < m["kv_live_read_share"]["value"] <= 1
    assert "decode_step_ms" in m
