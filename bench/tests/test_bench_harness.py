"""The harness: cells and their files found by name, BENCHMARK.json within
its contract, no measurement without a chip, and ``correct`` false when
the served path is broken underneath (tiny cell, CPU)."""

import io
import json
import re
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import harness

DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
TINY = json.loads((DATA / "bench_tiny.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_files(cell):
    c = harness.load_cell(BENCH, cell)
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(c.readers[m["name"]].read)
    assert {"_qmatmul_kernel", "_dequant_kernel"} <= set(c.costs)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)


def test_a_missing_file_is_an_error():
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "nope.chat", "config": "nope",
                               "traffic": "chat", "chips": 1, "why": "x"})
    with pytest.raises(FileNotFoundError):
        harness.load_cell(bench, "nope.chat")
    bench["workloads"][-1]["config"] = "qwen2-7b-w4kv4"
    with pytest.raises(FileNotFoundError):  # no checks/nope.chat.json
        harness.load_cell(bench, "nope.chat")
    with pytest.raises(KeyError):
        harness.load_cell(bench, "absent")


def test_no_chip_no_measurement(capsys):
    rc = harness.main(["--workload", "qwen2-7b-w4kv4.chat", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 3 and out.out == "" and "no measurement" in out.err


def test_benchmark_json_within_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (harness.ROOT / c["file"]).is_file()
        data = json.loads((harness.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def _run(cell):
    out = io.StringIO()
    return harness.run(cell, 11, 1.5, False, bench=TINY, data=DATA,
                       require_chip=False, out=out, err=io.StringIO())


def test_sound_tiny_run_is_correct():
    r = _run("tiny.chat")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"itl_p50_ms", "itl_p95_ms", "setup_s"}
    assert list(r)[-1] == "check"


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.serving import server

    real = server.sample_token

    def off_by_one(logits, key, temperature):
        return (real(logits, key, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(server, "sample_token", off_by_one)
    assert not _run("tiny.chat")["correct"]


def test_a_decode_step_that_leaves_the_cache_unchanged_is_caught(monkeypatch):
    from repro.models import attention

    monkeypatch.setattr(attention, "write_cache_paged",
                        lambda cache, *a, **k: cache)
    assert not _run("tiny.batch")["correct"]
