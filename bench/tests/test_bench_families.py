"""Model families found by name: every module has the interface, each
cell takes the family its configuration names (dense where it names
none), a new family joins by files alone and gets the shared metrics, an
unknown family is an error that names its file, and the dense family
counts FLOPs and draws weights as it always has."""

import hashlib
import io
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench.families import dense

DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
FAMILIES = sorted(p.stem for p in (harness.BENCH / "families").glob("*.py")
                  if not p.stem.startswith("__"))
INTERFACE = ("spec_from_config", "make_weights", "program_config",
             "program_params", "score")


@pytest.mark.parametrize("name", FAMILIES)
def test_each_family_has_the_interface(name):
    fam = harness._load_module("families", name)
    for fn in INTERFACE:
        assert callable(getattr(fam, fn, None)), f"{name} lacks {fn}"


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_config_without_a_family_is_dense(cell):
    # and a configuration that names one takes that module
    c = harness.load_cell(BENCH, cell)
    named = c.config.get("bench_family")
    assert c.family is harness._load_module("families", named or "dense")
    if named is None:
        assert c.family is dense


STUB = '''"""The dense family under a name of its own, as a new family comes."""
from bench.families.dense import (make_weights, program_config,  # noqa: F401
                                  program_params, score, spec_from_config)
'''


@pytest.fixture
def relabelled(tmp_path):
    """BENCHMARK.json with one more cell, a tiny configuration of the
    family ``relabelled``; that family, the configuration, its traffic
    and its check are new files under `tmp_path`, and no file that
    exists is edited."""
    for kind in ("configs", "families", "traffic", "checks"):
        (tmp_path / kind).mkdir()
    cfg = json.loads((DATA / "configs" / "tiny.json").read_text())
    cfg.update(name="tiny-relabelled", bench_family="relabelled")
    (tmp_path / "configs" / "tiny-relabelled.json").write_text(json.dumps(cfg))
    (tmp_path / "families" / "relabelled.py").write_text(STUB)
    shutil.copy(DATA / "traffic" / "tiny.json", tmp_path / "traffic")
    shutil.copy(DATA / "checks" / "tiny.chat.json",
                tmp_path / "checks" / "tiny-relabelled.chat.json")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "tiny-relabelled.chat",
                               "config": "tiny-relabelled", "traffic": "tiny",
                               "chips": 1, "why": "a new family's cell"})
    return bench, tmp_path


def test_a_new_family_joins_by_files_alone(relabelled):
    bench, data = relabelled
    c = harness.load_cell(bench, "tiny-relabelled.chat", data)
    assert c.family.__name__.endswith(".relabelled") and c.family is not dense
    assert c.family.score is dense.score
    # every metric that lists no cells, the whole step's share among them
    e2e = {m["name"] for m in bench["end_to_end"] if "workloads" not in m}
    shared = {m["name"] for m in bench["per_layer"]
              if "workloads" not in m and m["moves"] in e2e}
    assert {m["name"] for m in c.end_to_end} == e2e
    assert {m["name"] for m in c.per_layer} == shared
    assert {"decode_mfu", "decode_step_ms", "device_idle_share",
            "qmatmul_roofline", "kv_dequant_roofline", "step_host_ms",
            "kv_live_read_share"} <= shared


@pytest.mark.parametrize("trace", [False, True])
def test_a_new_family_is_served_and_checked(relabelled, trace):
    bench, data = relabelled
    r = harness.run("tiny-relabelled.chat", 11, 1.5, trace, bench=bench,
                    data=data, require_chip=False, out=io.StringIO(),
                    err=io.StringIO())
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    if trace:
        # the readers of spans read; those of the device trace, on no
        # chip, read nothing and are left out
        assert {"decode_step_ms", "step_host_ms",
                "kv_live_read_share"} <= set(r["metrics"])
        assert not {"decode_mfu", "qmatmul_roofline"} & set(r["metrics"])
    else:
        assert set(r["metrics"]) == {"itl_p50_ms", "setup_s"}


def test_an_unknown_family_names_its_file(tmp_path):
    (tmp_path / "configs").mkdir()
    cfg = json.loads((DATA / "configs" / "tiny.json").read_text())
    cfg["bench_family"] = "no_such_family"
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(cfg))
    bench = json.loads((DATA / "bench_tiny.json").read_text())
    with pytest.raises(FileNotFoundError, match="bench/families/no_such_family.py"):
        harness.load_cell(bench, "tiny.chat", tmp_path)


# the parent's formula and its values, before the count moved into the family
@pytest.mark.parametrize("config,rows,live,flops", [
    ("qwen2-7b-w4kv4", 1, 1.0, 14140973056.0),
    ("qwen2-7b-w4kv4", 64, 33000.0, 918243049472.0),
    ("qwen2-7b-w4kv4", 15, 9876.5, 216073080832.0),
    ("dscoder-33b-d16-w4kv4", 1, 1.0, 17433034752.0),
    ("dscoder-33b-d16-w4kv4", 128, 131072.0, 2291499270144.0),
    ("dscoder-33b-d16-w4kv4", 15, 9876.5, 266019504128.0),
])
def test_dense_decode_flops_is_the_parents_formula(config, rows, live, flops):
    cfg = json.loads((harness.BENCH / "configs" / f"{config}.json").read_text())
    s = dense.spec_from_config(cfg)
    assert s.decode_flops(rows, live) == flops
    assert s.decode_flops(rows, live) == (2.0 * s.matmul_params() * rows
                                          + 4.0 * s.n_layers * s.q_dim * live)


def test_dense_weights_are_the_parents():
    cfg = json.loads((DATA / "configs" / "tiny.json").read_text())
    w = dense.make_weights(dense.spec_from_config(cfg), 2**33 + 5)
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(w)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.ascontiguousarray(np.asarray(leaf))
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    # taken from the weights as drawn before the family modules existed
    assert h.hexdigest() == "20fa060c30ac2c9effb21363deeb6b5853da074ed8aced01e032aba17df54b18"
