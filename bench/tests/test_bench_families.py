"""Model families found by name: every module has the interface, a
configuration without ``bench_family`` is dense, an unknown family is an
error that names its file, and the dense family counts FLOPs and draws
weights as it always has."""

import hashlib
import json
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
    c = harness.load_cell(BENCH, cell)
    assert "bench_family" not in c.config
    assert c.family is dense


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
