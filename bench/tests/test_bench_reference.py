"""The plain reference against the program at a tiny size on the CPU:
the same weight values, the same KV cache format, the served tokens its
greedy choices, and a control one precision lower that reads worse."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, model, reference

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def tiny():
    cfg = json.loads((DATA / "configs" / "tiny.json").read_text())
    spec = model.spec_from_config(cfg)
    return cfg, spec, model.make_weights(spec, 2**33 + 5)


def test_weights_decode_as_the_program_reads_them(tiny):
    from repro.core.qtensor import dequantize_tensor

    cfg, spec, w = tiny
    params = model.program_params(w, spec)
    cb = model.float_codebook(spec.w_bits, spec.w_ebits)
    for name, (K, N) in spec.matrices().items():
        mod = "mixer" if name in ("wq", "wk", "wv", "wo") else "ffn"
        prog = dequantize_tensor(params["stack"][0][mod][name]["w"], jnp.float32)
        for i in range(spec.n_layers):
            ref = reference.decode_matrix(w["layers"][name]["packed"][i],
                                          w["layers"][name]["scales"][i], cb,
                                          spec.w_bits, spec.w_block)
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(prog[i]).T)


def test_program_tree_matches_its_own_builder(tiny):
    from repro.configs.base import QuantConfig
    from repro.models.quantize import init_quantized_params

    cfg, spec, w = tiny
    arch = model.program_config(cfg, spec)
    ours = jax.tree.map(lambda a: (a.shape, a.dtype), model.program_params(w, spec))
    theirs = jax.eval_shape(lambda: init_quantized_params(
        jax.random.PRNGKey(0), arch, QuantConfig(bits=4, dtype="float", block_size=64)))
    assert jax.tree.structure(ours) == jax.tree.structure(
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs))
    assert ours == jax.tree.map(lambda a: (a.shape, a.dtype), theirs)


def test_kv_format_as_the_program_stores_it(tiny):
    from repro.kernels.kv_dequant import KVQuantSpec, dequant_rows_ref, encode_rows

    _, spec, _ = tiny
    x = jax.random.normal(jax.random.PRNGKey(1), (64, spec.kv_dim)).astype(jnp.bfloat16)
    kvq = KVQuantSpec(bits=spec.kv_bits, block_size=spec.kv_block, dtype_name="float")
    prog = dequant_rows_ref(*encode_rows(x, kvq), kvq, spec.kv_dim, out_dtype=jnp.float32)
    ref = reference.kv_round(x.astype(jnp.float32),
                             model.float_codebook(spec.kv_bits, spec.kv_ebits),
                             spec.kv_block)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(prog))


@pytest.fixture(scope="module")
def served():
    bench = json.loads((DATA / "bench_tiny.json").read_text())
    cell = harness.load_cell(bench, "tiny.chat", DATA)
    ctx, logs, w = harness.serve(cell, 3, 2.0, False, require_chip=False)
    picked = check.sample(logs, 3, {"sample": 3})
    return ctx.spec, w, picked, cell.check


def test_served_tokens_are_the_reference_greedy_choices(served):
    spec, w, picked, chk = served
    geo = chk["geometry"]
    assert len(picked) == 3
    assert len(picked[0].prompt) + len(picked[0].tokens) == max(
        len(g.prompt) + len(g.tokens) for g in picked)
    got = check.readings(check.gaps(w, spec, picked, geo))
    assert got["tokens_compared"] == sum(len(g.tokens) for g in picked)
    # bf16 serving against an f32 reference: near-ties may flip, by little
    assert got["max_logit_gap"] < 0.2
    assert got["tokens_off_greedy"] <= got["tokens_compared"] // 10


def test_control_one_precision_lower_reads_worse(served):
    spec, w, picked, chk = served
    prog, low = check.with_control(w, spec, picked, chk["geometry"])
    p, c = check.readings(prog), check.readings(low)
    assert c["max_logit_gap"] > 2 * p["max_logit_gap"]
    assert c["tokens_off_greedy"] > p["tokens_off_greedy"]
    # the harness's own decision, with a limit between the two readings
    # as a cell's check file sets it: the program passes, the control not
    lim = {"max_logit_gap": (p["max_logit_gap"] + c["max_logit_gap"]) / 2}
    assert check.decide(p, lim)[0]
    assert not check.decide(c, lim)[0]
