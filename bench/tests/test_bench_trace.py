"""Trace reduction on two small traces recorded on one TPU v5e (a 2-layer,
512-wide qwen2-shaped model, kv4, 4 slots, 3 decode steps each wrapped in
a ``bench/step`` span): one from before the program named its Pallas
calls, matched by operand signature, and one whose calls carry their
kernel's name (``data/record_decode_trace.py``), matched by name; and the
kernels' cost functions at the widths of both configurations, against
counts made by hand."""

import gzip
import importlib
from pathlib import Path

import pytest

from bench import xspace

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
COSTS = {n: importlib.import_module(f"bench.costs.{n}")
         for n in ("_qmatmul_kernel", "_dequant_kernel")}


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    raw = gzip.open(DATA / "decode_small.xplane.pb.gz").read()
    return xspace.reduce(ProfileData.from_serialized_xspace(raw), COSTS, PEAKS)


def test_kernel_calls_counted_by_hand(reduced):
    k = reduced["kernels"]
    # 3 steps x (2 layers x 7 matrices + lm_head)
    assert k["_qmatmul_kernel"]["calls"] == 45
    # 3 steps x 2 layers x (K and V)
    assert k["_dequant_kernel"]["calls"] == 12
    assert reduced["modules"]["jit_step_paged"]["calls"] == 3


def test_kernel_work_by_hand(reduced):
    q = reduced["kernels"]["_qmatmul_kernel"]
    # per step, M = 8 rows (4 slots padded to 8): per layer wq 512x512,
    # wk, wv 512x128, wo 512x512, gate/up 512x1024, down 1024x512; head
    # 512x1024
    per_layer = 512 * 512 * 2 + 512 * 128 * 2 + 512 * 1024 * 3
    assert q["flops"] == 3 * 2 * 8 * (2 * per_layer + 512 * 1024)
    d = reduced["kernels"]["_dequant_kernel"]
    # rows = 33 pages x 16 positions = 528 -> tiles of 128: the call sees
    # 512 rows of 16 words, 2 scales and 128 features (its signature)
    one = 512 * 16 * 4 + 512 * 2 * 2 + 16 * 4 + 512 * 128 * 2
    assert d["bytes"] == 12 * one and d["flops"] == 0
    for v in (q, d):
        assert 0 < v["least_s"] < v["time_s"]


def test_busy_idle_and_breakdown(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # from the first bench/step span to the end of the third
    assert reduced["window_s"] == pytest.approx(
        (56048659 + 4740750 - 46101629) * 1e-9, rel=1e-6)
    idle = dict(reduced["idle_gaps"])
    assert "bench/step" in idle
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    names = [n for n, _ in reduced["device_ops"]]
    assert names[:2] == ["_qmatmul_kernel", "_dequant_kernel"]
    assert len(names) <= 10


def test_self_time_of_nested_events():
    # a while loop [0, 10] holding [1, 4] and [5, 9]
    assert xspace._self_times([(0, 10), (1, 4), (5, 9)]) == [3, 3, 4]


def test_parse_op_of_a_recorded_kernel_call():
    name = ('%closed_call.82 = bf16[8,3584]{1,0:T(8,128)(2,1)S(1)} custom-call('
            'bf16[8,3584]{1,0:T(8,128)(2,1)S(1)} %reshape.452, u32[448,3584]'
            '{1,0:T(8,128)S(1)} %dynamic-slice_bitcast_fusion.49, bf16[56,3584]'
            '{1,0:T(8,128)(2,1)S(1)} %dynamic-slice_bitcast_fusion.50, f32[16]'
            '{0:T(128)S(1)} %fusion.184), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={bf16[8,3584]{1,0}, u32[448,3584]{1,0}, '
            'bf16[56,3584]{1,0}, f32[16]{0}}, frontend_attributes={kernel_metadata={}}')
    op = xspace.parse_op(name)
    assert op["name"] == "closed_call.82" and op["opcode"] == "custom-call"
    assert op["target"] == "tpu_custom_call"
    assert op["out"] == [("bf16", (8, 3584))]
    assert [s for _, s in op["operands"]] == [(8, 3584), (448, 3584), (56, 3584), (16,)]
    c = COSTS["_qmatmul_kernel"].match(op)
    assert c == {"M": 8, "K": 3584, "N": 3584, "cpw": 8, "block": 64,
                 "x_dtype": "bf16", "scale_dtype": "bf16", "codebook": 16}
    assert COSTS["_dequant_kernel"].match(op) is None


def _qmm_op(M, K, N):
    return {"target": "tpu_custom_call", "out": [("bf16", (M, N))],
            "operands": [("bf16", (M, K)), ("u32", (K // 8, N)),
                         ("bf16", (K // 64, N)), ("f32", (16,))]}


def _kv_op(R, feat):
    return {"target": "tpu_custom_call", "out": [("bf16", (R, feat))],
            "operands": [("u32", (R, feat // 8)), ("bf16", (R, feat // 64)),
                         ("f32", (1, 16))]}


@pytest.mark.parametrize("M,K,N", [
    (64, 3584, 18944),    # qwen2-7b w_up, chat decode step
    (4096, 18944, 3584),  # qwen2-7b w_down, a 4096-token prefill
    (128, 7168, 7168),    # deepseek-coder-33b wq, batch decode step
    (128, 7168, 32256),   # deepseek-coder-33b lm_head
])
def test_qmatmul_cost_by_hand(M, K, N):
    mod = COSTS["_qmatmul_kernel"]
    flops, b = mod.cost(mod.match(_qmm_op(M, K, N)))
    assert flops == 2 * M * K * N
    assert b == M * K * 2 + K * N // 2 + (K // 64) * N * 2 + 64 + M * N * 2


@pytest.mark.parametrize("R,feat", [
    (64 * 2048, 512),     # qwen2-7b: 4 KV heads x 128, chat pool
    (128 * 2048, 1024),   # deepseek-coder-33b: 8 KV heads x 128, batch pool
])
def test_kv_dequant_cost_by_hand(R, feat):
    mod = COSTS["_dequant_kernel"]
    flops, b = mod.cost(mod.match(_kv_op(R, feat)))
    assert flops == 0
    # 4 bits a feature packed, a bf16 scale per 64, bf16 out
    assert b == R * feat // 2 + R * feat // 64 * 2 + 64 + R * feat * 2


@pytest.fixture(scope="module")
def named():
    from jax.profiler import ProfileData

    raw = gzip.open(DATA / "decode_named.xplane.pb.gz").read()
    return ProfileData.from_serialized_xspace(raw)


def _ops(pd):
    return [xspace.parse_op(e.name) for p in pd.planes
            if p.name.startswith("/device:TPU:") for line in p.lines
            if line.name == "XLA Ops" for e in line.events]


def test_named_calls_are_costed_by_name(named):
    calls = [op for op in _ops(named) if op["target"] == "tpu_custom_call"]
    assert {op["kernel"] for op in calls} == set(COSTS)
    k = xspace.reduce(named, COSTS, PEAKS)["kernels"]
    # the same steps as the unnamed trace: 3 x (2 layers x 7 matrices +
    # lm_head) GEMMs, 3 x 2 layers x (K and V) KV reads
    assert k["_qmatmul_kernel"]["calls"] == 45
    assert k["_dequant_kernel"]["calls"] == 12
    # a module that would take every custom call by its operands, first
    # in name order, takes none of the named ones
    greedy = {"_a_greedy": type("M", (), {
        "match": staticmethod(lambda op: {} if op["target"] else None),
        "cost": staticmethod(lambda sizes: (0.0, 0.0))})}
    assert xspace.reduce(named, {**greedy, **COSTS}, PEAKS)["kernels"] == k


def test_a_named_call_with_no_cost_file_shows_under_its_name(named):
    r = xspace.reduce(named, {"_qmatmul_kernel": COSTS["_qmatmul_kernel"]}, PEAKS)
    assert set(r["kernels"]) == {"_qmatmul_kernel"}
    ops = dict(r["device_ops"])
    assert ops["_dequant_kernel"] > 0
    assert not any(n.startswith("closed_call") for n in ops)


def test_name_and_signature_matching_agree(named, monkeypatch):
    by_name = xspace.reduce(named, COSTS, PEAKS)
    real = xspace.parse_op
    monkeypatch.setattr(xspace, "parse_op",
                        lambda name: {**real(name), "kernel": None})
    by_signature = xspace.reduce(named, COSTS, PEAKS)
    assert by_name == by_signature
