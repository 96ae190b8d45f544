"""Flag-matrix audit for repro.launch.serve.

--kv-bits, --matmul-mode, --plan, and --mesh landed in four different
PRs; this suite pins (a) every conflicting pairing fails LOUDLY at
validate_flags time — nothing is silently ignored — and (b) a
parametrized matrix of legal combinations actually serves end to end
(tiny arch, tiny workload).  The serve smokes are compile-heavy and run
in the slow lane; the conflict checks are pure argparse and stay fast.
"""

from __future__ import annotations

import dataclasses

import pytest

from repro.launch import serve as serve_mod

pytest.importorskip("jax")


def _args(*argv):
    return serve_mod.build_argparser().parse_args(["--arch", "tiny-160k",
                                                   *argv])


# -------------------------------------------------------------------------
# conflicting pairings fail loudly (fast)
# -------------------------------------------------------------------------

@pytest.mark.parametrize("argv,needle", [
    # --plan owns the weight-quant config
    (("--plan", "p.json", "--bits", "4"), "--plan"),
    (("--plan", "p.json", "--dtype", "float"), "--plan"),
    (("--plan", "p.json", "--block-size", "32"), "--plan"),
    (("--plan", "p.json", "--outlier-pct", "0.5"), "--plan"),
    # --dtype fp16 skips weight quantization
    (("--dtype", "fp16", "--bits", "4"), "fp16"),
    (("--dtype", "fp16", "--block-size", "32"), "fp16"),
    # kv knobs need a quantized cache
    (("--kv-bits", "16", "--kv-block-size", "32"), "--kv-bits"),
    (("--kv-dtype", "int"), "--kv-bits"),
    # mode-mismatched workload flags
    (("--mode", "static", "--num-slots", "4"), "static"),
    (("--mode", "static", "--rate", "1.0"), "static"),
    (("--mode", "static", "--stream"), "static"),
    (("--mode", "continuous", "--batch", "4"), "static-mode"),
    (("--mode", "continuous", "--prompt-len", "16"), "static-mode"),
    # kv probe needs a quantized cache, a telemetry sink, continuous mode
    (("--kv-probe-every", "2", "--metrics-out", "m.prom"), "bf16 cache"),
    (("--kv-bits", "4", "--kv-probe-every", "2"), "telemetry sink"),
    (("--kv-bits", "4", "--kv-probe-every", "0", "--metrics-out",
      "m.prom"), "positive"),
    (("--mode", "static", "--kv-bits", "4", "--kv-probe-every", "2",
      "--metrics-out", "m.prom"), "continuous-mode"),
    # SLA scheduler flags are continuous-only with validated values
    (("--mode", "static", "--prefill-chunk", "8"), "static"),
    (("--mode", "static", "--priorities", "2"), "static"),
    (("--mode", "static", "--max-preemptions", "1"), "static"),
    (("--prefill-chunk", "0"), "positive chunk length"),
    (("--priorities", "0"), "at least one class"),
    (("--max-preemptions", "-1"), ">= 0"),
    # preemption needs >= 2 classes to ever find a victim
    (("--max-preemptions", "2"), "--priorities"),
    (("--max-preemptions", "2", "--priorities", "1"), "--priorities"),
    # the profiler's gauges need a telemetry sink to land in
    (("--profile",), "--profile"),
    (("--peaks", "1e12,1e11", "--metrics-out", "m.prom"), "--profile"),
    (("--profile", "--peaks", "fast", "--metrics-out", "m.prom"), "--peaks"),
    (("--profile", "--peaks", "0,1e11", "--metrics-out", "m.prom"),
     "positive"),
    # paged-cache flags: continuous-only, exclusive with chunking/mesh
    (("--mode", "static", "--paged"), "static"),
    (("--page-size", "8"), "--paged"),
    (("--pages", "16"), "--paged"),
    (("--paged", "--prefill-chunk", "8"), "mutually exclusive"),
    (("--paged", "--mesh", "2x4"), "single-host"),
    (("--paged", "--page-size", "0"), "positive"),
    (("--paged", "--pages", "1"), "trash page"),
    # sampling / checkpoint flags validate their values up front
    (("--temperature", "-0.5"), "--temperature"),
    (("--ckpt-dir", "/nonexistent/ckpt-dir-for-test"), "--ckpt-dir"),
])
def test_conflicting_flags_rejected(argv, needle):
    with pytest.raises(SystemExit, match=needle):
        serve_mod.validate_flags(_args(*argv))


def test_mesh_flag_validated():
    with pytest.raises(SystemExit, match="DATAxMODEL"):
        serve_mod.parse_mesh("banana")
    with pytest.raises(SystemExit, match="devices"):
        serve_mod.parse_mesh("16x16")  # this process has 1 CPU device
    assert serve_mod.parse_mesh(None) is None


@pytest.mark.parametrize("argv", [
    (),
    ("--kv-bits", "4", "--kv-block-size", "32", "--kv-dtype", "int"),
    ("--plan", "p.json", "--kv-bits", "4", "--matmul-mode", "fused"),
    ("--dtype", "fp16",),
    ("--mode", "static", "--batch", "2", "--prompt-len", "8"),
    ("--mode", "continuous", "--num-slots", "2", "--rate", "1.0"),
    ("--kv-bits", "4", "--kv-probe-every", "2", "--metrics-out", "m.prom",
     "--trace-out", "t.jsonl"),
    ("--mode", "static", "--metrics-out", "m.prom"),
    ("--prefill-chunk", "8"),
    ("--priorities", "2", "--max-preemptions", "2"),
    ("--prefill-chunk", "16", "--priorities", "3", "--max-preemptions", "1",
     "--kv-bits", "4"),
    ("--max-preemptions", "0"),
    ("--profile", "--metrics-out", "m.prom"),
    ("--profile", "--trace-out", "t.jsonl"),
    ("--mode", "static", "--profile", "--metrics-out", "m.prom"),
    ("--profile", "--peaks", "197e12,819e9", "--metrics-out", "m.prom"),
    ("--seed", "7"),
    ("--paged",),
    ("--paged", "--page-size", "8", "--pages", "32", "--kv-bits", "4"),
    ("--paged", "--priorities", "2", "--max-preemptions", "1"),
])
def test_legal_flag_combinations_validate(argv):
    serve_mod.validate_flags(_args(*argv))


# -------------------------------------------------------------------------
# the legal matrix serves end to end (slow: each cell compiles a serve)
# -------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_plan(tmp_path_factory):
    """A minimal mixed plan for tiny-160k, saved as --plan JSON."""
    from repro.configs import QuantConfig
    from repro.precision import PrecisionPlan

    base = QuantConfig(bits=4, dtype="float", block_size=64)
    plan = PrecisionPlan(arch="tiny-160k",
                         default=dataclasses.asdict(base),
                         assignments={})
    path = tmp_path_factory.mktemp("plans") / "tiny.json"
    plan.save(path)
    return str(path)


@pytest.mark.slow
@pytest.mark.parametrize("argv", [
    # mode x kv-bits x matmul-mode corners, plus --plan riding along
    ("--mode", "static", "--max-new", "4"),
    ("--mode", "static", "--kv-bits", "4", "--matmul-mode", "fused",
     "--max-new", "4"),
    ("--mode", "continuous", "--kv-bits", "4", "--max-new", "4"),
    ("--mode", "continuous", "--kv-bits", "8", "--kv-block-size", "32",
     "--matmul-mode", "dequant_einsum", "--max-new", "4"),
    ("--mode", "continuous", "--matmul-mode", "fused", "--max-new", "4"),
    ("PLAN", "--mode", "continuous", "--kv-bits", "4", "--max-new", "4"),
    ("PLAN", "--mode", "static", "--matmul-mode", "fused", "--max-new", "4"),
    # the SLA scheduler serves end to end through the launcher
    ("--mode", "continuous", "--kv-bits", "4", "--prefill-chunk", "8",
     "--priorities", "2", "--max-preemptions", "1", "--max-new", "4"),
    # the paged KV cache serves end to end through the launcher
    ("--mode", "continuous", "--kv-bits", "4", "--paged", "--page-size",
     "8", "--max-new", "4"),
])
def test_flag_matrix_serves(argv, tiny_plan, capsys):
    argv = list(argv)
    if argv and argv[0] == "PLAN":
        argv = ["--plan", tiny_plan] + argv[1:]
    full = ["--arch", "tiny-160k"] + argv
    if argv[argv.index("--mode") + 1] == "continuous":
        full += ["--num-requests", "3", "--num-slots", "2"]
    else:
        full += ["--batch", "2", "--prompt-len", "8"]
    serve_mod.main(full)
    out = capsys.readouterr().out
    assert ("tok/s" in out) or ("generated" in out), out


@pytest.mark.slow
def test_profile_flag_serves_with_roofline_gauges(tmp_path, capsys):
    """--profile end to end through the launcher: the serve must print
    the roofline summary and the metrics dump must carry the profile_*
    gauge families (the CI telemetry smoke greps the same)."""
    mpath = tmp_path / "m.prom"
    serve_mod.main(["--arch", "tiny-160k", "--mode", "continuous",
                    "--kv-bits", "4", "--num-requests", "3",
                    "--num-slots", "2", "--max-new", "4", "--profile",
                    "--peaks", "1e12,1e11", "--metrics-out", str(mpath)])
    out = capsys.readouterr().out
    assert "profiler (" in out and "decode_step" in out, out
    text = mpath.read_text()
    for fam in ("profile_program_flops", "profile_roofline_frac",
                "profile_step_seconds_bucket"):
        assert fam in text, fam
    assert 'kv_bits="4"' in text


@pytest.mark.slow
def test_mesh_serve_smoke_subprocess():
    """--mesh composes with --kv-bits end to end: a 2x4 virtual-mesh
    continuous serve of a packed 4-bit pool (the tentpole wiring through
    the launcher).  tiny-650k: 4 heads divide the model axis."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "tiny-650k",
         "--mesh", "2x4", "--kv-bits", "4", "--mode", "continuous",
         "--num-requests", "3", "--num-slots", "2", "--max-new", "4"],
        capture_output=True, text=True, timeout=900, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "MB/device" in res.stdout, res.stdout
    assert "tok/s" in res.stdout, res.stdout
