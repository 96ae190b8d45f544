"""reprolint analyzer tests (analysis/rules|findings|lint|audit).

Three tiers:

* a corrupt-fixture matrix — per rule, one minimal snippet that MUST
  fire and one near-miss that MUST stay silent, run through the same
  engine + CLI the repo lint uses (each fixture is a self-contained
  mini-repo in tmp_path, so the cross-file rules locate their
  declarations inside the fixture);
* baseline semantics — grandfathering, mandatory justifications, stale
  entries, suppression comments;
* the self-run — the real ``src/`` tree plus the committed baseline must
  lint clean, and the Layer-2 HLO predicates / compile counting are
  unit-tested on synthetic text and a live tiny program.

The full Layer-2 grid (kv16/8/4 Engine+Server) runs in the CI lint lane
via ``scripts/lint.py --audit``; here a single slow test covers one
kv4 round so the full pytest lane exercises the driver end to end.
"""

from __future__ import annotations

import io
import json
import textwrap
from pathlib import Path

import pytest

pytest.importorskip("jax")

from repro.analysis import audit as audit_mod
from repro.analysis import lint as lint_mod
from repro.analysis.findings import Baseline, apply_suppressions, suppressed_lines
from repro.analysis.rules import run_rules

REPO_ROOT = Path(__file__).resolve().parents[1]


def _mini_repo(tmp_path, files: dict) -> Path:
    root = tmp_path / "mini"
    root.mkdir(parents=True)
    for name, src in files.items():
        p = root / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return root


def _rules_fired(root: Path) -> set:
    findings, sources = run_rules(root)
    return {f.rule for f in apply_suppressions(findings, sources)}


# -------------------------------------------------------------------------
# corrupt-fixture matrix: each rule fires on its snippet, not its near-miss
# -------------------------------------------------------------------------

RL001_FIRE = {
    "bad.py": """
        import time

        import jax


        def f(x):
            t = time.time()
            print(t)
            return x.item() + x
        g = jax.jit(f)
    """,
}
RL001_MISS = {
    "ok.py": """
        import time

        import jax
        import jax.numpy as jnp


        def f(x):
            return jnp.sum(x)
        g = jax.jit(f)


        def host_logger(x):
            # host-side wrapper around the jitted call — prints are fine
            print(time.time(), g(x).item())
    """,
}

RL002_FIRE = {
    "bad.py": """
        import jax


        def f(x, n):
            if n > 0:
                x = x + 1
            return x
        g = jax.jit(f)
    """,
}
RL002_MISS = {
    "ok.py": """
        import jax


        def f(x, n, kvq=None):
            if kvq is not None:
                x = x * 2
            if n > 0:
                x = x + 1
            return x
        g = jax.jit(f, static_argnums=(1,))
    """,
}

RL003_FIRE = {
    "tel.py": """
        METRIC_FAMILIES = {
            "serve_tokens_total": "tokens",
            "dead_gauge": "never emitted anywhere",
        }
    """,
    "emit.py": """
        def record(reg):
            reg.inc("serve_tokens_total")
            reg.inc("undeclared_total")
    """,
}
RL003_MISS = {
    "tel.py": """
        METRIC_FAMILIES = {
            "serve_tokens_total": "tokens",
            "serve_fill": "gauge",
        }
    """,
    "emit.py": """
        def record(reg, prof, fill):
            reg.inc("serve_tokens_total")
            reg.set_gauge("serve_fill", fill)
            # profiler-session observe is keyed by program name, not a
            # registry family — must not be mistaken for an emit
            prof.observe("decode_step", 0.1)
    """,
}

RL004_FIRE = {
    "trace.py": """
        SPAN_NAMES = {"prefill"}
        EVENT_NAMES = {"submit", "dead_event"}
    """,
    "emit.py": """
        def go(tel, t0, t1):
            tel.span("prefill", t0, t1)
            tel.span("bogus_span", t0, t1)
            tel.event("submit", t0)
    """,
}
RL004_MISS = {
    "trace.py": """
        SPAN_NAMES = {"prefill"}
        EVENT_NAMES = {"submit", "truncated"}

        def export(events):
            # literal record construction counts as the emit site
            return [{"name": "truncated", "dropped": len(events)}]
    """,
    "emit.py": """
        def go(tel, t0, t1):
            tel.span("prefill", t0, t1)
            tel.event("submit", t0)
    """,
}

RL005_FIRE = {
    "serve.py": """
        import argparse


        def build():
            ap = argparse.ArgumentParser()
            ap.add_argument("--covered-flag", type=int, default=None)
            ap.add_argument("--orphan-flag", type=int, default=None)
            return ap


        def validate_flags(args):
            if args.covered_flag is not None and args.covered_flag < 0:
                raise SystemExit("--covered-flag must be >= 0")
    """,
}
RL005_MISS = {
    "serve.py": """
        import argparse

        _MODE_ONLY = ("tuple-flag",)


        def build():
            ap = argparse.ArgumentParser()
            ap.add_argument("--covered-flag", type=int, default=None)
            ap.add_argument("--tuple-flag", type=int, default=None)
            return ap


        def validate_flags(args):
            if args.covered_flag is not None and args.covered_flag < 0:
                raise SystemExit("--covered-flag must be >= 0")
            for f in _MODE_ONLY:
                if getattr(args, f.replace("-", "_")) is not None:
                    raise SystemExit(f)
    """,
}

MATRIX = [
    ("RL001", RL001_FIRE, RL001_MISS),
    ("RL002", RL002_FIRE, RL002_MISS),
    ("RL003", RL003_FIRE, RL003_MISS),
    ("RL004", RL004_FIRE, RL004_MISS),
    ("RL005", RL005_FIRE, RL005_MISS),
]


@pytest.mark.parametrize("rule,fire,miss", MATRIX,
                         ids=[m[0] for m in MATRIX])
def test_rule_fires_on_corrupt_fixture(tmp_path, rule, fire, miss):
    assert rule in _rules_fired(_mini_repo(tmp_path / "f", fire)), \
        f"{rule} must fire on its corrupt fixture"
    assert rule not in _rules_fired(_mini_repo(tmp_path / "m", miss)), \
        f"{rule} must stay silent on its near-miss"


def test_rl004_counts_scope_sites(tmp_path):
    """Telemetry.scope(name) records a span: its name must be declared,
    and a declared span emitted only by a scope is not dead."""
    trace = """
        SPAN_NAMES = {"prefill", "step"}
        EVENT_NAMES = {"submit"}
    """
    emit = """
        def go(tel, t0, t1):
            tel.span("prefill", t0, t1)
            tel.event("submit", t0)
            with tel.scope("step"):
                pass
    """
    assert "RL004" not in _rules_fired(_mini_repo(
        tmp_path / "ok", {"trace.py": trace, "emit.py": emit}))
    bad = emit + """
            with tel.scope("undeclared"):
                pass
    """
    assert "RL004" in _rules_fired(_mini_repo(
        tmp_path / "bad", {"trace.py": trace, "emit.py": bad}))


@pytest.mark.parametrize("rule,fire,_", MATRIX, ids=[m[0] for m in MATRIX])
def test_cli_exits_1_on_corrupt_fixture(tmp_path, rule, fire, _):
    root = _mini_repo(tmp_path, fire)
    assert lint_mod.lint(root, out=io.StringIO()) == 1


def test_rl001_flags_all_forbidden_families(tmp_path):
    root = _mini_repo(tmp_path, RL001_FIRE)
    findings, _ = run_rules(root)
    msgs = " ".join(f.message for f in findings if f.rule == "RL001")
    assert "print()" in msgs
    assert "wall-clock" in msgs
    assert ".item()" in msgs


def test_rl003_reports_both_directions(tmp_path):
    root = _mini_repo(tmp_path, RL003_FIRE)
    findings, _ = run_rules(root)
    symbols = {f.symbol for f in findings if f.rule == "RL003"}
    assert symbols == {"undeclared_total", "dead_gauge"}


# -------------------------------------------------------------------------
# suppression + baseline semantics
# -------------------------------------------------------------------------

def test_suppression_comment_silences_one_rule(tmp_path):
    files = {"bad.py": """
        import jax


        def f(x):
            print(x)  # reprolint: disable=RL001
            return x
        g = jax.jit(f)
    """}
    assert "RL001" not in _rules_fired(_mini_repo(tmp_path, files))
    assert suppressed_lines("x = 1  # reprolint: disable=RL001, RL003") \
        == {1: {"RL001", "RL003"}}


def test_baseline_grandfathers_with_justification(tmp_path):
    root = _mini_repo(tmp_path, RL002_FIRE)
    findings, _ = run_rules(root)
    bl_path = root / "LINT_BASELINE.json"
    entry = {"rule": "RL002", "path": "bad.py", "symbol": "f",
             "why": "intentional: n is host-concrete at every call site"}
    bl_path.write_text(json.dumps({"version": 1, "entries": [entry]}))
    assert lint_mod.lint(root, out=io.StringIO()) == 0

    # an empty justification is itself a lint failure
    entry["why"] = ""
    bl_path.write_text(json.dumps({"version": 1, "entries": [entry]}))
    assert lint_mod.lint(root, out=io.StringIO()) == 1


def test_stale_baseline_entry_fails(tmp_path):
    root = _mini_repo(tmp_path, RL001_MISS)  # clean tree
    (root / "LINT_BASELINE.json").write_text(json.dumps({
        "version": 1,
        "entries": [{"rule": "RL001", "path": "gone.py", "symbol": "f",
                     "why": "the violation this covered was deleted"}],
    }))
    out = io.StringIO()
    assert lint_mod.lint(root, out=out) == 1
    assert "stale" in out.getvalue()


def test_baseline_partition():
    from repro.analysis.findings import Finding
    bl = Baseline(entries=[
        {"rule": "RL001", "path": "a.py", "symbol": "f", "why": "w"}])
    f_old = Finding("RL001", "a.py", 3, "f", "m")
    f_new = Finding("RL001", "b.py", 9, "g", "m")
    new, old, stale = bl.partition([f_old, f_new])
    assert new == [f_new] and old == [f_old] and stale == []


# -------------------------------------------------------------------------
# self-run: the repo itself lints clean against the committed baseline
# -------------------------------------------------------------------------

def test_self_run_zero_nonbaselined_findings():
    out = io.StringIO()
    rc = lint_mod.lint(REPO_ROOT, out=out)
    assert rc == 0, f"repo lint must be clean:\n{out.getvalue()}"


def test_real_violations_are_fixed_not_baselined():
    bl = Baseline.load(REPO_ROOT / "LINT_BASELINE.json")
    for e in bl.entries:
        assert str(e.get("why", "")).strip(), \
            "every committed baseline entry needs a justification"


# -------------------------------------------------------------------------
# Layer-2 predicates (pure text) + compile counting
# -------------------------------------------------------------------------

ALIAS_HEADER = (
    "HloModule jit_step, input_output_alias={ {1}: (12, {}, may-alias), "
    "{2}: (13, {}, may-alias), {3}: (16, {}, may-alias) }, "
    "entry_computation_layout={...}"
)


def test_parse_alias_params():
    assert audit_mod.parse_alias_params(ALIAS_HEADER) == [12, 13, 16]
    assert audit_mod.parse_alias_params("HloModule jit_f") == []


def test_host_callback_detection():
    dirty = 'x = custom-call(), custom_call_target="xla_python_cpu_callback"'
    clean = 'y = custom-call(), custom_call_target="__onednn$matmul"'
    assert audit_mod.host_callback_targets(dirty) == ["xla_python_cpu_callback"]
    assert audit_mod.host_callback_targets(clean) == []


def test_compile_count_tracks_shape_buckets():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x * 2

    f(jnp.zeros(4))
    f(jnp.zeros(4))
    assert audit_mod.compile_count(f) == 1
    f(jnp.zeros(8))
    assert audit_mod.compile_count(f) == 2
    # the Recorder wrapper stays transparent to counting
    rec = audit_mod.Recorder(f, "f")
    rec(jnp.zeros(8))
    assert audit_mod.compile_count(rec) == 2


def test_fused_signature_cpu_fence():
    import jax

    if jax.default_backend() == "tpu":
        assert audit_mod.fused_signature_present("stablehlo.custom_call "
                                                 "@tpu_custom_call")
    else:
        assert audit_mod.fused_signature_present(
            "%0 = stablehlo.optimization_barrier %arg0")
        assert not audit_mod.fused_signature_present("%0 = stablehlo.add")


# -------------------------------------------------------------------------
# one live Layer-2 round (the full kv16/8/4 grid runs in the CI lint lane)
# -------------------------------------------------------------------------

@pytest.mark.slow
def test_audit_one_round_kv4():
    report = audit_mod.run_audit(kv_bits=(4,))
    assert report.ok, "\n" + "\n".join(c.render() for c in report.failures())
    checks = {(c.program, c.check) for c in report.checks}
    # the acceptance surface: donation on the spill/restore scatters, the
    # fused fence, and the remap recompile assertion all actually ran
    assert ("slot_pool.restore_scatter[kv4]", "donation") in checks
    assert ("paged_pool.reattach_scatter[kv4]", "donation") in checks
    assert ("server.decode_step[kv4+fused]", "fused_fence") in checks
    assert ("server.decode_step_paged[kv4]", "recompile") in checks
