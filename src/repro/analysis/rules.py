"""Layer-1 AST rules (RL001–RL005) over the serving source tree.

Each rule is a class with a stable ``id``, a one-line ``title``, and a
``run(ctx)`` returning :class:`~repro.analysis.findings.Finding`s.  The
engine parses every ``.py`` file once and hands rules a shared
:class:`RepoContext`; cross-file rules (metric families, trace schema,
launcher flags) locate their declaration sites *within the scanned
tree*, so the corrupt-fixture tests can run the same rules over a
self-contained temporary mini-repo.

Scope notes (documented limits, enforced instead by Layer 2's HLO
audit): RL001/RL002 analyse the function object passed to
``jax.jit``/``shard_map`` plus everything lexically nested inside it —
they do not chase calls into other modules.  The compiled-program
auditor (:mod:`repro.analysis.audit`) covers the transitive closure by
inspecting the lowered HLO of the real serving programs.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path

from repro.analysis.findings import Finding

# ---------------------------------------------------------------------------
# parsing infrastructure


@dataclass
class ParsedFile:
    path: str  # relative to scan root, posix
    source: str
    tree: ast.Module

    # local name -> imported module dotted path ("np" -> "numpy")
    module_aliases: dict = field(default_factory=dict)
    # local name -> (module, original attr) for from-imports
    from_aliases: dict = field(default_factory=dict)

    def resolve(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.module_aliases[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom):
                for a in node.names:
                    self.from_aliases[a.asname or a.name] = (node.module or "", a.name)


@dataclass
class RepoContext:
    root: Path
    files: list  # list[ParsedFile] under the scan root (findings scope)
    extra_sources: dict = field(default_factory=dict)  # path -> raw text (read-only aides)


def parse_tree(root: Path, extra_paths=()) -> RepoContext:
    """Parse every .py under ``root`` (recursively) into a RepoContext."""
    files = []
    for p in sorted(root.rglob("*.py")):
        if "__pycache__" in p.parts:
            continue
        src = p.read_text()
        try:
            tree = ast.parse(src, filename=str(p))
        except SyntaxError:
            continue  # fixtures may hold intentionally-broken snippets
        pf = ParsedFile(path=p.relative_to(root).as_posix(), source=src, tree=tree)
        pf.resolve()
        files.append(pf)
    extras = {}
    for ep in extra_paths:
        ep = Path(ep)
        if ep.exists():
            extras[ep.name] = ep.read_text()
    return RepoContext(root=root, files=files, extra_sources=extras)


def dotted(node) -> str | None:
    """Render a Name/Attribute chain as 'a.b.c', else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# ---------------------------------------------------------------------------
# jit-site discovery (shared by RL001 / RL002)

_JIT_NAMES = {"jax.jit", "jit"}
_SHMAP_NAMES = {"jax.shard_map", "shard_map"}
_PARTIAL_NAMES = {"partial", "functools.partial"}


@dataclass
class JitSite:
    fn: object  # ast.FunctionDef | ast.Lambda
    name: str  # display/symbol name
    file: ParsedFile
    static_params: set = field(default_factory=set)
    via: str = "jax.jit"  # or "shard_map"


def _static_params(call: ast.Call, fn) -> set:
    """Param names marked static via static_argnums/static_argnames."""
    out: set = set()
    if not isinstance(fn, ast.FunctionDef):
        return out
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for kw in call.keywords:
        val = kw.value
        if kw.arg == "static_argnames":
            elts = val.elts if isinstance(val, (ast.Tuple, ast.List)) else [val]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    out.add(e.value)
        elif kw.arg == "static_argnums":
            elts = val.elts if isinstance(val, (ast.Tuple, ast.List)) else [val]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    if 0 <= e.value < len(params):
                        out.add(params[e.value])
    return out


def _defs_by_name(tree: ast.Module) -> dict:
    out: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, []).append(node)
    return out


def collect_jit_sites(pf: ParsedFile) -> list:
    """Find every function object handed to jax.jit / shard_map in a file."""
    sites: list = []
    defs = _defs_by_name(pf.tree)

    def target_of(call: ast.Call):
        """The function expression jitted by this call, unwrapping partial."""
        if not call.args:
            return None
        arg = call.args[0]
        if isinstance(arg, ast.Call):
            inner = dotted(arg.func)
            if inner in _PARTIAL_NAMES and arg.args:
                arg = arg.args[0]
            else:
                return None  # jit(make_step(...)) — unresolvable factory
        return arg

    def add(arg, call: ast.Call, via: str):
        if isinstance(arg, ast.Lambda):
            sites.append(JitSite(fn=arg, name="<lambda>", file=pf, via=via,
                                 static_params=set()))
        elif isinstance(arg, ast.Name):
            for fn in defs.get(arg.id, []):
                sites.append(JitSite(fn=fn, name=fn.name, file=pf, via=via,
                                     static_params=_static_params(call, fn)))

    for node in ast.walk(pf.tree):
        if isinstance(node, ast.Call):
            callee = dotted(node.func)
            if callee in _JIT_NAMES:
                arg = target_of(node)
                if arg is not None:
                    add(arg, node, "jax.jit")
            elif callee in _SHMAP_NAMES:
                arg = target_of(node)
                if arg is not None:
                    add(arg, node, "shard_map")
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                dn = dotted(dec) if not isinstance(dec, ast.Call) else dotted(dec.func)
                if dn in _JIT_NAMES:
                    call = dec if isinstance(dec, ast.Call) else ast.Call(
                        func=dec, args=[], keywords=[])
                    sites.append(JitSite(fn=node, name=node.name, file=pf,
                                         static_params=_static_params(call, node)))
                elif dn in _PARTIAL_NAMES and isinstance(dec, ast.Call) and dec.args:
                    if dotted(dec.args[0]) in _JIT_NAMES:
                        sites.append(JitSite(fn=node, name=node.name, file=pf,
                                             static_params=_static_params(dec, node)))
    # dedupe (a def may be both decorated and referenced)
    seen, uniq = set(), []
    for s in sites:
        k = (id(s.fn), s.via)
        if k not in seen:
            seen.add(k)
            uniq.append(s)
    return uniq


# ---------------------------------------------------------------------------
# RL001 — jit purity


class JitPurityRule:
    """No host-side effects inside functions traced by jit/shard_map."""

    id = "RL001"
    title = "host-side call inside a jitted function"

    _ATTR_CALLS = {"item", "tolist", "block_until_ready"}
    _TEL_METHODS = {"inc", "set_gauge", "observe", "span", "event"}
    _JAX_HOST = {"jax.device_get", "jax.pure_callback", "jax.debug.callback",
                 "jax.experimental.io_callback"}
    _TIME_FNS = {"time", "perf_counter", "monotonic", "process_time"}

    def run(self, ctx: RepoContext):
        findings = []
        for pf in ctx.files:
            np_aliases = {n for n, mod in pf.module_aliases.items() if mod == "numpy"}
            time_aliases = {n for n, mod in pf.module_aliases.items() if mod == "time"}
            time_froms = {n for n, (mod, attr) in pf.from_aliases.items()
                          if mod == "time" and attr in self._TIME_FNS}
            for site in collect_jit_sites(pf):
                for node in ast.walk(site.fn):
                    if not isinstance(node, ast.Call):
                        continue
                    msg = self._check_call(node, np_aliases, time_aliases, time_froms)
                    if msg:
                        findings.append(Finding(
                            rule=self.id, path=pf.path, line=node.lineno,
                            symbol=site.name,
                            message=f"{msg} inside {site.via}-traced "
                                    f"'{site.name}' — policy is strictly "
                                    "host-side (see docs/analysis.md#rl001)"))
        return findings

    def _check_call(self, node, np_aliases, time_aliases, time_froms):
        fn = node.func
        name = dotted(fn)
        if isinstance(fn, ast.Name):
            if fn.id == "print":
                return "print() call"
            if fn.id in time_froms:
                return f"wall-clock read '{fn.id}()'"
        if name in self._JAX_HOST:
            return f"host callback '{name}'"
        if isinstance(fn, ast.Attribute):
            root = fn.value
            if isinstance(root, ast.Name):
                if root.id in time_aliases and fn.attr in self._TIME_FNS:
                    return f"wall-clock read '{root.id}.{fn.attr}()'"
                if root.id in np_aliases:
                    return f"host numpy call '{root.id}.{fn.attr}()'"
            if fn.attr in self._ATTR_CALLS:
                return f"device sync '.{fn.attr}()'"
            if fn.attr in self._TEL_METHODS:
                return f"telemetry record '.{fn.attr}(...)'"
        return None


# ---------------------------------------------------------------------------
# RL002 — traced-branch hazards


class TracedBranchRule:
    """Python if/while on traced arguments inside a jitted body."""

    id = "RL002"
    title = "Python control flow on a traced argument"

    def run(self, ctx: RepoContext):
        findings = []
        for pf in ctx.files:
            for site in collect_jit_sites(pf):
                fn = site.fn
                if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue  # lambdas cannot hold if-statements
                traced = {a.arg for a in fn.args.posonlyargs + fn.args.args
                          + fn.args.kwonlyargs}
                traced -= site.static_params
                traced.discard("self")
                for node in ast.walk(fn):
                    if not isinstance(node, (ast.If, ast.While)):
                        continue
                    bad = self._traced_names(node.test, traced)
                    if bad:
                        kw = "if" if isinstance(node, ast.If) else "while"
                        findings.append(Finding(
                            rule=self.id, path=pf.path, line=node.lineno,
                            symbol=site.name,
                            message=f"Python '{kw}' on traced arg(s) "
                                    f"{sorted(bad)} in jitted '{site.name}' — "
                                    "use lax.cond/select or mark the arg "
                                    "static"))
        return findings

    def _traced_names(self, test, traced):
        """Traced params referenced by a branch test, None-checks exempt."""
        if self._is_none_check(test) or self._is_isinstance(test):
            return set()
        hits = set()
        for node in ast.walk(test):
            if isinstance(node, ast.Name) and node.id in traced:
                hits.add(node.id)
            elif isinstance(node, ast.Call):
                # isinstance(x, T) nested inside a bool op is also exempt
                if self._is_isinstance(node):
                    for sub in ast.walk(node):
                        if isinstance(sub, ast.Name):
                            hits.discard(sub.id)
        return hits

    @staticmethod
    def _is_none_check(test) -> bool:
        if isinstance(test, ast.BoolOp):
            return all(TracedBranchRule._is_none_check(v) for v in test.values)
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return TracedBranchRule._is_none_check(test.operand)
        return (isinstance(test, ast.Compare)
                and all(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops)
                and any(isinstance(c, ast.Constant) and c.value is None
                        for c in [test.left, *test.comparators]))

    @staticmethod
    def _is_isinstance(node) -> bool:
        return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "isinstance")


# ---------------------------------------------------------------------------
# RL003 — metric-family consistency


# Receivers that look like metric emits but are profiler-session wall-time
# observations (serving/profiler.py), not registry families.
_PROFILER_RECEIVERS = {"_prof", "prof", "session", "_session"}


class MetricFamilyRule:
    """Every emit names a declared family; every family has an emit site."""

    id = "RL003"
    title = "metric family not declared / declared but never emitted"

    _EMIT_METHODS = {"inc", "set_gauge", "observe", "counter", "gauge", "histogram"}

    def run(self, ctx: RepoContext):
        declared, decl_pf, decl_line = self._declared(ctx)
        if decl_pf is None:
            return []  # no METRIC_FAMILIES in tree — rule not applicable
        findings, emitted = [], {}
        for pf in ctx.files:
            for node in ast.walk(pf.tree):
                name = self._emit_name(node)
                if name is None:
                    continue
                emitted.setdefault(name, []).append((pf, node.lineno))
        for name, sites in sorted(emitted.items()):
            if name not in declared:
                pf, line = sites[0]
                findings.append(Finding(
                    rule=self.id, path=pf.path, line=line, symbol=name,
                    message=f"metric family '{name}' emitted but not declared "
                            "in METRIC_FAMILIES — declare it (single source "
                            "of truth) or rename the emit"))
        for name in sorted(declared - set(emitted)):
            findings.append(Finding(
                rule=self.id, path=decl_pf.path, line=decl_line.get(name, 1),
                symbol=name,
                message=f"metric family '{name}' declared in METRIC_FAMILIES "
                        "but never emitted anywhere under src/ — dead "
                        "families are errors; delete it or wire the emit"))
        return findings

    def _declared(self, ctx):
        for pf in ctx.files:
            for node in ast.walk(pf.tree):
                if (isinstance(node, ast.Assign)
                        and any(isinstance(t, ast.Name) and t.id == "METRIC_FAMILIES"
                                for t in node.targets)
                        and isinstance(node.value, ast.Dict)):
                    names, lines = set(), {}
                    for k in node.value.keys:
                        if isinstance(k, ast.Constant) and isinstance(k.value, str):
                            names.add(k.value)
                            lines[k.value] = k.lineno
                    return names, pf, lines
        return set(), None, {}

    def _emit_name(self, node):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
            return None
        if node.func.attr not in self._EMIT_METHODS:
            return None
        if not (node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            return None
        # profiler-session observe("decode_step", dt) is a wall-time probe
        # keyed by program name, not a registry family
        recv = node.func.value
        tail = recv.attr if isinstance(recv, ast.Attribute) else (
            recv.id if isinstance(recv, ast.Name) else None)
        if tail in _PROFILER_RECEIVERS:
            return None
        return node.args[0].value


# ---------------------------------------------------------------------------
# RL004 — trace-span/event schema consistency


class TraceSchemaRule:
    """Span/event names must match the v2 validator schema in trace.py."""

    id = "RL004"
    title = "trace span/event name outside the v2 schema"

    def run(self, ctx: RepoContext):
        spans, events, decl_pf, decl_lines = self._schema(ctx)
        if decl_pf is None:
            return []
        findings = []
        span_sites, event_sites = {}, {}
        for pf in ctx.files:
            for node in ast.walk(pf.tree):
                if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                    if (node.args and isinstance(node.args[0], ast.Constant)
                            and isinstance(node.args[0].value, str)):
                        # Telemetry.scope(name) records a span too
                        if node.func.attr in ("span", "scope"):
                            span_sites.setdefault(node.args[0].value, []).append(
                                (pf, node.lineno))
                        elif node.func.attr == "event":
                            event_sites.setdefault(node.args[0].value, []).append(
                                (pf, node.lineno))
                # literal record construction ({"name": "truncated", ...}) in
                # the schema-owning module counts as an emit site
                if pf is decl_pf and isinstance(node, ast.Dict):
                    for k, v in zip(node.keys, node.values):
                        if (isinstance(k, ast.Constant) and k.value == "name"
                                and isinstance(v, ast.Constant)
                                and isinstance(v.value, str)):
                            event_sites.setdefault(v.value, []).append((pf, v.lineno))
                            span_sites.setdefault(v.value, []).append((pf, v.lineno))
        for name, sites in sorted(span_sites.items()):
            if name not in spans and sites[0][0] is not decl_pf:
                pf, line = sites[0]
                findings.append(Finding(
                    rule=self.id, path=pf.path, line=line, symbol=name,
                    message=f"span '{name}' emitted but absent from SPAN_NAMES "
                            "— the v2 trace validator will reject it"))
        for name, sites in sorted(event_sites.items()):
            if name not in events and sites[0][0] is not decl_pf:
                pf, line = sites[0]
                findings.append(Finding(
                    rule=self.id, path=pf.path, line=line, symbol=name,
                    message=f"event '{name}' emitted but absent from "
                            "EVENT_NAMES — the v2 trace validator will "
                            "reject it"))
        for name in sorted(spans - set(span_sites)):
            findings.append(Finding(
                rule=self.id, path=decl_pf.path, line=decl_lines.get(name, 1),
                symbol=name,
                message=f"SPAN_NAMES declares '{name}' but no .span() or "
                        ".scope() site emits it — dead schema entries are "
                        "errors"))
        for name in sorted(events - set(event_sites)):
            findings.append(Finding(
                rule=self.id, path=decl_pf.path, line=decl_lines.get(name, 1),
                symbol=name,
                message=f"EVENT_NAMES declares '{name}' but no .event() site "
                        "emits it — dead schema entries are errors"))
        return findings

    def _schema(self, ctx):
        spans, events, decl_pf, lines = set(), set(), None, {}
        for pf in ctx.files:
            found = False
            for node in ast.walk(pf.tree):
                if not isinstance(node, ast.Assign):
                    continue
                for t in node.targets:
                    if not isinstance(t, ast.Name):
                        continue
                    if t.id in ("SPAN_NAMES", "EVENT_NAMES"):
                        vals = self._set_values(node.value)
                        if vals is None:
                            continue
                        found = True
                        for name, line in vals:
                            lines[name] = line
                            (spans if t.id == "SPAN_NAMES" else events).add(name)
            if found:
                decl_pf = pf
                break
        return spans, events, decl_pf, lines

    @staticmethod
    def _set_values(node):
        if isinstance(node, ast.Set):
            elts = node.elts
        elif (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
              and node.func.id in ("set", "frozenset") and node.args
              and isinstance(node.args[0], (ast.Set, ast.List, ast.Tuple))):
            elts = node.args[0].elts
        else:
            return None
        return [(e.value, e.lineno) for e in elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]


# ---------------------------------------------------------------------------
# RL005 — launcher-flag coverage


class LauncherFlagRule:
    """Every argparse flag is exercised by validate_flags or the launch tests."""

    id = "RL005"
    title = "launcher flag covered by neither validate_flags nor tests"

    def run(self, ctx: RepoContext):
        findings = []
        for pf in ctx.files:
            flags = self._flags(pf)
            validate = self._find_def(pf, "validate_flags")
            if not flags or validate is None:
                continue
            covered = self._coverage(pf, validate)
            test_src = "\n".join(
                src for name, src in ctx.extra_sources.items()
                if name.startswith("test_launch"))
            for dest, (flag, line) in sorted(flags.items()):
                if dest in covered or flag in covered:
                    continue
                if test_src and (flag in test_src or f'"{dest}"' in test_src):
                    continue
                findings.append(Finding(
                    rule=self.id, path=pf.path, line=line, symbol=dest,
                    message=f"flag '{flag}' is referenced by neither "
                            "validate_flags nor the test_launch_serve matrix "
                            "— add a validation rule or a test row"))
        return findings

    def _flags(self, pf):
        out = {}
        for node in ast.walk(pf.tree):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_argument" and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith("--")):
                flag = node.args[0].value
                dest = flag.lstrip("-").replace("-", "_")
                for kw in node.keywords:
                    if kw.arg == "dest" and isinstance(kw.value, ast.Constant):
                        dest = kw.value.value
                out[dest] = (flag, node.lineno)
        return out

    @staticmethod
    def _find_def(pf, name):
        for node in ast.walk(pf.tree):
            if isinstance(node, ast.FunctionDef) and node.name == name:
                return node
        return None

    def _coverage(self, pf, validate):
        covered, referenced_globals = set(), set()
        for node in ast.walk(validate):
            if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
                if node.value.id in ("args", "ns", "flags"):
                    covered.add(node.attr)
            elif isinstance(node, ast.Constant) and isinstance(node.value, str):
                covered.add(node.value)
                covered.add(node.value.lstrip("-").replace("-", "_"))
            elif isinstance(node, ast.Name):
                referenced_globals.add(node.id)
        # module-level string collections read by validate_flags (e.g. the
        # _STATIC_ONLY / _CONTINUOUS_ONLY mode tables)
        for node in ast.walk(pf.tree):
            if not isinstance(node, ast.Assign):
                continue
            for t in node.targets:
                if (isinstance(t, ast.Name) and t.id in referenced_globals
                        and isinstance(node.value, (ast.Tuple, ast.List, ast.Set))):
                    for e in node.value.elts:
                        if isinstance(e, ast.Constant) and isinstance(e.value, str):
                            covered.add(e.value)
                            covered.add(e.value.lstrip("-").replace("-", "_"))
        return covered


ALL_RULES = (JitPurityRule(), TracedBranchRule(), MetricFamilyRule(),
             TraceSchemaRule(), LauncherFlagRule())


def run_rules(scan_root: Path, extra_paths=(), rules=ALL_RULES):
    """Run rules over a tree; returns (findings, {path: source})."""
    ctx = parse_tree(Path(scan_root), extra_paths=extra_paths)
    findings = []
    for rule in rules:
        findings.extend(rule.run(ctx))
    sources = {pf.path: pf.source for pf in ctx.files}
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, sources
