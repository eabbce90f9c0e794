"""repro-lint: AST-based static checker for the repo's cross-file contracts.

Ordinary linters cannot see the stack's real invariants — that a
``StateArrays`` column write must be paired with ``mark_dirty`` (R001,
the PR-8 incremental-root contract), that every kernel-factory op ships
a NumPy semantics-of-record mirror plus device impls pinned by a parity
test (R002), that nothing reachable from the fused record/execute or
digest paths reads the wall clock or unseeded RNG (R003), that jitted
functions stay free of host syncs and traced-value branching (R004),
that ``EventLog`` internals are mutated only by their owner (R005), and
that mempool admission decisions never read the wall clock (R008).
This pass does.

Usage::

    PYTHONPATH=src python -m repro.analysis.lint src/repro [--json out.json]

Findings are machine-readable (file, line, col, rule id, fix hint);
exit status is nonzero iff any unsuppressed finding remains.  Suppress a
line with ``# repro-lint: disable=R001`` (comma-separate several rules)
or a whole file with ``# repro-lint: disable-file=R003``.  The rule
catalog — shared with the runtime sanitizer — lives in
``analysis/invariants.py``; docs/ANALYSIS.md is the human-facing form.

Pure stdlib on purpose: the linter never imports the modules it checks,
so it runs in any environment (CI's repro-lint job) without jax/numpy.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.invariants import (
    ADMISSION_SEED_CLASSES, ADMISSION_SEED_FUNCS, DETERMINISM_SEED_CLASSES,
    DETERMINISM_SEED_FUNCS, EVENTLOG_OWNER_MODULE, MIN_IMPLS_PER_OP,
    REQUIRED_MIRROR_IMPL, STATE_COLUMNS, fix_hint)

# ---------------------------------------------------------------------------
# findings + suppressions


@dataclasses.dataclass(frozen=True)
class Finding:
    """One machine-readable violation."""

    file: str
    line: int
    col: int
    rule: str
    message: str
    hint: str

    def to_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)

    def render(self) -> str:
        return (f"{self.file}:{self.line}:{self.col}: {self.rule} "
                f"{self.message} (hint: {self.hint})")


_SUPPRESS_RE = re.compile(r"#\s*repro-lint:\s*disable=([A-Z0-9, ]+)")
_SUPPRESS_FILE_RE = re.compile(r"#\s*repro-lint:\s*disable-file=([A-Z0-9, ]+)")


@dataclasses.dataclass
class Module:
    """One parsed source file plus its suppression tables."""

    path: str                      # as given (for findings)
    rel: str                       # posix path, for owner-module checks
    tree: ast.Module
    lines: List[str]
    line_suppress: Dict[int, Set[str]]
    file_suppress: Set[str]

    def suppressed(self, rule: str, line: int) -> bool:
        return (rule in self.file_suppress
                or rule in self.line_suppress.get(line, ()))


def _parse_module(path: str) -> Tuple[Optional[Module], Optional[Finding]]:
    with open(path, "r", encoding="utf-8") as fh:
        src = fh.read()
    lines = src.splitlines()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return None, Finding(path, e.lineno or 1, e.offset or 0, "R000",
                             f"syntax error: {e.msg}", "fix the parse error")
    line_sup: Dict[int, Set[str]] = {}
    file_sup: Set[str] = set()
    for i, text in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(text)
        if m:
            line_sup[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
        m = _SUPPRESS_FILE_RE.search(text)
        if m:
            file_sup |= {r.strip() for r in m.group(1).split(",") if r.strip()}
    return Module(path, path.replace(os.sep, "/"), tree, lines,
                  line_sup, file_sup), None


# ---------------------------------------------------------------------------
# shared AST helpers


def _iter_functions(tree: ast.Module):
    """Yield (function_node, enclosing_class_name_or_None), including
    nested functions (tagged with their outermost class, if any)."""
    def walk(node, cls):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                yield from walk(child, child.name)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield child, cls
                yield from walk(child, cls)
            else:
                yield from walk(child, cls)
    yield from walk(tree, None)


def _safe_unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ""


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


# ---------------------------------------------------------------------------
# R001: StateArrays writes paired with mark_dirty

#: ufuncs whose ``.at`` form scatters into a column in place
_SCATTER_UFUNCS = {"add", "subtract", "maximum", "minimum", "multiply"}
#: parameter/local names treated as StateArrays by convention
_STATE_NAMES = {"state", "state_arrays"}


def _r001_state_vars(fn: ast.AST) -> Set[str]:
    """Names bound to a StateArrays inside ``fn`` (annotation or
    construction/attribute provenance), beyond the conventional names."""
    out: Set[str] = set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs):
        if a.annotation is not None and "StateArrays" in _safe_unparse(a.annotation):
            out.add(a.arg)
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not isinstance(tgt, ast.Name):
            continue
        val = node.value
        if isinstance(val, ast.Call):
            f = val.func
            name = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else "")
            if name == "StateArrays":
                out.add(tgt.id)
        elif isinstance(val, ast.Attribute) and val.attr == "state_arrays":
            out.add(tgt.id)
    return out


def _r001_is_state_base(node: ast.AST, state_vars: Set[str]) -> bool:
    if isinstance(node, ast.Name):
        return node.id in _STATE_NAMES or node.id in state_vars
    if isinstance(node, ast.Attribute):
        return node.attr in ("state_arrays", "state")
    return False


def _r001_column_write(node: ast.AST, state_vars: Set[str]):
    """If ``node`` (an assignment target) writes a StateArrays column,
    return (base_key, column); else None."""
    tgt = node
    if isinstance(tgt, ast.Subscript):
        tgt = tgt.value
    if (isinstance(tgt, ast.Attribute) and tgt.attr in STATE_COLUMNS
            and _r001_is_state_base(tgt.value, state_vars)):
        return _safe_unparse(tgt.value), tgt.attr
    return None


def check_r001(mod: Module) -> List[Finding]:
    findings: List[Finding] = []
    for fn, cls in _iter_functions(mod.tree):
        if cls == "StateArrays":        # the class owns its own caches
            continue
        state_vars = _r001_state_vars(fn)
        writes: List[Tuple[str, str, int, int]] = []   # base, col, line, col
        marks: List[Tuple[str, int]] = []              # base, line
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    hit = _r001_column_write(t, state_vars)
                    if hit:
                        writes.append((*hit, node.lineno, node.col_offset))
            elif isinstance(node, ast.AugAssign):
                hit = _r001_column_write(node.target, state_vars)
                if hit:
                    writes.append((*hit, node.lineno, node.col_offset))
            elif isinstance(node, ast.Call):
                f = node.func
                if (isinstance(f, ast.Attribute) and f.attr == "mark_dirty"):
                    marks.append((_safe_unparse(f.value), node.lineno))
                elif (isinstance(f, ast.Attribute) and f.attr == "at"
                      and isinstance(f.value, ast.Attribute)
                      and f.value.attr in _SCATTER_UFUNCS and node.args):
                    # np.add.at(<col expr>, ids, x) scatter form
                    a0 = node.args[0]
                    base = None
                    if (isinstance(a0, ast.Attribute)
                            and a0.attr in STATE_COLUMNS
                            and _r001_is_state_base(a0.value, state_vars)):
                        base = a0.value
                    elif (isinstance(a0, ast.Call)
                          and isinstance(a0.func, ast.Name)
                          and a0.func.id == "getattr" and a0.args
                          and _r001_is_state_base(a0.args[0], state_vars)):
                        base = a0.args[0]
                    if base is not None:
                        writes.append((_safe_unparse(base), "<scatter>",
                                       node.lineno, node.col_offset))
        for base, col, line, colno in writes:
            if any(mb == base and ml >= line for mb, ml in marks):
                continue
            findings.append(Finding(
                mod.path, line, colno, "R001",
                f"write to StateArrays column {col!r} via {base!r} has no "
                f"matching {base}.mark_dirty(...) later in this function",
                fix_hint("R001")))
    return findings


# ---------------------------------------------------------------------------
# R002: kernel-registry completeness


def _repo_root_of(path: str) -> Optional[str]:
    d = os.path.dirname(os.path.abspath(path))
    while True:
        if os.path.isdir(os.path.join(d, "tests")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            return None
        d = parent


_TEST_TEXT_CACHE: Dict[str, str] = {}


def _test_corpus(repo_root: str) -> str:
    """Concatenated text of tests/test_*.py (the parity-test family)."""
    if repo_root in _TEST_TEXT_CACHE:
        return _TEST_TEXT_CACHE[repo_root]
    chunks: List[str] = []
    tdir = os.path.join(repo_root, "tests")
    for base, _dirs, files in os.walk(tdir):
        for f in sorted(files):
            if f.startswith("test_") and f.endswith(".py"):
                with open(os.path.join(base, f), encoding="utf-8") as fh:
                    chunks.append(fh.read())
    _TEST_TEXT_CACHE[repo_root] = "\n".join(chunks)
    return _TEST_TEXT_CACHE[repo_root]


def check_r002(mods: Sequence[Module]) -> List[Finding]:
    # op -> (impls, first registration site)
    regs: Dict[str, Tuple[Set[str], Tuple[Module, int, int]]] = {}
    for mod in mods:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else "")
            if name != "register_kernel" or len(node.args) < 2:
                continue
            op, impl = _const_str(node.args[0]), _const_str(node.args[1])
            if op is None or impl is None:
                continue
            impls, site = regs.setdefault(
                op, (set(), (mod, node.lineno, node.col_offset)))
            impls.add(impl)
    findings: List[Finding] = []
    for op, (impls, (mod, line, col)) in sorted(regs.items()):
        if REQUIRED_MIRROR_IMPL not in impls:
            findings.append(Finding(
                mod.path, line, col, "R002",
                f"kernel op {op!r} has no {REQUIRED_MIRROR_IMPL!r} "
                f"semantics-of-record mirror (impls: {sorted(impls)})",
                fix_hint("R002")))
        if len(impls) < MIN_IMPLS_PER_OP:
            findings.append(Finding(
                mod.path, line, col, "R002",
                f"kernel op {op!r} registers only {sorted(impls)}; the "
                f"factory contract is the {REQUIRED_MIRROR_IMPL!r} mirror "
                f"plus a device impl (>= {MIN_IMPLS_PER_OP} impls per op)",
                fix_hint("R002")))
        root = _repo_root_of(mod.path)
        if root is not None and op not in _test_corpus(root):
            findings.append(Finding(
                mod.path, line, col, "R002",
                f"kernel op {op!r} has no parity test: no tests/test_*.py "
                f"file mentions it",
                fix_hint("R002")))
    return findings


# ---------------------------------------------------------------------------
# R003: determinism on fused-replay / digest paths


def _called_names(fn: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name):
                out.add(f.id)
            elif isinstance(f, ast.Attribute):
                out.add(f.attr)
    return out


def _reach(mods: Sequence[Module], seed_classes: Sequence[str],
           seed_funcs: Sequence[str]) -> List[Tuple[Module, ast.AST]]:
    """Functions reachable from the seeds, BFS over simple-name call
    edges (conservative: a matching name anywhere in the scan set counts
    as an edge).  Shared by the R003 and R008 sweeps."""
    index: Dict[str, List[Tuple[Module, ast.AST, Optional[str]]]] = {}
    seeds: List[Tuple[Module, ast.AST]] = []
    for mod in mods:
        for fn, cls in _iter_functions(mod.tree):
            index.setdefault(fn.name, []).append((mod, fn, cls))
            if cls in seed_classes or fn.name in seed_funcs:
                seeds.append((mod, fn))
    # AST nodes hash by identity, so plain node sets give the identity
    # bookkeeping without id() (rule R003 applies to this file too)
    reachable: Set[ast.AST] = set()
    frontier = list(seeds)
    reach_list: List[Tuple[Module, ast.AST]] = []
    while frontier:
        mod, fn = frontier.pop()
        if fn in reachable:
            continue
        reachable.add(fn)
        reach_list.append((mod, fn))
        for name in _called_names(fn):
            for tmod, tfn, _cls in index.get(name, ()):
                if tfn not in reachable:
                    frontier.append((tmod, tfn))
    return reach_list


def _wallclock_findings(mod: Module, fn: ast.AST, rule: str) -> List[Finding]:
    """time.time/datetime.now-family calls inside ``fn``, as ``rule``."""
    findings: List[Finding] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not isinstance(f, ast.Attribute):
            continue
        chain = _safe_unparse(f)
        base = f.value
        where = f"on a path reachable from {fn.name!r}"
        if (isinstance(base, ast.Name) and base.id == "time"
                and f.attr in ("time", "time_ns", "perf_counter",
                               "monotonic", "clock")):
            findings.append(Finding(
                mod.path, node.lineno, node.col_offset, rule,
                f"wall-clock read {chain}() {where}", fix_hint(rule)))
        elif f.attr in ("now", "utcnow", "today") and "datetime" in chain:
            findings.append(Finding(
                mod.path, node.lineno, node.col_offset, rule,
                f"wall-clock read {chain}() {where}", fix_hint(rule)))
    return findings


def check_r003(mods: Sequence[Module]) -> List[Finding]:
    findings: List[Finding] = []
    reach_list = _reach(mods, DETERMINISM_SEED_CLASSES,
                        DETERMINISM_SEED_FUNCS)
    for mod, fn in reach_list:
        findings.extend(_wallclock_findings(mod, fn, "R003"))
        has_stdlib_random = any(
            isinstance(n, ast.Import) and any(a.name == "random" for a in n.names)
            for n in ast.walk(mod.tree))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            where = f"on a path reachable from {fn.name!r}"
            if isinstance(f, ast.Attribute):
                chain = _safe_unparse(f)
                base = f.value
                if chain.startswith(("np.random.", "numpy.random.")):
                    if f.attr != "default_rng":
                        findings.append(Finding(
                            mod.path, node.lineno, node.col_offset, "R003",
                            f"unseeded global RNG {chain}() {where}",
                            fix_hint("R003")))
                    elif not node.args and not node.keywords:
                        findings.append(Finding(
                            mod.path, node.lineno, node.col_offset, "R003",
                            f"{chain}() without a seed {where}",
                            fix_hint("R003")))
                elif (has_stdlib_random and isinstance(base, ast.Name)
                      and base.id == "random"):
                    findings.append(Finding(
                        mod.path, node.lineno, node.col_offset, "R003",
                        f"stdlib random.{f.attr}() {where}", fix_hint("R003")))
            elif isinstance(f, ast.Name) and f.id == "id" and len(node.args) == 1:
                findings.append(Finding(
                    mod.path, node.lineno, node.col_offset, "R003",
                    f"id()-based keying/ordering {where} is process-"
                    f"nondeterministic", fix_hint("R003")))
    return findings


# ---------------------------------------------------------------------------
# R008: admission-path purity (no wall clock in mempool decisions)


def check_r008(mods: Sequence[Module]) -> List[Finding]:
    """Admission decisions are pure functions of (spec, sender state,
    pool state) on MODELED time: nothing reachable from the admission
    seeds (``AdmissionController``/``PendingPool``) may read the wall
    clock — the recorded admission log would stop replaying to the same
    admitted set."""
    findings: List[Finding] = []
    for mod, fn in _reach(mods, ADMISSION_SEED_CLASSES,
                          ADMISSION_SEED_FUNCS):
        findings.extend(_wallclock_findings(mod, fn, "R008"))
    return findings


# ---------------------------------------------------------------------------
# R004: jit hygiene

#: attribute reads on a traced array that are static metadata, not values
_STATIC_ATTRS = {"dtype", "shape", "ndim", "size"}


def _jit_like(call: ast.Call) -> Optional[str]:
    """'jit'/'vmap'/'scan' if ``call`` wraps a function for tracing."""
    f = call.func
    name = f.id if isinstance(f, ast.Name) else (
        f.attr if isinstance(f, ast.Attribute) else "")
    if name in ("jit", "vmap"):
        return name
    if name == "scan" and isinstance(f, ast.Attribute) and \
            _safe_unparse(f).endswith("lax.scan"):
        return "scan"
    if name == "partial" and call.args:
        inner = _safe_unparse(call.args[0])
        if inner in ("jit", "jax.jit", "vmap", "jax.vmap"):
            return "jit"
    return None


@dataclasses.dataclass
class _TracedFn:
    node: ast.AST                       # FunctionDef or Lambda
    static_names: Set[str]              # params excluded via static_arg*
    skip_branch_check: bool             # static spec we could not resolve


def _static_param_names(fn: ast.AST, call: Optional[ast.Call]):
    """Resolve static_argnums/static_argnames of ``call`` against ``fn``'s
    positional params.  Returns (names, unresolvable)."""
    if call is None:
        return set(), False
    names: Set[str] = set()
    pos = [a.arg for a in fn.args.posonlyargs + fn.args.args] \
        if not isinstance(fn, ast.Lambda) else [a.arg for a in fn.args.args]
    for kw in call.keywords:
        if kw.arg not in ("static_argnums", "static_argnames"):
            continue
        vals = kw.value.elts if isinstance(kw.value, ast.Tuple) else [kw.value]
        for v in vals:
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                if 0 <= v.value < len(pos):
                    names.add(pos[v.value])
            elif isinstance(v, ast.Constant) and isinstance(v.value, str):
                names.add(v.value)
            else:
                return names, True
    return names, False


def _collect_traced(mod: Module) -> List[_TracedFn]:
    fns: Dict[str, ast.AST] = {}
    for fn, _cls in _iter_functions(mod.tree):
        fns[fn.name] = fn
    traced: List[_TracedFn] = []
    seen: Set[ast.AST] = set()

    def add(fn, call):
        if fn in seen:
            return
        seen.add(fn)
        static, unresolved = _static_param_names(fn, call)
        traced.append(_TracedFn(fn, static, unresolved))

    for fn, _cls in _iter_functions(mod.tree):
        for dec in fn.decorator_list:
            text = _safe_unparse(dec)
            if re.search(r"\b(jit|vmap)\b", text):
                add(fn, dec if isinstance(dec, ast.Call) else None)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        kind = _jit_like(node)
        if kind is None:
            continue
        # partial(jit, ...)(f) or jit(f)/vmap(f)/lax.scan(f, ...): the
        # wrapped callable is the first positional arg that is not the
        # inner `jit` of a partial
        args = node.args[1:] if (isinstance(node.func, ast.Name)
                                 and node.func.id == "partial") else node.args
        if not args:
            continue
        target = args[0]
        if isinstance(target, ast.Name) and target.id in fns:
            add(fns[target.id], node)
        elif isinstance(target, ast.Lambda):
            add(target, node)
    return traced


def check_r004(mod: Module) -> List[Finding]:
    findings: List[Finding] = []
    for tf in _collect_traced(mod):
        fn = tf.node
        if isinstance(fn, ast.Lambda):
            params = {a.arg for a in fn.args.args}
            body_nodes = [fn.body]
            fname = "<lambda>"
        else:
            params = {a.arg for a in
                      fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs}
            body_nodes = fn.body
            fname = fn.name
        params -= tf.static_names
        traced_params = params - {"self", "cls"}

        def traced_use(expr) -> Optional[ast.Name]:
            """A bare load of a traced param that is not static metadata."""
            static_heads: Set[ast.AST] = set()
            for n in ast.walk(expr):
                if (isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS
                        and isinstance(n.value, ast.Name)):
                    static_heads.add(n.value)
                elif (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                      and n.func.id in ("len", "isinstance", "getattr",
                                        "hasattr", "type")):
                    for sub in ast.walk(n):
                        if isinstance(sub, ast.Name):
                            static_heads.add(sub)
            for n in ast.walk(expr):
                if (isinstance(n, ast.Name) and n.id in traced_params
                        and n not in static_heads):
                    return n
            return None

        for body in body_nodes:
            for node in ast.walk(body):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item" and not node.args):
                    findings.append(Finding(
                        mod.path, node.lineno, node.col_offset, "R004",
                        f".item() host sync inside traced function {fname!r}",
                        fix_hint("R004")))
                elif (isinstance(node, ast.Call)
                      and isinstance(node.func, ast.Name)
                      and node.func.id in ("float", "int", "bool")
                      and len(node.args) == 1):
                    hit = traced_use(node.args[0])
                    if hit is not None:
                        findings.append(Finding(
                            mod.path, node.lineno, node.col_offset, "R004",
                            f"{node.func.id}({hit.id}) concretizes a traced "
                            f"value inside {fname!r}", fix_hint("R004")))
                elif (isinstance(node, (ast.If, ast.While))
                      and not tf.skip_branch_check):
                    hit = traced_use(node.test)
                    if hit is not None:
                        findings.append(Finding(
                            mod.path, node.lineno, node.col_offset, "R004",
                            f"Python branching on traced value {hit.id!r} "
                            f"inside {fname!r}", fix_hint("R004")))
    findings.extend(_check_donated_reuse(mod))
    return findings


def _check_donated_reuse(mod: Module) -> List[Finding]:
    """Reuse of a buffer after passing it at a donate_argnums position."""
    donated: Dict[str, Set[int]] = {}       # jitted-callable name -> positions
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt, val = node.targets[0], node.value
        if not (isinstance(tgt, ast.Name) and isinstance(val, ast.Call)
                and _jit_like(val) == "jit"):
            continue
        for kw in val.keywords:
            if kw.arg != "donate_argnums":
                continue
            vals = kw.value.elts if isinstance(kw.value, ast.Tuple) \
                else [kw.value]
            pos = {v.value for v in vals
                   if isinstance(v, ast.Constant) and isinstance(v.value, int)}
            if pos:
                donated[tgt.id] = pos
    if not donated:
        return []
    findings: List[Finding] = []
    for fn, _cls in _iter_functions(mod.tree):
        # names rebound by an assignment, per line: `x, s = f(p, s)` with s
        # donated is the legal donate-and-rebind idiom, not a reuse
        rebound: Dict[int, Set[str]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            rebound.setdefault(node.lineno, set()).add(n.id)
        handed: List[Tuple[str, int]] = []  # (buffer name, donation line)
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                    and node.func.id in donated):
                for i in donated[node.func.id]:
                    if (i < len(node.args)
                            and isinstance(node.args[i], ast.Name)
                            and node.args[i].id
                            not in rebound.get(node.lineno, ())):
                        handed.append((node.args[i].id, node.lineno))
        for buf, after in handed:
            for node in ast.walk(fn):
                if (isinstance(node, ast.Name) and node.id == buf
                        and isinstance(node.ctx, ast.Load)
                        and node.lineno > after):
                    findings.append(Finding(
                        mod.path, node.lineno, node.col_offset, "R004",
                        f"buffer {buf!r} used after being donated at line "
                        f"{after} (donate_argnums)", fix_hint("R004")))
                    break
    return findings


# ---------------------------------------------------------------------------
# R005: EventLog internals owned by core/events.py

_LIST_MUTATORS = {"append", "extend", "insert", "pop", "remove",
                  "clear", "sort", "reverse"}


def check_r005(mod: Module) -> List[Finding]:
    if mod.rel.endswith(EVENTLOG_OWNER_MODULE):
        return []
    findings: List[Finding] = []
    for fn, _cls in _iter_functions(mod.tree):
        aliases: Set[str] = set()
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Attribute)
                    and node.value.attr == "_events"):
                aliases.add(node.targets[0].id)

        def events_obj(expr) -> bool:
            if isinstance(expr, ast.Attribute) and expr.attr == "_events":
                return True
            return isinstance(expr, ast.Name) and expr.id in aliases

        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    base = t.value if isinstance(t, ast.Subscript) else t
                    if events_obj(base) and not (
                            isinstance(t, ast.Name) and isinstance(
                                node, ast.Assign)):
                        findings.append(Finding(
                            mod.path, node.lineno, node.col_offset, "R005",
                            "direct mutation of EventLog._events outside "
                            "core/events.py", fix_hint("R005")))
            elif isinstance(node, ast.Call):
                f = node.func
                if (isinstance(f, ast.Attribute)
                        and f.attr in _LIST_MUTATORS and events_obj(f.value)):
                    findings.append(Finding(
                        mod.path, node.lineno, node.col_offset, "R005",
                        f"_events.{f.attr}(...) outside core/events.py",
                        fix_hint("R005")))
                elif (isinstance(f, ast.Attribute) and f.attr == "__setattr__"
                      and isinstance(f.value, ast.Name)
                      and f.value.id == "object" and len(node.args) >= 2
                      and _const_str(node.args[1]) in ("seq", "time")):
                    findings.append(Finding(
                        mod.path, node.lineno, node.col_offset, "R005",
                        f"object.__setattr__(_, {_const_str(node.args[1])!r}, "
                        f"...) renumbers an event outside core/events.py",
                        fix_hint("R005")))
    return findings


# ---------------------------------------------------------------------------
# driver


def _collect_files(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for base, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", ".git")]
            out.extend(os.path.join(base, f)
                       for f in sorted(files) if f.endswith(".py"))
    return sorted(set(out))


def scan(paths: Sequence[str]) -> Tuple[List[Finding], int]:
    """Lint ``paths``; returns (unsuppressed findings, n_suppressed)."""
    mods: List[Module] = []
    findings: List[Finding] = []
    for path in _collect_files(paths):
        mod, err = _parse_module(path)
        if err is not None:
            findings.append(err)
            continue
        mods.append(mod)
    by_path = {m.path: m for m in mods}
    for mod in mods:
        findings.extend(check_r001(mod))
        findings.extend(check_r004(mod))
        findings.extend(check_r005(mod))
    findings.extend(check_r002(mods))
    findings.extend(check_r003(mods))
    findings.extend(check_r008(mods))
    # dedupe by site+rule (several R003 seeds can reach one call site)
    seen_sites: Set[Tuple[str, int, int, str]] = set()
    unique: List[Finding] = []
    for f in findings:
        site = (f.file, f.line, f.col, f.rule)
        if site not in seen_sites:
            seen_sites.add(site)
            unique.append(f)
    findings = unique
    kept: List[Finding] = []
    n_sup = 0
    for f in findings:
        mod = by_path.get(f.file)
        if f.rule != "R000" and mod is not None and mod.suppressed(f.rule, f.line):
            n_sup += 1
        else:
            kept.append(f)
    kept.sort(key=lambda f: (f.file, f.line, f.rule))
    return kept, n_sup


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro-lint",
        description="invariant-aware static checker (rules R001-R005 + "
                    "R008; see docs/ANALYSIS.md)")
    ap.add_argument("paths", nargs="+", help="files or directories to lint")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="also write machine-readable findings to FILE")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-finding output (exit status only)")
    ns = ap.parse_args(argv)
    findings, n_sup = scan(ns.paths)
    if not ns.quiet:
        for f in findings:
            print(f.render())
        print(f"repro-lint: {len(findings)} finding(s)"
              f" ({n_sup} suppressed)", file=sys.stderr)
    if ns.json:
        with open(ns.json, "w", encoding="utf-8") as fh:
            json.dump({"version": 1,
                       "n_findings": len(findings),
                       "n_suppressed": n_sup,
                       "findings": [f.to_dict() for f in findings]}, fh,
                      indent=2)
            fh.write("\n")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
