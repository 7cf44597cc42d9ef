"""Compile-cost static auditor (analysis layer 4).

Tier-1 is XLA-compile-bound: three full runs in the PR 10 session had
ZERO failing tests yet died rc=124 at the 870s cap from ~9% box drift.
The conftest compile guard catches an over-budget test only *at runtime*,
after the wall has already been paid.  This layer makes compile cost a
statically checked property of the test suite instead: it walks the test
tree and library by AST + a one-level import graph and maps every
**program-materialization site** without executing anything —

- direct ``jax.jit`` wrapper creation + invocation (including
  ``.lower().compile()`` chains and module-level ``j_x = jax.jit(...)``
  wrappers called from tests);
- eager calls of ``@jax.jit``-decorated library functions in
  ``lodestar_tpu/`` (recorded in the map; violation only when the
  runtime ledger corroborates an expensive event, because a shared
  library wrapper compiles once per process and is often sub-threshold);
- ``TpuBlsVerifier`` constructions with *real* (non-stub) programs,
  resolved through stub factories (a helper that assigns into
  ``executor.compiled[...]`` before returning neutralizes the
  construction) and pytest fixtures, plus the drive calls
  (``verify_signature_sets*`` / ``dispatch`` / ``warmup*`` / handing the
  verifier to a ``BlsBatchPool``) that actually materialize programs;
- the per-(entry, bucket) program key each real construction implies,
  derived exactly like ``tpu_verifier._entry_name`` (``fused`` kwarg x
  ``buckets``).

The static map is then cross-checked against the runtime ledgers
(``.jax_cache/tier1_timings.json`` per-test compile-guard events) and
the conftest ``COMPILE_WHITELIST``, emitting four typed violations:

- ``compile-unstubbed-test``    a tier-1 (non-slow) test statically
  reaches a real verifier materialization and is not whitelisted — or
  the runtime ledger records guard events for a test the whitelist does
  not cover.
- ``compile-duplicate-program`` two tier-1 test modules materialize the
  same (entry, bucket) program key (or jit the same library target)
  instead of sharing ``_PROGRAM_MEMO``/AOT artifacts through one module.
- ``compile-whitelist-stale``   a whitelist pattern that matches no
  statically-compiling test (and no ledger-evidenced compile) — dead
  budget that hides future regressions.
- ``tier2-unmarked``            an irreducibly compile-bound test
  (direct jit of a device program) lacking both the ``slow`` marker and
  a whitelist entry.

Everything here is stdlib-only (ast/json/fnmatch): importing this module
never imports jax, so the auditor itself runs inside the tier-1 compile
guard and in tools/lint.py.
"""

from __future__ import annotations

import ast
import fnmatch
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .report import Violation, filter_suppressed

RULE_UNSTUBBED = "compile-unstubbed-test"
RULE_DUPLICATE = "compile-duplicate-program"
RULE_STALE = "compile-whitelist-stale"
RULE_TIER2 = "tier2-unmarked"

# mirrors tpu_verifier.DEFAULT_BUCKETS without importing jax
_DEFAULT_BUCKETS = (4, 16, 64, 128, 256)

# methods whose call on a REAL verifier materializes device programs
_DRIVE_METHODS = {
    "verify_signature_sets",
    "verify_signature_sets_async",
    "dispatch",
    "warmup",
    "warmup_sharded",
    "warmup_async",
}
# constructors that drive a verifier handed to them (the pool exists to
# dispatch batches through it)
_POOL_CTORS = {"BlsBatchPool"}


# ---------------------------------------------------------------------------
# small AST helpers (shared idiom with ast_lint, duplicated here so the
# layer stays importable without the jax-adjacent checkers)
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _root_name(node: ast.AST) -> Optional[str]:
    """Terminal Name at the base of an attribute/subscript/call chain."""
    while True:
        if isinstance(node, ast.Attribute):
            node = node.value
        elif isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Name):
            return node.id
        else:
            return None


def _literal(node: ast.AST):
    try:
        return ast.literal_eval(node)
    except (ValueError, TypeError, SyntaxError):
        return None


# ---------------------------------------------------------------------------
# per-module scan
# ---------------------------------------------------------------------------

@dataclass
class ConstructInfo:
    line: int
    ctor: str
    buckets: Tuple[int, ...]
    entry: str  # xla_split / fused_split
    stubbed: bool = False

    @property
    def keys(self) -> Tuple[str, ...]:
        return tuple(f"{self.entry}@{b}" for b in self.buckets)


@dataclass
class FuncScan:
    """Raw facts about one function/method body (nested defs included)."""

    name: str
    qualname: str  # Class::name for methods
    lineno: int
    is_test: bool = False
    is_fixture: bool = False
    slow: bool = False
    skipif: bool = False
    params: Tuple[str, ...] = ()
    constructs: Dict[str, ConstructInfo] = field(default_factory=dict)
    assigned_calls: Dict[str, Tuple[int, str]] = field(default_factory=dict)
    drives: List[Tuple[str, int, str]] = field(default_factory=list)  # var, line, method
    jit_sites: List[Tuple[int, Optional[str]]] = field(default_factory=list)
    lib_jit_sites: List[Tuple[int, str]] = field(default_factory=list)
    pallas_sites: List[Tuple[int, str]] = field(default_factory=list)
    trace_sites: List[Tuple[int, Optional[str]]] = field(default_factory=list)
    calls: List[Tuple[int, str]] = field(default_factory=list)  # resolved dotted refs
    returns_vars: Set[str] = field(default_factory=set)
    memo_primed: bool = False  # test primes _PROGRAM_MEMO before driving
    # resolved in phase 2:
    materializes: bool = False
    mat_sites: List[Tuple[int, str, Tuple[str, ...]]] = field(default_factory=list)
    # (line, kind, program keys) with kind in jit|verifier|helper|fixture|pallas
    returns_real_verifier: bool = False
    is_stub_factory: bool = False
    real_keys: Set[str] = field(default_factory=set)


@dataclass
class ModuleScan:
    path: str  # repo-relative, e.g. tests/test_foo.py
    dotted: str  # tests.test_foo
    module_slow: bool = False
    funcs: Dict[str, FuncScan] = field(default_factory=dict)  # qualname -> scan
    aliases: Dict[str, str] = field(default_factory=dict)
    verifier_ctors: Set[str] = field(default_factory=set)
    jit_wrappers: Dict[str, Optional[str]] = field(default_factory=dict)
    source: str = ""

    def tests(self) -> List[FuncScan]:
        return [f for f in self.funcs.values() if f.is_test]


def _decorator_names(node, aliases) -> List[str]:
    out = []
    for dec in node.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = _dotted(target)
        if name:
            out.append(_expand_alias(name, aliases))
    return out


def _expand_alias(dotted: str, aliases: Dict[str, str]) -> str:
    head, _, rest = dotted.partition(".")
    if head in aliases:
        return aliases[head] + ("." + rest if rest else "")
    return dotted


def _is_slow_mark(name: str) -> bool:
    return name.endswith("pytest.mark.slow") or name == "pytest.mark.slow"


def _pytestmark_is_slow(value: ast.AST, aliases) -> bool:
    nodes = value.elts if isinstance(value, (ast.List, ast.Tuple)) else [value]
    for n in nodes:
        name = _dotted(n.func if isinstance(n, ast.Call) else n)
        if name and _is_slow_mark(_expand_alias(name, aliases)):
            return True
    return False


def _collect_imports(tree: ast.Module) -> Dict[str, str]:
    aliases: Dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def _entry_for_kwargs(kwargs: Dict[str, object]) -> str:
    """Static twin of tpu_verifier._entry_name: fused=None resolves to
    the XLA path on the CPU backend tier-1 runs on."""
    return "fused_split" if kwargs.get("fused") else "xla_split"


class _BodyScanner(ast.NodeVisitor):
    """Walks one callable body (nested defs inlined — the async
    ``def main()`` inside a test runs via ``asyncio.run``) and records
    raw materialization facts."""

    def __init__(self, mod: ModuleScan, fn: FuncScan,
                 jitted_lib: Dict[str, Set[str]],
                 pallas_lib: Optional[Dict[str, Set[str]]] = None):
        self.mod = mod
        self.fn = fn
        self.jitted_lib = jitted_lib
        self.pallas_lib = pallas_lib or {}
        self.alias_vars: Dict[str, str] = {}  # ex -> v (executor aliases)
        self.local_wrappers: Dict[str, Optional[str]] = {}
        self.aliases: Dict[str, str] = dict(mod.aliases)  # + in-body imports
        self.in_raises = 0  # inside `with pytest.raises(...)`

    # -- helpers ----------------------------------------------------------
    def _resolve(self, node: ast.AST) -> Optional[str]:
        name = _dotted(node)
        return _expand_alias(name, self.aliases) if name else None

    def visit_Import(self, node: ast.Import):
        for a in node.names:
            self.aliases[a.asname or a.name.split(".")[0]] = (
                a.name if a.asname else a.name.split(".")[0]
            )

    def visit_ImportFrom(self, node: ast.ImportFrom):
        if node.module and node.level == 0:
            for a in node.names:
                self.aliases[a.asname or a.name] = f"{node.module}.{a.name}"

    def _verifier_root(self, name: Optional[str]) -> Optional[str]:
        """Follow executor aliases back to the constructed verifier var."""
        seen = set()
        while name is not None and name not in seen:
            seen.add(name)
            if name in self.fn.constructs:
                return name
            name = self.alias_vars.get(name)
        return None

    def _record_jit_creation(self, call: ast.Call) -> Optional[str]:
        if not call.args:
            return None
        return self._resolve(call.args[0])

    # -- statements -------------------------------------------------------
    def visit_Assign(self, node: ast.Assign):
        self._scan_assign(node.targets, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign):
        if node.value is not None:
            self._scan_assign([node.target], node.value)
        self.generic_visit(node)

    def _scan_assign(self, targets, value):
        # stub injection: <chain>.compiled[...] = ...
        for t in targets:
            if (
                isinstance(t, ast.Subscript)
                and isinstance(t.value, ast.Attribute)
                and t.value.attr == "compiled"
            ):
                root = self._verifier_root(_root_name(t.value))
                if root is not None:
                    self.fn.constructs[root].stubbed = True
            # kernel-builder replacement: v._kernel = <fake> means warmup
            # and dispatch build host callables, never XLA programs
            if isinstance(t, ast.Attribute) and t.attr == "_kernel":
                root = self._verifier_root(_root_name(t))
                if root is not None:
                    self.fn.constructs[root].stubbed = True
            # priming the process-level program memo before a warmup
            # serves the stub instead of compiling
            if isinstance(t, ast.Subscript) and _root_name(t) == "_PROGRAM_MEMO":
                self.fn.memo_primed = True
        if isinstance(value, ast.Call):
            resolved = self._resolve(value.func)
            names = [t.id for t in targets if isinstance(t, ast.Name)]
            if resolved in ("jax.jit", "jit"):
                target = self._record_jit_creation(value)
                for n in names:
                    self.local_wrappers[n] = target
                return
            info = self._construct_info(value, resolved)
            if info is not None:
                for n in names:
                    self.fn.constructs[n] = info
                return
            if resolved:
                for n in names:
                    self.fn.assigned_calls[n] = (value.lineno, resolved)
                return
        # plain aliasing: ex = v._executors[0]
        root = _root_name(value)
        if root is not None:
            for t in targets:
                if isinstance(t, ast.Name):
                    self.alias_vars[t.id] = root

    def visit_With(self, node):
        # a helper invoked under pytest.raises is asserted to fail before
        # it can materialize; don't propagate its compile cost
        raises = any(
            isinstance(item.context_expr, ast.Call)
            and (self._resolve(item.context_expr.func) or "").endswith(
                "pytest.raises"
            )
            for item in node.items
        )
        if raises:
            self.in_raises += 1
        self.generic_visit(node)
        if raises:
            self.in_raises -= 1

    visit_AsyncWith = visit_With

    def visit_For(self, node: ast.For):
        # for ex in v._executors: ...
        root = _root_name(node.iter)
        if isinstance(node.target, ast.Name) and root is not None:
            self.alias_vars[node.target.id] = root
        self.generic_visit(node)

    def _construct_info(self, value: ast.Call, resolved) -> Optional["ConstructInfo"]:
        """ConstructInfo when `value` is a verifier construction."""
        ctor = _dotted(value.func)
        if not (resolved and (
            resolved.endswith(".TpuBlsVerifier")
            or (ctor and ctor in self.mod.verifier_ctors)
        )):
            return None
        kwargs = {
            kw.arg: _literal(kw.value)
            for kw in value.keywords
            if kw.arg is not None
        }
        buckets = kwargs.get("buckets")
        if not isinstance(buckets, (tuple, list)):
            buckets = _DEFAULT_BUCKETS
        return ConstructInfo(
            line=value.lineno,
            ctor=ctor or "TpuBlsVerifier",
            buckets=tuple(int(b) for b in buckets),
            entry=_entry_for_kwargs(kwargs),
            # load_only verifiers serve prewarmed AOT executables
            # or degrade — they never backend-compile by contract
            stubbed=kwargs.get("load_only") is True,
        )

    def _record_returned(self, value) -> None:
        if isinstance(value, ast.Name):
            self.fn.returns_vars.add(value.id)
        elif isinstance(value, ast.Call):
            # `return TpuBlsVerifier(...)` — no Assign ever binds it, so
            # synthesize one: factories that construct inline still
            # classify as real-verifier / stub factories
            info = self._construct_info(value, self._resolve(value.func))
            if info is not None:
                var = f"<ret:{value.lineno}>"
                self.fn.constructs[var] = info
                self.fn.returns_vars.add(var)

    def visit_Return(self, node: ast.Return):
        self._record_returned(node.value)
        self.generic_visit(node)

    def visit_Yield(self, node: ast.Yield):
        self._record_returned(node.value)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        resolved = self._resolve(node.func)
        # drive methods on a tracked object: v.verify_signature_sets(...)
        if isinstance(node.func, ast.Attribute):
            method = node.func.attr
            if method in _DRIVE_METHODS:
                base = _root_name(node.func.value)
                if base is not None:
                    self.fn.drives.append((base, node.lineno, method))
        if resolved in ("jax.jit", "jit"):
            parent_compiles = self._jit_chain_compiles(node)
            if parent_compiles or self._is_called_directly(node):
                self.fn.jit_sites.append(
                    (node.lineno, self._record_jit_creation(node))
                )
        elif resolved in ("jax.make_jaxpr", "make_jaxpr"):
            self.fn.trace_sites.append(
                (node.lineno, self._record_jit_creation(node))
            )
        elif resolved is not None:
            head = resolved.rsplit(".", 1)
            if resolved.rsplit(".", 1)[-1] == "pallas_call" or (
                len(head) == 2
                and head[1] in self.pallas_lib.get(head[0], ())
            ):
                self.fn.pallas_sites.append((node.lineno, resolved))
            elif len(head) == 2 and head[1] in self.jitted_lib.get(head[0], ()):
                self.fn.lib_jit_sites.append((node.lineno, resolved))
            elif isinstance(node.func, ast.Name):
                name = node.func.id
                if name in self.local_wrappers or name in self.mod.jit_wrappers:
                    target = self.local_wrappers.get(
                        name, self.mod.jit_wrappers.get(name)
                    )
                    self.fn.jit_sites.append((node.lineno, target))
                elif not self.in_raises:
                    self.fn.calls.append((node.lineno, resolved))
            elif not self.in_raises:
                self.fn.calls.append((node.lineno, resolved))
            # verifier handed to a batch pool counts as a drive
            tail = resolved.rsplit(".", 1)[-1]
            if tail in _POOL_CTORS:
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    if isinstance(arg, ast.Name):
                        self.fn.drives.append(
                            (arg.id, node.lineno, "BlsBatchPool")
                        )
        self.generic_visit(node)

    def _is_called_directly(self, node: ast.Call) -> bool:
        parent = getattr(node, "_cc_parent", None)
        return isinstance(parent, ast.Call) and parent.func is node

    def _jit_chain_compiles(self, node: ast.Call) -> bool:
        """jax.jit(f).lower(args).compile() materializes a program."""
        parent = getattr(node, "_cc_parent", None)
        chain = []
        while isinstance(parent, (ast.Attribute, ast.Call)):
            if isinstance(parent, ast.Attribute):
                chain.append(parent.attr)
            parent = getattr(parent, "_cc_parent", None)
        return "compile" in chain


def _annotate_parents(tree: ast.AST) -> None:
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            child._cc_parent = parent


def scan_module(path: str, repo: str,
                jitted_lib: Dict[str, Set[str]],
                pallas_lib: Optional[Dict[str, Set[str]]] = None,
                ) -> Optional[ModuleScan]:
    rel = os.path.relpath(path, repo)
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=rel)
    except (OSError, SyntaxError):
        return None
    _annotate_parents(tree)
    dotted = rel[:-3].replace(os.sep, ".")
    mod = ModuleScan(path=rel, dotted=dotted, source=source)
    mod.aliases = _collect_imports(tree)

    # local TpuBlsVerifier subclasses are constructors too (the stub
    # fleets subclass the verifier to override dispatch)
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            for base in node.bases:
                name = _dotted(base)
                if name and _expand_alias(name, mod.aliases).endswith(
                    "TpuBlsVerifier"
                ):
                    mod.verifier_ctors.add(node.name)
    mod.verifier_ctors.add("TpuBlsVerifier")

    # module-level facts: pytestmark, jit wrapper assignments
    for node in tree.body:
        if isinstance(node, ast.Assign):
            names = [t.id for t in node.targets if isinstance(t, ast.Name)]
            if "pytestmark" in names and _pytestmark_is_slow(
                node.value, mod.aliases
            ):
                mod.module_slow = True
            if isinstance(node.value, ast.Call):
                resolved = _dotted(node.value.func)
                resolved = (
                    _expand_alias(resolved, mod.aliases) if resolved else None
                )
                if resolved in ("jax.jit", "jit"):
                    target = None
                    if node.value.args:
                        t = _dotted(node.value.args[0])
                        target = _expand_alias(t, mod.aliases) if t else None
                    for n in names:
                        mod.jit_wrappers[n] = target

    def scan_callable(node, class_name=None, class_slow=False):
        qual = f"{class_name}::{node.name}" if class_name else node.name
        decos = _decorator_names(node, mod.aliases)
        fn = FuncScan(
            name=node.name,
            qualname=qual,
            lineno=node.lineno,
            is_test=node.name.startswith("test"),
            is_fixture=any(d.endswith("pytest.fixture") or d == "pytest.fixture"
                           for d in decos),
            slow=mod.module_slow or class_slow
            or any(_is_slow_mark(d) for d in decos),
            skipif=any(".mark.skipif" in d for d in decos),
            params=tuple(a.arg for a in node.args.args if a.arg != "self"),
        )
        scanner = _BodyScanner(mod, fn, jitted_lib, pallas_lib)
        for stmt in node.body:
            scanner.visit(stmt)
        mod.funcs[qual] = fn

    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_callable(node)
        elif isinstance(node, ast.ClassDef):
            cdecos = _decorator_names(node, mod.aliases)
            c_slow = any(_is_slow_mark(d) for d in cdecos)
            for item in node.body:
                if isinstance(item, ast.Assign):
                    names = [
                        t.id for t in item.targets if isinstance(t, ast.Name)
                    ]
                    if "pytestmark" in names and _pytestmark_is_slow(
                        item.value, mod.aliases
                    ):
                        c_slow = True
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    scan_callable(item, class_name=node.name, class_slow=c_slow)
    return mod


# ---------------------------------------------------------------------------
# library scan: which lodestar_tpu functions are @jax.jit-decorated
# ---------------------------------------------------------------------------

def jitted_library_functions(repo: str) -> Dict[str, Set[str]]:
    out: Dict[str, Set[str]] = {}
    lib = os.path.join(repo, "lodestar_tpu")
    for dirpath, dirnames, filenames in os.walk(lib):
        dirnames[:] = [d for d in dirnames if not d.startswith((".", "__"))]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, repo)
            dotted = rel[:-3].replace(os.sep, ".")
            try:
                with open(path, encoding="utf-8") as f:
                    tree = ast.parse(f.read())
            except (OSError, SyntaxError):
                continue
            names: Set[str] = set()
            for node in tree.body:
                if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    d = _dotted(target)
                    if d in ("jax.jit", "jit"):
                        names.add(node.name)
                    elif d in ("partial", "functools.partial") and isinstance(
                        dec, ast.Call
                    ) and dec.args:
                        inner = _dotted(dec.args[0])
                        if inner in ("jax.jit", "jit"):
                            names.add(node.name)
            if names:
                out[dotted] = names
    return out


def pallas_library_functions(repo: str) -> Dict[str, Set[str]]:
    """Module dotted path -> top-level functions that reach a
    ``pl.pallas_call`` (directly, or through a same-module callee).
    Calling one from tier-1 materializes a Mosaic/interpret program
    exactly like a jit site — interpret=True still XLA-compiles the
    discharged kernel on CPU."""
    out: Dict[str, Set[str]] = {}
    lib = os.path.join(repo, "lodestar_tpu")
    for dirpath, dirnames, filenames in os.walk(lib):
        dirnames[:] = [d for d in dirnames if not d.startswith((".", "__"))]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, repo)
            dotted = rel[:-3].replace(os.sep, ".")
            try:
                with open(path, encoding="utf-8") as f:
                    tree = ast.parse(f.read())
            except (OSError, SyntaxError):
                continue
            direct: Set[str] = set()
            callees: Dict[str, Set[str]] = {}
            for node in tree.body:
                if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                called: Set[str] = set()
                for sub in ast.walk(node):
                    if not isinstance(sub, ast.Call):
                        continue
                    d = _dotted(sub.func)
                    if not d:
                        continue
                    if d.rsplit(".", 1)[-1] == "pallas_call":
                        direct.add(node.name)
                    elif "." not in d:
                        called.add(d)
                callees[node.name] = called
            # same-module propagation: fq12_combine_ring_dma ->
            # ring_all_gather -> pallas_call
            changed = True
            while changed:
                changed = False
                for name, refs in callees.items():
                    if name not in direct and refs & direct:
                        direct.add(name)
                        changed = True
            if direct:
                out[dotted] = direct
    return out


# ---------------------------------------------------------------------------
# phase 2: cross-module resolution (import-graph fixpoint)
# ---------------------------------------------------------------------------

def _resolve_modules(mods: Dict[str, ModuleScan]) -> None:
    """Classify helpers (stub factory vs real-verifier factory vs
    materializing) and propagate through calls to a fixpoint, then
    resolve fixture-mediated materialization inside each module."""
    index: Dict[Tuple[str, str], FuncScan] = {}
    for mod in mods.values():
        for fn in mod.funcs.values():
            index[(mod.dotted, fn.name)] = fn
            index[(mod.dotted, fn.qualname)] = fn

    def lookup(ref: str) -> Optional[FuncScan]:
        module, _, name = ref.rpartition(".")
        return index.get((module, name))

    # local classification
    for mod in mods.values():
        for fn in mod.funcs.values():
            returned_constructs = [
                fn.constructs[v] for v in fn.returns_vars if v in fn.constructs
            ]
            if returned_constructs:
                if all(c.stubbed for c in returned_constructs):
                    fn.is_stub_factory = True
                else:
                    fn.returns_real_verifier = True
                    for c in returned_constructs:
                        if not c.stubbed:
                            fn.real_keys.update(c.keys)
            for var, line, method in fn.drives:
                info = fn.constructs.get(var)
                if info is not None and not info.stubbed and not fn.memo_primed:
                    fn.materializes = True
                    fn.mat_sites.append((line, "verifier", info.keys))
            for line, target in fn.jit_sites:
                fn.materializes = True
                fn.mat_sites.append(
                    (line, "jit", (f"jit:{target}",) if target else ())
                )
            for line, target in fn.pallas_sites:
                fn.materializes = True
                fn.mat_sites.append((line, "pallas", (f"pallas:{target}",)))

    # helper factories: v = make_real(); v.verify(...)
    for mod in mods.values():
        for fn in mod.funcs.values():
            real_vars = {}
            for var, (line, ref) in fn.assigned_calls.items():
                # dotted refs resolve cross-module; bare names fall back
                # to the same module (mirrors the fixpoint stage below)
                callee = lookup(ref) or index.get(
                    (mod.dotted, ref.rsplit(".", 1)[-1])
                )
                if callee is not None and callee.returns_real_verifier:
                    real_vars[var] = (line, callee)
            for var, line, method in fn.drives:
                if var in real_vars:
                    fn.materializes = True
                    fn.mat_sites.append(
                        (line, "verifier", tuple(sorted(real_vars[var][1].real_keys)))
                    )

    # call-graph propagation to a fixpoint (helpers calling helpers)
    changed = True
    rounds = 0
    while changed and rounds < len(index) + 2:
        changed = False
        rounds += 1
        for mod in mods.values():
            for fn in mod.funcs.values():
                for line, ref in fn.calls:
                    callee = lookup(ref) or index.get(
                        (mod.dotted, ref.rsplit(".", 1)[-1])
                    )
                    if callee is None or callee is fn:
                        continue
                    if callee.is_stub_factory:
                        continue
                    if callee.materializes and not fn.materializes:
                        fn.materializes = True
                        keys: Tuple[str, ...] = tuple(
                            sorted({k for _, _, ks in callee.mat_sites for k in ks})
                        )
                        fn.mat_sites.append((line, "helper", keys))
                        changed = True

    # fixture-mediated: a test whose param is a real-verifier fixture and
    # that drives it (or whose fixture materializes during setup)
    for mod in mods.values():
        fixtures = {f.name: f for f in mod.funcs.values() if f.is_fixture}
        for fn in mod.funcs.values():
            if not fn.is_test:
                continue
            for param in fn.params:
                fx = fixtures.get(param)
                if fx is None:
                    continue
                if fx.materializes and not fn.materializes:
                    fn.materializes = True
                    keys = tuple(
                        sorted({k for _, _, ks in fx.mat_sites for k in ks})
                    )
                    fn.mat_sites.append((fn.lineno, "fixture", keys))
                if fx.returns_real_verifier:
                    for var, line, method in fn.drives:
                        if var == param:
                            fn.materializes = True
                            fn.mat_sites.append(
                                (line, "verifier",
                                 tuple(sorted(fx.real_keys)))
                            )


# ---------------------------------------------------------------------------
# whitelist + runtime ledger
# ---------------------------------------------------------------------------

def parse_whitelist(repo: str) -> List[Tuple[str, int]]:
    """(pattern, conftest line) pairs from tests/conftest.py's
    COMPILE_WHITELIST, by AST — never imports the conftest."""
    path = os.path.join(repo, "tests", "conftest.py")
    try:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return []
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "COMPILE_WHITELIST"
            for t in node.targets
        ):
            if isinstance(node.value, (ast.Tuple, ast.List)):
                return [
                    (elt.value, elt.lineno)
                    for elt in node.value.elts
                    if isinstance(elt, ast.Constant)
                    and isinstance(elt.value, str)
                ]
    return []


def load_ledger_compiles(repo: str) -> Dict[str, int]:
    """nodeid -> compile-guard event count, merged over the recorded
    FULL tier-1 runs (partial -k subsets say nothing about coverage)."""
    path = os.path.join(repo, ".jax_cache", "tier1_timings.json")
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    try:
        from lodestar_tpu.observatory.run_ledger import TIER1_FULL_RUN_MIN_TESTS
    except Exception:  # pragma: no cover - observatory always importable
        TIER1_FULL_RUN_MIN_TESTS = 400
    merged: Dict[str, int] = {}
    for run in data.get("runs", []):
        if run.get("n_tests", 0) < TIER1_FULL_RUN_MIN_TESTS:
            continue
        for nodeid, count in (run.get("test_compiles") or {}).items():
            merged[nodeid] = max(merged.get(nodeid, 0), int(count))
    return merged


def _whitelisted(nodeid: str, whitelist: Sequence[Tuple[str, int]]) -> bool:
    return any(fnmatch.fnmatch(nodeid, pat) for pat, _ in whitelist)


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

@dataclass
class CompileCostReport:
    modules: Dict[str, ModuleScan]
    whitelist: List[Tuple[str, int]]
    ledger_compiles: Dict[str, int]
    violations: List[Violation]

    def materializing_tests(self) -> Dict[str, List[Tuple[int, str, Tuple[str, ...]]]]:
        out = {}
        for mod in self.modules.values():
            for fn in mod.tests():
                if fn.materializes or fn.lib_jit_sites:
                    out[f"{mod.path}::{fn.qualname}"] = list(fn.mat_sites)
        return out


def build_map(
    repo: Optional[str] = None,
    test_paths: Optional[Sequence[str]] = None,
    whitelist: Optional[Sequence[Tuple[str, int]]] = None,
) -> CompileCostReport:
    """The static map alone (no violations yet): scan + resolve."""
    if repo is None:
        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    jitted = jitted_library_functions(repo)
    pallas = pallas_library_functions(repo)
    if test_paths is None:
        tdir = os.path.join(repo, "tests")
        test_paths = sorted(
            os.path.join(tdir, f)
            for f in os.listdir(tdir)
            if f.startswith("test_") and f.endswith(".py")
        )
        tools_dir = os.path.join(repo, "tools")
        if os.path.isdir(tools_dir):
            test_paths = list(test_paths) + sorted(
                os.path.join(tools_dir, f)
                for f in os.listdir(tools_dir)
                if f.endswith(".py")
            )
    mods: Dict[str, ModuleScan] = {}
    for path in test_paths:
        scan = scan_module(path, repo, jitted, pallas)
        if scan is not None:
            mods[scan.dotted] = scan
    _resolve_modules(mods)
    wl = list(whitelist) if whitelist is not None else parse_whitelist(repo)
    return CompileCostReport(
        modules=mods, whitelist=wl, ledger_compiles={}, violations=[]
    )


def audit_compile_cost(
    repo: Optional[str] = None,
    test_paths: Optional[Sequence[str]] = None,
    whitelist: Optional[Sequence[Tuple[str, int]]] = None,
    use_ledger: bool = True,
) -> List[Violation]:
    if repo is None:
        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    report = build_map(repo, test_paths=test_paths, whitelist=whitelist)
    report.ledger_compiles = load_ledger_compiles(repo) if use_ledger else {}
    v: List[Violation] = []

    test_mods = {
        d: m for d, m in report.modules.items()
        if os.path.basename(m.path).startswith("test_")
    }

    # -- compile-unstubbed-test + tier2-unmarked --------------------------
    for mod in test_mods.values():
        for fn in mod.tests():
            nodeid = f"{mod.path}::{fn.qualname}"
            if fn.slow or fn.skipif or _whitelisted(nodeid, report.whitelist):
                continue
            verifier_sites = [
                s for s in fn.mat_sites
                if s[1] in ("verifier", "fixture", "helper", "pallas")
            ]
            jit_only = [s for s in fn.mat_sites if s[1] == "jit"]
            for line, kind, keys in verifier_sites:
                v.append(Violation(
                    rule=RULE_UNSTUBBED,
                    path=mod.path,
                    line=line,
                    message=(
                        f"{nodeid} statically reaches a real verifier "
                        f"materialization ({kind}"
                        + (f": {', '.join(keys)}" if keys else "")
                        + ") outside the compile whitelist — inject stub "
                        "programs (executor.compiled[key] = ...), ride a "
                        "prewarmed .aot_store load, or mark it slow "
                        "(docs/static_analysis.md#tier-1-budget-discipline)"
                    ),
                ))
            for line, kind, keys in jit_only:
                v.append(Violation(
                    rule=RULE_TIER2,
                    path=mod.path,
                    line=line,
                    message=(
                        f"{nodeid} is irreducibly compile-bound (direct "
                        f"{', '.join(keys) or 'jax.jit'} materialization) but "
                        "carries no `slow` marker and no whitelist entry — "
                        "tier-1 has no compile budget for it; mark it "
                        "@pytest.mark.slow (nightly tier) or whitelist it "
                        "with a budget justification"
                    ),
                ))

    # -- runtime-ledger cross-check --------------------------------------
    static_materializing = set()
    all_tests: Dict[str, Tuple[ModuleScan, FuncScan]] = {}
    for mod in test_mods.values():
        for fn in mod.tests():
            nodeid = f"{mod.path}::{fn.qualname}"
            all_tests[nodeid] = (mod, fn)
            if fn.materializes or fn.lib_jit_sites:
                static_materializing.add(nodeid)
    for nodeid, count in sorted(report.ledger_compiles.items()):
        base = nodeid.split("[", 1)[0]
        if _whitelisted(nodeid, report.whitelist):
            continue
        if base in static_materializing:
            continue
        hit = all_tests.get(base)
        path = hit[0].path if hit else nodeid.split("::", 1)[0]
        line = hit[1].lineno if hit else 0
        v.append(Violation(
            rule=RULE_UNSTUBBED,
            path=path,
            line=line,
            message=(
                f"runtime ledger records {count} compile-guard event(s) for "
                f"{nodeid}, which is neither whitelisted nor statically "
                "mapped as materializing — it compiled under "
                "LODESTAR_TPU_COMPILE_GUARD=0 or through a path the static "
                "map cannot see; stub it or whitelist it"
            ),
        ))

    # -- compile-duplicate-program ---------------------------------------
    key_owners: Dict[str, Dict[str, int]] = {}
    for mod in test_mods.values():
        for fn in mod.tests():
            if fn.slow or fn.skipif:
                continue
            for line, kind, keys in fn.mat_sites:
                for key in keys:
                    owners = key_owners.setdefault(key, {})
                    owners.setdefault(mod.path, line)
    for key, owners in sorted(key_owners.items()):
        if len(owners) < 2:
            continue
        paths = sorted(owners)
        for path in paths[1:]:
            line = owners[path]
            v.append(Violation(
                rule=RULE_DUPLICATE,
                path=path,
                line=line,
                message=(
                    f"program key {key} is materialized by {len(paths)} "
                    f"tier-1 modules ({', '.join(paths)}) — each pays its "
                    "own trace+lower+load; share one module's programs via "
                    "_PROGRAM_MEMO / the AOT store, or stub the extra copy"
                ),
            ))

    # -- compile-whitelist-stale -----------------------------------------
    conftest_rel = os.path.join("tests", "conftest.py")
    for pat, wl_line in report.whitelist:
        alive = False
        for nodeid, (mod, fn) in all_tests.items():
            if not fnmatch.fnmatch(nodeid, pat):
                continue
            if fn.materializes or fn.lib_jit_sites or fn.trace_sites:
                alive = True
                break
        if not alive:
            for nodeid, count in report.ledger_compiles.items():
                if count and fnmatch.fnmatch(nodeid, pat):
                    alive = True
                    break
        if not alive:
            v.append(Violation(
                rule=RULE_STALE,
                path=conftest_rel,
                line=wl_line,
                message=(
                    f"COMPILE_WHITELIST entry {pat!r} matches no "
                    "statically-compiling test and no ledger-recorded "
                    "compile event — dead budget; remove it so a future "
                    "test cannot silently compile under its cover"
                ),
            ))

    source_by_path = {m.path: m.source for m in report.modules.values()}
    conftest_path = os.path.join(repo, conftest_rel)
    try:
        with open(conftest_path, encoding="utf-8") as f:
            source_by_path[conftest_rel] = f.read()
    except OSError:
        pass
    return filter_suppressed(v, source_by_path)
