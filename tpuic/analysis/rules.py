"""The JAX/TPU footgun rules (docs/analysis.md has the catalog).

Every rule is born from a debugging session PRs 1-3 actually paid for:

- TPU1xx — host/device boundary: silent syncs in hot-path modules, the
  recompile hazards (Python branching on tracers, f-strings on traced
  values, jit args that should be static).
- TPU2xx — donation misuse: donated buffers read after the call, and the
  codified PR-2 bisect: ``lax.cond`` inside a donated jit is one
  persistent-compile-cache away from silent buffer corruption.
- TPU3xx — dtype discipline: accidental float64 promotion and
  per-trace ``jnp.array`` construction inside jitted code.
- TPU4xx — PRNG hygiene: key reuse / missing key threading.
- TPU5xx — generic hygiene: unused imports, unreachable code.

The analysis is a single AST pass per module with a *jit context*: a
function counts as jitted when it is decorated with ``jax.jit`` (bare,
called, or via ``partial``) or when any ``jax.jit(<its name>, ...)``
call appears in the module (the ``make_train_step`` idiom — the def and
the wrap are far apart).  Nested defs inherit the context: everything
inside a jitted function traces.

These are heuristics, deliberately precision-biased: a rule that cries
wolf gets suppressed wholesale and protects nothing.  Shape/ndim/dtype
attribute accesses are recognized as static and never flagged.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tpuic.analysis.core import Finding, Severity

# Modules whose per-step loops are latency-critical: a blocking host sync
# here stalls dispatch every step.  Matched by
# path suffix; ``.item()`` / ``jax.device_get`` are flagged anywhere in
# these modules.  The deferred-drain sites inside them carry explicit
# ``# tpuic-ok: TPU101`` suppressions with their rationale — put the
# comment on the ``def`` line to allowlist a whole drain function.
HOT_PATH_SUFFIXES = (
    "tpuic/train/loop.py",
    "tpuic/train/step.py",
    "tpuic/serve/engine.py",
    "tpuic/data/pipeline.py",
    "tpuic/data/device_prep.py",
)

# The per-step loop functions themselves: here even ``float(...)`` /
# ``np.asarray`` are flagged (each is a blocking readback when handed a
# device value).  Nested defs inherit — a drain closure inside
# ``val_epoch`` is still the hot loop.
HOT_LOOP_FUNCS = {
    "tpuic/train/loop.py": {"train_epoch", "_drain_train_log",
                            "val_epoch"},
    "tpuic/serve/engine.py": {"submit", "predict", "_gather", "_dispatch",
                              "_resolve", "_run"},
}

_SYNC_CALLS = {
    "jax.device_get": "blocking device->host transfer",
    "np.asarray": "materializes device arrays on host",
    "np.array": "materializes device arrays on host",
    "numpy.asarray": "materializes device arrays on host",
    "numpy.array": "materializes device arrays on host",
}
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}
_KEY_MAKERS = {"key", "PRNGKey", "split", "fold_in", "wrap_key_data",
               "clone"}


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    severity: Severity
    doc: str


RULES: Dict[str, Rule] = {r.id: r for r in (
    Rule("TPU101", "host-sync-in-hot-path", Severity.ERROR,
         "Host-sync call (.item(), float(), np.asarray, jax.device_get) "
         "in a hot-path module outside an allowlisted deferred-drain "
         "site, or inside jitted code where it breaks tracing."),
    Rule("TPU102", "traced-python-branch", Severity.WARNING,
         "Python control flow (if/while/range) on a traced argument "
         "inside a jitted function: every distinct value retraces — use "
         "lax.cond/lax.select or mark the arg static_argnums."),
    Rule("TPU103", "fstring-on-tracer", Severity.WARNING,
         "f-string interpolating a traced value inside a jitted "
         "function: concretizes (or silently bakes one trace's value)."),
    Rule("TPU201", "donated-buffer-read", Severity.ERROR,
         "Argument donated to a jitted call is read afterwards: the "
         "buffer was surrendered to XLA and may alias the output."),
    Rule("TPU202", "cond-in-donated-jit", Severity.ERROR,
         "lax.cond inside a jit with donate_argnums: with a persistent "
         "compilation cache, cache-deserialized executables corrupt "
         "cond's donated pass-through buffers (PR-2 bisect, jax<=0.4.37 "
         "CPU). Use a jnp.where select or suppress with the measured "
         "rationale."),
    Rule("TPU301", "float64-in-jit", Severity.WARNING,
         "float64 inside jitted code: accidental double promotion "
         "silently doubles HBM/ICI bytes (or truncates under the "
         "default x64-disabled config)."),
    Rule("TPU302", "jnp-array-in-jit", Severity.WARNING,
         "jnp.array(...) construction inside jitted code: builds a "
         "fresh constant every trace — hoist it out of the jit or use "
         "jnp.asarray on an existing array."),
    Rule("TPU401", "prng-key-reuse", Severity.ERROR,
         "The same PRNG key consumed by more than one jax.random "
         "sampling call without split/fold_in between: the draws are "
         "identical, not independent."),
    Rule("TPU501", "unused-import", Severity.WARNING,
         "Imported name never referenced in the module."),
    Rule("TPU502", "dead-code", Severity.WARNING,
         "Statement unreachable after return/raise/break/continue."),
    # -- project-wide passes (analysis/conc.py, spmd.py, contracts.py;
    # these never fire from the per-file lint pass) -------------------
    Rule("CONC101", "lock-order-cycle", Severity.ERROR,
         "Cycle in the project-wide lock-order graph: two threads "
         "taking the cycle's locks in opposite orders deadlock. "
         "Project-level finding, fingerprinted on the sorted edge set."),
    Rule("CONC102", "signal-unsafe-call", Severity.ERROR,
         "Lock acquisition, event-bus publish, or shared file-handle "
         "mutation reachable from a signal-handler registration — the "
         "handler may interrupt the frame that holds the resource "
         "(the PR-8 FlightRecorder deadlock, codified)."),
    Rule("CONC103", "unlocked-shared-closure", Severity.WARNING,
         "threading.Thread target closes over a variable both the "
         "thread and the spawning scope mutate with no common lock."),
    Rule("SPMD101", "rank-divergent-collective", Severity.ERROR,
         "Collective (psum/pmean/all_gather/ppermute/all_to_all/...) "
         "reachable under control flow conditioned on a rank-dependent "
         "value (process_index, TPUIC_FLEET_RANK, rank attrs) — ranks "
         "that skip it hang the fleet at the next sync point."),
    Rule("SPMD102", "collective-order-divergence", Severity.WARNING,
         "Two functions execute the same pair of collectives in "
         "opposite orders — opposite sync-point acquisition orders "
         "across ranks, the collective flavor of CONC101."),
    Rule("CTR101", "event-kind-contract", Severity.ERROR,
         "Every published event kind must be registered in EVENT_KINDS "
         "and every registered kind must have a schema row in "
         "docs/observability.md."),
    Rule("CTR102", "prom-row-contract", Severity.WARNING,
         "Every metric row name emitted by telemetry/prom.py must "
         "appear in docs/observability.md (and stay statically "
         "enumerable so this check can see it)."),
    Rule("CTR103", "exit-code-contract", Severity.ERROR,
         "Supervisor EXIT_* constants must be distinct, never shadowed "
         "in gang.py, never bypassed with raw sys.exit(<int>) "
         "literals, and documented (value + name) in "
         "docs/robustness.md."),
)}


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.cond' for Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _int_elems(node: Optional[ast.AST]) -> Tuple[Set[int], bool]:
    """(literal ints in a donate/static argnums expression, definitely
    empty?).  Non-literal expressions — ``(0,) if donate else ()`` —
    count as 'maybe non-empty' with no known indices."""
    if node is None:
        return set(), True
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}, False
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[int] = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.add(e.value)
        return out, not node.elts
    return set(), False  # dynamic expression: assume maybe-donating


def _str_elems(node: Optional[ast.AST]) -> Set[str]:
    if node is None:
        return set()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        return {e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)}
    return set()


@dataclasses.dataclass
class _JitInfo:
    static_idx: Set[int] = dataclasses.field(default_factory=set)
    static_names: Set[str] = dataclasses.field(default_factory=set)
    donate_idx: Set[int] = dataclasses.field(default_factory=set)
    donates: bool = False


def _jit_call_info(call: ast.Call) -> _JitInfo:
    info = _JitInfo()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            idx, _ = _int_elems(kw.value)
            info.static_idx |= idx
        elif kw.arg == "static_argnames":
            info.static_names |= _str_elems(kw.value)
        elif kw.arg in ("donate_argnums", "donate_argnames"):
            idx, empty = _int_elems(kw.value)
            info.donate_idx |= idx
            if not empty:
                info.donates = True
    return info


def _is_jit_func(node: ast.AST) -> bool:
    d = _dotted(node)
    return d in ("jax.jit", "jit", "pjit", "jax.pjit")


def _decorator_jit(dec: ast.AST) -> Optional[_JitInfo]:
    """_JitInfo when the decorator applies jax.jit, else None."""
    if _is_jit_func(dec):
        return _JitInfo()
    if isinstance(dec, ast.Call):
        if _is_jit_func(dec.func):
            return _jit_call_info(dec)
        d = _dotted(dec.func)
        if d in ("partial", "functools.partial") and dec.args \
                and _is_jit_func(dec.args[0]):
            return _jit_call_info(dec)
    return None


def _param_names(fn) -> List[str]:
    a = fn.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args])


class _Ctx:
    """Jit / hot-loop context threaded through the recursive walk."""

    __slots__ = ("in_jit", "traced", "static", "donates", "hot",
                 "allowed")

    def __init__(self, in_jit=False, traced=frozenset(), static=frozenset(),
                 donates=False, hot=False, allowed=frozenset()):
        self.in_jit = in_jit
        self.traced = traced
        self.static = static
        self.donates = donates
        self.hot = hot            # inside a designated hot-loop function
        self.allowed = allowed    # rules allowlisted on the def line
        # allowed == {"*"} means every rule (bare '# tpuic-ok:')


class Analyzer:
    def __init__(self, tree: ast.Module, path: str, source: str,
                 supp: Optional[Dict] = None) -> None:
        self.tree = tree
        self.path = path.replace("\\", "/")
        self.source = source
        self.findings: List[Finding] = []
        self.hot_path = any(self.path.endswith(s)
                            for s in HOT_PATH_SUFFIXES)
        self.hot_funcs = next((fns for s, fns in HOT_LOOP_FUNCS.items()
                               if self.path.endswith(s)), frozenset())
        if supp is None:  # direct Analyzer use; lint_source passes it in
            from tpuic.analysis.core import suppressions
            supp = suppressions(source)
        self._supp = supp
        # Pre-pass: functions wrapped by name — jax.jit(train_step, ...).
        self.wrapped: Dict[str, _JitInfo] = {}
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call) and _is_jit_func(node.func)
                    and node.args and isinstance(node.args[0], ast.Name)):
                info = _jit_call_info(node)
                prev = self.wrapped.get(node.args[0].id)
                if prev is not None:  # merge multiple wrap sites
                    info.static_idx |= prev.static_idx
                    info.static_names |= prev.static_names
                    info.donate_idx |= prev.donate_idx
                    info.donates = info.donates or prev.donates
                self.wrapped[node.args[0].id] = info

    # -- helpers -----------------------------------------------------------
    def add(self, rule: str, node: ast.AST, message: str,
            ctx: Optional[_Ctx] = None) -> None:
        if ctx is not None and ("*" in ctx.allowed or rule in ctx.allowed):
            return  # def-line function allowlist
        r = RULES[rule]
        self.findings.append(Finding(rule, r.severity, self.path,
                                     getattr(node, "lineno", 1), message))

    def _traced_name_nodes(self, node: ast.AST,
                           traced: frozenset) -> List[ast.Name]:
        """Loads of traced params in ``node``, excluding anything under a
        static attribute access (x.shape, x.ndim, x.dtype, x.size)."""
        hits: List[ast.Name] = []

        def rec(n: ast.AST) -> None:
            if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
                return
            if isinstance(n, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                    for op in n.ops):
                # `x is None` / `"k" in params`: structural tests that
                # never concretize a tracer — the dominant JAX idiom for
                # optional args and pytree membership.
                return
            if isinstance(n, ast.Name) and n.id in traced \
                    and isinstance(n.ctx, ast.Load):
                hits.append(n)
                return
            for c in ast.iter_child_nodes(n):
                rec(c)
        rec(node)
        return hits

    # -- per-module rules --------------------------------------------------
    def run(self) -> List[Finding]:
        self._unused_imports()
        self._walk_block(self.tree.body, _Ctx())
        return self.findings

    def _unused_imports(self) -> None:
        if self.path.endswith("__init__.py"):
            return  # re-export modules: unused-by-design
        imported: List[Tuple[str, ast.AST, str]] = []
        used: Set[str] = set()
        exported: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name.split(".")[0]
                    imported.append((name, node, a.name))
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for a in node.names:
                    if a.name == "*":
                        continue
                    name = a.asname or a.name
                    imported.append((name, node, a.name))
            elif isinstance(node, ast.Name):
                if not isinstance(node.ctx, ast.Store):
                    used.add(node.id)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__all__":
                        exported |= _str_elems(node.value)
        seen: Set[int] = set()
        for name, node, orig in imported:
            if name in used or name in exported or name.startswith("_"):
                continue
            key = (id(node) << 16) ^ hash(name)
            if key in seen:
                continue
            seen.add(key)
            self.add("TPU501", node, f"'{name}' imported but unused")

    # -- the recursive walk ------------------------------------------------
    def _walk_block(self, body: Sequence[ast.stmt], ctx: _Ctx) -> None:
        terminated = False
        for stmt in body:
            if terminated:
                self.add("TPU502", stmt,
                         "unreachable: previous statement always exits "
                         "this block")
                terminated = False  # one finding per dead region
            self._walk_stmt(stmt, ctx)
            if isinstance(stmt, (ast.Return, ast.Raise, ast.Break,
                                 ast.Continue)):
                terminated = True

    def _walk_stmt(self, stmt: ast.stmt, ctx: _Ctx) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._enter_function(stmt, ctx)
            return
        if isinstance(stmt, ast.ClassDef):
            for s in stmt.body:
                self._walk_stmt(s, ctx)
            return
        # Expression-level rules over this statement's OWN expressions
        # (nested statements are walked by their own _walk_stmt calls).
        self._scan_exprs(stmt, ctx)
        if ctx.in_jit and isinstance(stmt, (ast.If, ast.While)):
            hits = self._traced_name_nodes(stmt.test, ctx.traced)
            if hits:
                names = ", ".join(sorted({h.id for h in hits}))
                kw = "while" if isinstance(stmt, ast.While) else "if"
                self.add("TPU102", stmt,
                         f"Python `{kw}` on traced argument(s) {names} "
                         "inside jitted code — retraces per value; use "
                         "lax.cond/jnp.where or static_argnums", ctx)
        # Recurse into child blocks.
        for field in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, field, None)
            if sub:
                self._walk_block(sub, ctx)
        for h in getattr(stmt, "handlers", []) or []:
            self._walk_block(h.body, ctx)

    def _enter_function(self, fn, outer: _Ctx) -> None:
        info = None
        for dec in fn.decorator_list:
            info = _decorator_jit(dec)
            if info is not None:
                break
        if info is None:
            info = self.wrapped.get(fn.name)
        params = _param_names(fn)
        hot = outer.hot or fn.name in self.hot_funcs
        # Def-line allowlist: '# tpuic-ok: TPU101 why' on the def line
        # silences that rule for the whole function body (the drain-site
        # allowlist mechanism).  Inherited by nested defs.
        allowed = set(outer.allowed)
        if fn.lineno in self._supp:
            ids = self._supp[fn.lineno]
            allowed |= {"*"} if ids is None else ids
        if info is not None:
            static = {params[i] for i in info.static_idx
                      if i < len(params)} | info.static_names
            ctx = _Ctx(True, frozenset(p for p in params
                                       if p not in static),
                       frozenset(static),
                       info.donates or bool(info.donate_idx),
                       hot, frozenset(allowed))
        elif outer.in_jit:
            # Nested def inside jitted code traces with the parent; its
            # own params are traced values too (closure-invoked).
            ctx = _Ctx(True, outer.traced | frozenset(params),
                       outer.static, outer.donates, hot,
                       frozenset(allowed))
        else:
            ctx = _Ctx(hot=hot, allowed=frozenset(allowed))
        self._check_key_reuse(fn, ctx)
        self._check_donated_reads(fn, ctx)
        self._walk_block(fn.body, ctx)

    # -- expression-level rules -------------------------------------------
    def _scan_exprs(self, stmt: ast.stmt, ctx: _Ctx) -> None:
        """Check the statement's own expression subtree; recursion stops
        at nested statements (their own _walk_stmt visit covers them), so
        a call nested three blocks deep is reported exactly once."""
        def rec(n: ast.AST) -> None:
            for c in ast.iter_child_nodes(n):
                if isinstance(c, ast.stmt):
                    continue
                self._check_expr(c, ctx)
                rec(c)
        self._check_expr(stmt, ctx)
        rec(stmt)

    def _check_expr(self, node: ast.AST, ctx: _Ctx) -> None:
        if isinstance(node, ast.Call):
            self._check_call(node, ctx)
        elif isinstance(node, ast.JoinedStr) and ctx.in_jit:
            hits = []
            for v in node.values:
                if isinstance(v, ast.FormattedValue):
                    hits += self._traced_name_nodes(v.value, ctx.traced)
            if hits:
                names = ", ".join(sorted({h.id for h in hits}))
                self.add("TPU103", node,
                         f"f-string interpolates traced value(s) "
                         f"{names} inside jitted code", ctx)
        elif ctx.in_jit and isinstance(node, ast.Attribute):
            d = _dotted(node)
            if d in ("jnp.float64", "np.float64", "jax.numpy.float64",
                     "numpy.float64"):
                self.add("TPU301", node,
                         f"{d} inside jitted code — accidental double "
                         "promotion", ctx)
        elif ctx.in_jit and isinstance(node, ast.Constant) \
                and node.value == "float64":
            self.add("TPU301", node,
                     "'float64' dtype literal inside jitted code", ctx)

    def _check_call(self, call: ast.Call, ctx: _Ctx) -> None:
        d = _dotted(call.func)
        # .item() — a blocking scalar sync wherever it appears in a
        # hot-path module, and a trace-breaker inside jit.
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr == "item" and not call.args):
            if ctx.in_jit or ctx.hot:
                self.add("TPU101", call,
                         ".item() is a blocking host sync"
                         + (" inside jitted code" if ctx.in_jit else
                            " inside the hot loop"), ctx)
            return
        if d in _SYNC_CALLS:
            if ctx.in_jit:
                self.add("TPU101", call,
                         f"{d}(): {_SYNC_CALLS[d]} — illegal on tracers "
                         "inside jitted code", ctx)
            elif d == "jax.device_get" and self.hot_path:
                self.add("TPU101", call,
                         "jax.device_get(): blocking device->host "
                         "transfer in a hot-path module; belongs in the "
                         "deferred drain", ctx)
            elif ctx.hot and d != "jax.device_get":
                self.add("TPU101", call,
                         f"{d}(): {_SYNC_CALLS[d]} — a blocking readback "
                         "when handed a device value, inside the hot "
                         "loop", ctx)
            return
        if d == "float" and len(call.args) == 1 \
                and (ctx.in_jit or ctx.hot):
            arg = call.args[0]
            if not isinstance(arg, ast.Constant) and not any(
                    isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS
                    for n in ast.walk(arg)):
                if ctx.in_jit and self._traced_name_nodes(arg, ctx.traced):
                    self.add("TPU101", call,
                             "float() on a traced value concretizes "
                             "(host sync / trace error)", ctx)
                elif not ctx.in_jit:
                    self.add("TPU101", call,
                             "float() forces a blocking scalar readback "
                             "inside the hot loop; defer it to the "
                             "drain site", ctx)
            return
        if ctx.in_jit:
            if d == "range" and self._traced_name_nodes(call, ctx.traced):
                self.add("TPU102", call,
                         "range() over a traced argument inside jitted "
                         "code — concretizes; use lax.fori_loop or "
                         "static_argnums", ctx)
            elif d in ("jnp.array", "jax.numpy.array"):
                self.add("TPU302", call,
                         "jnp.array(...) inside jitted code rebuilds the "
                         "constant every trace — hoist it or use "
                         "jnp.asarray", ctx)
            elif ctx.donates and d in ("jax.lax.cond", "lax.cond"):
                self.add("TPU202", call,
                         "lax.cond inside a donated jit: donated "
                         "pass-through + persistent compile cache "
                         "corrupts buffers (PR-2 bisect); prefer a "
                         "jnp.where select", ctx)

    # -- PRNG key reuse ----------------------------------------------------
    def _check_key_reuse(self, fn, ctx: Optional[_Ctx] = None) -> None:
        """Within ONE function scope (nested defs excluded — exclusive
        cond branches would false-positive), a key name consumed by two
        sampling calls with no rebind between is a reuse."""
        tracked: Set[str] = {p for p in _param_names(fn)
                             if "rng" in p.lower() or "key" in p.lower()}
        own_nodes = self._scope_nodes(fn)
        events: List[Tuple[int, int, str, str, ast.AST]] = []
        for node in own_nodes:
            if isinstance(node, ast.Assign):
                names = [t.id for t in node.targets
                         if isinstance(t, ast.Name)]
                src = node.value
                maker = False
                if isinstance(src, ast.Call):
                    sd = _dotted(src.func) or ""
                    maker = sd.split(".")[-1] in _KEY_MAKERS
                elif isinstance(src, (ast.Subscript, ast.Starred)):
                    maker = True  # keys = split(...); k = keys[0]
                for n in names:
                    if maker or n in tracked:
                        events.append((node.lineno, node.col_offset,
                                       "bind" if maker else "unbind", n,
                                       node))
                        if maker:
                            tracked.add(n)
            elif isinstance(node, ast.Call):
                sd = _dotted(node.func) or ""
                parts = sd.split(".")
                if len(parts) >= 2 and parts[-2] == "random" \
                        and parts[-1] not in _KEY_MAKERS and node.args:
                    a0 = node.args[0]
                    if isinstance(a0, ast.Name):
                        events.append((node.lineno, node.col_offset,
                                       "consume", a0.id, node))
        events.sort(key=lambda e: (e[0], e[1]))
        consumed: Set[str] = set()
        for lineno, _col, kind, name, node in events:
            if kind in ("bind", "unbind"):
                consumed.discard(name)
            elif kind == "consume":
                if name in consumed:
                    self.add("TPU401", node,
                             f"PRNG key '{name}' already consumed by an "
                             "earlier jax.random call — split or fold_in "
                             "before reusing", ctx)
                consumed.add(name)

    def _scope_nodes(self, fn) -> List[ast.AST]:
        """All nodes in fn's body excluding nested function/class bodies."""
        out: List[ast.AST] = []

        def rec(n: ast.AST) -> None:
            out.append(n)
            for c in ast.iter_child_nodes(n):
                if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                    continue
                rec(c)
        for s in fn.body:
            rec(s)
        return out

    # -- donated buffers read after the call -------------------------------
    def _check_donated_reads(self, fn, ctx: Optional[_Ctx] = None) -> None:
        """``f = jax.jit(g, donate_argnums=(0,)); out = f(x); ... x ...``
        — x was surrendered; the later read is the bug."""
        own = self._scope_nodes(fn)
        jitted: Dict[str, Set[int]] = {}
        donated_calls: List[Tuple[int, str]] = []  # (call lineno, arg name)
        for node in own:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and _is_jit_func(node.value.func):
                info = _jit_call_info(node.value)
                if info.donate_idx:
                    jitted[node.targets[0].id] = info.donate_idx
        if not jitted:
            return
        handled: set = set()
        for node in own:
            rebound: Set[str] = set()
            call = None
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                call = node.value
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        rebound.add(t.id)
                    elif isinstance(t, (ast.Tuple, ast.List)):
                        rebound |= {e.id for e in t.elts
                                    if isinstance(e, ast.Name)}
            elif isinstance(node, ast.Call):
                call = node
            if call is None or id(call) in handled \
                    or not isinstance(call.func, ast.Name) \
                    or call.func.id not in jitted:
                continue
            handled.add(id(call))
            end = getattr(call, "end_lineno", call.lineno) or call.lineno
            for i in jitted[call.func.id]:
                if i < len(call.args) and isinstance(call.args[i], ast.Name):
                    name = call.args[i].id
                    if name not in rebound:
                        # `state = step(state, ...)` rebinds the donated
                        # name to the RESULT — the surrendered buffer is
                        # no longer reachable, which is the correct idiom.
                        donated_calls.append((end, name))
        # Static-metadata reads survive donation: `x.dtype` / `x.shape` /
        # `x.ndim` / `x.size` live on the (host-side) array object, not in
        # the surrendered device buffer. The bf16 tier's cast-then-donate
        # sites (`x16 = x.astype(bf16); out = step(x16); log(x16.dtype)`)
        # are the common benign shape — only a VALUE read after donation
        # is the bug.
        static_reads = {
            id(a.value) for a in own
            if isinstance(a, ast.Attribute)
            and isinstance(a.value, ast.Name) and a.attr in _STATIC_ATTRS}
        for call_line, name in donated_calls:
            later = sorted(
                (n for n in own if isinstance(n, ast.Name)
                 and n.id == name and n.lineno > call_line),
                key=lambda n: (n.lineno, n.col_offset))
            for n in later:
                if isinstance(n.ctx, ast.Store):
                    break  # rebound: the old buffer is gone cleanly
                if id(n) in static_reads:
                    continue  # metadata-only read; buffer untouched
                self.add("TPU201", n,
                         f"'{name}' was donated to a jitted call on line "
                         f"{call_line} and is read here — the buffer may "
                         "alias the output", ctx)
                break


def run_rules(tree: ast.Module, path: str, source: str,
              supp: Optional[Dict] = None) -> List[Finding]:
    return Analyzer(tree, path, source, supp=supp).run()
