"""Repo-specific AST lint rules (``python -m repro.analysis``): the
discipline the engine's architecture depends on but generic linters
cannot see.

Rule catalog (``docs/analysis.md`` has the rationale in full):

``wall-clock-in-trace``
    No ``time.time()`` / ``time.perf_counter()`` / ``datetime.now()``
    inside a traced body: a traced call evaluates ONCE at trace time and
    bakes the timestamp into the compiled program (measure around the
    dispatch, not inside it).
``python-random-in-trace``
    No Python-level ``random.*`` / ``np.random.*`` inside a traced body:
    same trace-once constant-folding, plus it breaks the replayable
    ``jax.random`` key discipline that makes backends bit-comparable.
``static-operand-capture``
    Runtime operands (``lam``/``lr``/``local_h``/``periods``/
    ``participation``) must reach a traced body as ARGUMENTS, never as
    closure captures: a captured Python float is a compile-time
    constant, so every sweep point retraces (the PR-4 lambda bug class).
``span-in-trace``
    No program span (``repro.core.instrument.span`` or
    ``jax.profiler.TraceAnnotation``) inside a traced body: it opens and
    closes once, at trace time, so it would time the tracing, not the
    running (name device stages with ``jax.named_scope`` there instead).
``jit-outside-engine``
    ``jax.jit`` belongs in ``core/engine`` and ``kernels`` (plus
    explicitly waived call sites): stray jits fragment the executor
    caches, dodge the cache-stats accounting strict mode budgets, and
    hide retraces the trace guard cannot see.
``mutable-default-in-frozen-dataclass``
    No mutable literal defaults in frozen dataclasses; plans and configs
    are hashed/compared, and a shared mutable default aliases state
    across instances.
``undonated-carry``
    Engine jits of chunk-carry step functions (``step*`` /
    ``program_state*``) must pass ``donate_argnums``: callers rebind
    ``state = step(...)`` every chunk, so an undonated carry doubles the
    peak state footprint and forces XLA to allocate fresh buffers per
    round instead of updating in place.

Waivers: append ``# analysis: allow(<rule-name>)`` on the offending
line (or the ``def``/``class`` line that owns the body) -- every waiver
is a reviewed, documented exception, greppable as a set.
"""
from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Iterable, List, Optional, Sequence, Set


@dataclasses.dataclass(frozen=True)
class LintFinding:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


# directories whose files may call jax.jit freely (the engine owns the
# executor caches; kernels wrap their own dispatch)
JIT_ALLOWED_PREFIXES = ("src/repro/core/engine/", "src/repro/kernels/")
# jit discipline only binds library code; tests/benchmarks/examples jit
# ad hoc by design (they ARE the call sites being measured)
JIT_RULE_SCOPE_PREFIX = "src/repro/"

WALLCLOCK_CALLS = {
    ("time", "time"), ("time", "perf_counter"), ("time", "monotonic"),
    ("time", "process_time"), ("datetime", "now"), ("datetime", "utcnow"),
}
# host spans: ``instrument.span`` and the profiler annotations it wraps
SPAN_CALLS = {"span", "instrument.span"}
SPAN_ANNOTATIONS = {"TraceAnnotation", "StepTraceAnnotation"}
PYRANDOM_MODULES = {"random"}
NUMPY_RANDOM_ATTR = "random"   # np.random.* inside a traced body
# runtime operands of the schedule engine: these names reaching a traced
# body as free variables (closure captures) instead of arguments is the
# retrace-per-sweep-point bug class
RUNTIME_OPERANDS = {"lam", "lr", "local_h", "periods", "participation",
                    "acceleration"}

# chunk-carry step functions (rebind ``state = step(...)`` per chunk);
# jitting one in the engine without buffer donation doubles the carry's
# peak footprint -- see the ``undonated-carry`` rule
CARRY_STEP_PREFIXES = ("step", "program_state")
# transforms a carry step may be wrapped in on its way into jax.jit
_CARRY_WRAPPERS = {"jax.vmap", "vmap", "shard_map",
                   "jax.experimental.shard_map.shard_map"}

_ALLOW_PREFIX = "# analysis: allow("


def _waivers(source: str) -> dict:
    """line number -> set of waived rule names."""
    out: dict = {}
    for i, line in enumerate(source.splitlines(), start=1):
        idx = line.find(_ALLOW_PREFIX)
        if idx < 0:
            continue
        inner = line[idx + len(_ALLOW_PREFIX):]
        inner = inner.split(")", 1)[0]
        out[i] = {r.strip() for r in inner.split(",") if r.strip()}
    return out


def _call_name(node: ast.AST) -> Optional[str]:
    """Dotted name of a call's function, e.g. ``jax.jit`` -> "jax.jit"."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_expr(node: ast.AST) -> bool:
    """Does this expression produce a jitted/traced transform of a
    function?  Covers ``jax.jit``, ``jit``, ``functools.partial(jax.jit,
    ...)`` and ``jax.jit(f, ...)``."""
    name = _call_name(node)
    if name in ("jax.jit", "jit", "pjit", "jax.pjit"):
        return True
    if isinstance(node, ast.Call):
        fn = _call_name(node.func)
        if fn in ("jax.jit", "jit", "pjit", "jax.pjit"):
            return True
        if fn in ("functools.partial", "partial") and node.args:
            return _is_jit_expr(node.args[0])
    return False


TRACING_TRANSFORMS = {
    "jax.jit", "jit", "jax.pjit", "pjit",
    "jax.vmap", "vmap", "jax.pmap", "pmap",
    "jax.grad", "grad", "jax.value_and_grad", "value_and_grad",
    "jax.lax.scan", "lax.scan", "scan",
    "jax.lax.fori_loop", "lax.fori_loop", "fori_loop",
    "jax.lax.while_loop", "lax.while_loop", "while_loop",
    "jax.lax.cond", "lax.cond",
    "jax.lax.map", "lax.map",
    "shard_map", "jax.experimental.shard_map.shard_map",
    "jax.checkpoint", "jax.remat",
    "pl.pallas_call", "pallas_call",
}


class _Analyzer(ast.NodeVisitor):
    """Single-pass file analyzer.

    Traced-function discovery (two sources, then closure over nesting):
      * decorated defs: ``@jax.jit``, ``@functools.partial(jax.jit, ..)``
      * call sites: a function NAME (or a ``def`` passed by name later)
        appearing as the function/first-arg of a tracing transform --
        ``jax.jit(step)``, ``lax.scan(body, ...)``, ``shard_map(f, ..)``.
    Any ``def`` nested inside a traced def is traced too (it runs under
    the same trace).
    """

    def __init__(self, path: str, tree: ast.Module, source: str):
        self.path = path
        self.tree = tree
        self.waivers = _waivers(source)
        self.findings: List[LintFinding] = []
        self.traced_defs: Set[ast.AST] = set()
        self._def_stack: List[ast.AST] = []
        self._parents: dict = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent

    # -- helpers ---------------------------------------------------------
    def _emit(self, rule: str, node: ast.AST, message: str,
              owner: Optional[ast.AST] = None):
        lines = {getattr(node, "lineno", 0)}
        if owner is not None:
            lines.add(getattr(owner, "lineno", 0))
        for ln in lines:
            if rule in self.waivers.get(ln, ()):
                return
        self.findings.append(
            LintFinding(rule, self.path, getattr(node, "lineno", 0),
                        message))

    # -- traced-def discovery -------------------------------------------
    def collect_traced(self):
        named_defs: dict = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                named_defs.setdefault(node.name, node)
                for dec in node.decorator_list:
                    if _is_jit_expr(dec) or \
                            _call_name(dec) in TRACING_TRANSFORMS or \
                            (isinstance(dec, ast.Call)
                             and _call_name(dec.func) in TRACING_TRANSFORMS):
                        self.traced_defs.add(node)
                    # functools.partial(jax.vmap, ...) style
                    if isinstance(dec, ast.Call) and \
                            _call_name(dec.func) in ("functools.partial",
                                                     "partial") and \
                            dec.args and \
                            _call_name(dec.args[0]) in TRACING_TRANSFORMS:
                        self.traced_defs.add(node)
        # names passed into tracing transforms
        traced_names: Set[str] = set()
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = _call_name(node.func)
            if fn not in TRACING_TRANSFORMS:
                continue
            for arg in node.args[:2]:  # (f, ...) or scan(body, init, ...)
                if isinstance(arg, ast.Name):
                    traced_names.add(arg.id)
                elif isinstance(arg, (ast.Lambda,)):
                    self.traced_defs.add(arg)
        for name in traced_names:
            if name in named_defs:
                self.traced_defs.add(named_defs[name])
        # closure, to a fixed point, over two edges:
        #   * nesting -- a def inside a traced def runs under the trace;
        #   * calls -- a same-file def CALLED from a traced body executes
        #     under the trace too, so its parameters are tracers/operands
        #     there (without this edge, an operand threaded through a
        #     helper's argument list mis-reports as a closure capture)
        calls_in: dict = {}             # def node -> called names
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                continue
            names: Set[str] = set()
            body = node.body if not isinstance(node, ast.Lambda) \
                else [node.body]
            for stmt in body:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Call):
                        cn = _call_name(sub.func)
                        if cn is not None and "." not in cn:
                            names.add(cn)
            calls_in[node] = names
        changed = True
        while changed:
            changed = False
            for node in ast.walk(self.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if node in self.traced_defs:
                    continue
                p = self._parents.get(node)
                while p is not None:
                    if p in self.traced_defs:
                        self.traced_defs.add(node)
                        changed = True
                        break
                    p = self._parents.get(p)
            for caller in list(self.traced_defs):
                for cn in calls_in.get(caller, ()):
                    callee = named_defs.get(cn)
                    if callee is not None and \
                            callee not in self.traced_defs:
                        self.traced_defs.add(callee)
                        changed = True
        return self.traced_defs

    def _owning_def(self, node: ast.AST) -> Optional[ast.AST]:
        p = self._parents.get(node)
        while p is not None:
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                return p
            p = self._parents.get(p)
        return None

    def _in_traced(self, node: ast.AST) -> Optional[ast.AST]:
        d = self._owning_def(node)
        while d is not None:
            if d in self.traced_defs:
                return d
            d = self._owning_def(d)
        return None

    # -- rules -----------------------------------------------------------
    def run(self) -> List[LintFinding]:
        self.collect_traced()
        self._rule_traced_bodies()
        self._rule_jit_location()
        self._rule_frozen_defaults()
        self._rule_undonated_carry()
        return self.findings

    def _rule_traced_bodies(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            owner = self._in_traced(node)
            if owner is None:
                continue
            fn = _call_name(node.func)
            if fn is None:
                continue
            parts = tuple(fn.split("."))
            if len(parts) >= 2 and parts[-2:] in WALLCLOCK_CALLS:
                self._emit(
                    "wall-clock-in-trace", node,
                    f"{fn}() inside a traced body evaluates ONCE at "
                    "trace time (the compiled program reuses the baked "
                    "constant); time around the dispatch instead",
                    owner)
            if fn in SPAN_CALLS or parts[-1] in SPAN_ANNOTATIONS:
                self._emit(
                    "span-in-trace", node,
                    f"{fn}() inside a traced body opens and closes once, "
                    "at trace time, so it times the tracing, not the "
                    "running; span the dispatch on the host, or name the "
                    "device stage with jax.named_scope",
                    owner)
            if parts[0] in PYRANDOM_MODULES or \
                    (len(parts) >= 2 and parts[0] in ("np", "numpy")
                     and parts[1] == NUMPY_RANDOM_ATTR):
                self._emit(
                    "python-random-in-trace", node,
                    f"{fn}() inside a traced body is constant-folded at "
                    "trace time and breaks the replayable jax.random "
                    "key discipline; thread a PRNG key in as an operand",
                    owner)
        # static closure capture of runtime operands.  A load inside a
        # traced def is fine when the nearest enclosing def BINDING the
        # name is itself traced (the value is a tracer/operand there);
        # it is the bug when the binder is a non-traced builder or the
        # module scope -- the value crosses the trace boundary as a
        # baked compile-time constant.
        for sub in ast.walk(self.tree):
            if not (isinstance(sub, ast.Name)
                    and isinstance(sub.ctx, ast.Load)
                    and sub.id in RUNTIME_OPERANDS):
                continue
            owner = self._in_traced(sub)
            if owner is None:
                continue
            binder = None
            d = self._owning_def(sub)
            while d is not None:
                if sub.id in _bound_names(d):
                    binder = d
                    break
                d = self._owning_def(d)
            if binder is not None and binder in self.traced_defs:
                continue
            self._emit(
                "static-operand-capture", sub,
                f"traced body closes over runtime operand {sub.id!r} "
                "from outside the trace: a captured Python value is a "
                "compile-time constant, so every new value retraces "
                "(pass it as an argument; the executors take "
                "lambda/lr/step masks as operands)",
                owner)

    def _rule_jit_location(self):
        norm = self.path.replace("\\", "/")
        anchor = norm.find("src/repro/")
        rel = norm[anchor:] if anchor >= 0 else norm
        if not rel.startswith(JIT_RULE_SCOPE_PREFIX):
            return
        if any(rel.startswith(p) for p in JIT_ALLOWED_PREFIXES):
            return
        decorator_exprs = set()
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    decorator_exprs.add(id(dec))
                    if _is_jit_expr(dec):
                        self._emit(
                            "jit-outside-engine", dec,
                            "bare jax.jit outside core/engine + kernels: "
                            "stray jits fragment the executor caches and "
                            "dodge the cache-stats accounting strict "
                            "mode budgets.  Route through the engine "
                            "executors, or waive with '# analysis: "
                            "allow(jit-outside-engine)' and a reason",
                            node)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and id(node) not in \
                    decorator_exprs and _is_jit_expr(node):
                self._emit(
                    "jit-outside-engine", node,
                    "bare jax.jit outside core/engine + kernels: stray "
                    "jits fragment the executor caches and dodge the "
                    "cache-stats accounting strict mode budgets.  Route "
                    "through the engine executors, or waive with "
                    "'# analysis: allow(jit-outside-engine)' and a "
                    "reason")

    def _rule_undonated_carry(self):
        """Engine-only: a ``jax.jit`` whose jitted function is a
        chunk-carry step (name ``step*`` / ``program_state*``, possibly
        wrapped in ``jax.vmap`` / ``shard_map``) must donate the carry
        via ``donate_argnums`` -- callers rebind ``state = step(...)``
        every chunk, so the previous carry is dead the moment the call
        dispatches and its buffers should be reused in place."""
        norm = self.path.replace("\\", "/")
        anchor = norm.find("src/repro/")
        rel = norm[anchor:] if anchor >= 0 else norm
        if not rel.startswith("src/repro/core/engine/"):
            return

        def _carry_target(arg) -> Optional[str]:
            # unwrap vmap/shard_map layers down to the named function
            while isinstance(arg, ast.Call) and \
                    _call_name(arg.func) in _CARRY_WRAPPERS:
                if not arg.args:
                    return None
                arg = arg.args[0]
            name = _call_name(arg)
            if name is not None and any(
                    name.split(".")[-1].startswith(p)
                    for p in CARRY_STEP_PREFIXES):
                return name
            return None

        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call)
                    and _call_name(node.func) in ("jax.jit", "jit",
                                                  "jax.pjit", "pjit")
                    and node.args):
                continue
            target = _carry_target(node.args[0])
            if target is None:
                continue
            if any(kw.arg == "donate_argnums" for kw in node.keywords):
                continue
            self._emit(
                "undonated-carry", node,
                f"jax.jit of chunk-carry step {target!r} without "
                "donate_argnums: callers rebind state = step(...) every "
                "chunk, so the undonated carry doubles the peak state "
                "footprint (donate the state argument, or waive with "
                "'# analysis: allow(undonated-carry)' and a reason)")

    def _rule_frozen_defaults(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            frozen = False
            for dec in node.decorator_list:
                name = _call_name(dec.func if isinstance(dec, ast.Call)
                                  else dec)
                if name in ("dataclasses.dataclass", "dataclass"):
                    if isinstance(dec, ast.Call):
                        for kw in dec.keywords:
                            if kw.arg == "frozen" and \
                                    isinstance(kw.value, ast.Constant) and \
                                    kw.value.value is True:
                                frozen = True
            if not frozen:
                continue
            for stmt in node.body:
                if not isinstance(stmt, ast.AnnAssign) or stmt.value is None:
                    continue
                if isinstance(stmt.value, (ast.List, ast.Dict, ast.Set)) or \
                        (isinstance(stmt.value, ast.Call)
                         and _call_name(stmt.value.func) in
                         ("list", "dict", "set", "bytearray")):
                    self._emit(
                        "mutable-default-in-frozen-dataclass", stmt,
                        "mutable literal default in a frozen dataclass: "
                        "the object is shared across every instance (and "
                        "frozen classes are hashed/compared as values); "
                        "use dataclasses.field(default_factory=...) or a "
                        "tuple", node)


def _bound_names(fn) -> Set[str]:
    """Names bound in ``fn``'s OWN scope: parameters plus assignments
    directly in its body (nested defs contribute their name, not their
    locals -- matching Python scoping, so a Name not bound here resolves
    to an enclosing scope)."""
    out: Set[str] = set()
    args = fn.args
    for a in (list(args.posonlyargs) + list(args.args)
              + list(args.kwonlyargs)):
        out.add(a.arg)
    if args.vararg:
        out.add(args.vararg.arg)
    if args.kwarg:
        out.add(args.kwarg.arg)
    if isinstance(fn, ast.Lambda):
        return out
    stack = list(fn.body)
    while stack:
        sub = stack.pop()
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.add(sub.name)
            continue  # its locals are its own scope
        if isinstance(sub, ast.Lambda):
            continue
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
            out.add(sub.id)
        stack.extend(ast.iter_child_nodes(sub))
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def lint_file(path: str) -> List[LintFinding]:
    """All rule findings for one Python source file."""
    source = Path(path).read_text()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [LintFinding("syntax-error", path, e.lineno or 0, str(e))]
    return _Analyzer(path, tree, source).run()


def iter_python_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        pp = Path(p)
        if pp.is_file() and pp.suffix == ".py":
            yield str(pp)
        elif pp.is_dir():
            for f in sorted(pp.rglob("*.py")):
                if "__pycache__" in f.parts:
                    continue
                yield str(f)


def lint_paths(paths: Sequence[str]) -> List[LintFinding]:
    """Run every rule over all ``.py`` files under ``paths``."""
    out: List[LintFinding] = []
    for f in iter_python_files(paths):
        out.extend(lint_file(f))
    return out
