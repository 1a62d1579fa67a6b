"""JAX tracing-hazard rules.

Two passes over the project:

* **Pass A** (`collect_jit_registry`) walks every module and records which
  functions are jit-compiled — via ``jax.jit(fn, ...)`` calls (including
  ``jax.jit(partial(fn, bound...))`` and ``self.x = jax.jit(...)``) and via
  ``@functools.partial(jax.jit, ...)`` decorators — along with how many
  leading positional parameters are bound by ``partial`` (those are trace-time
  constants, not tracers) and which parameters are static.
* **Pass B** (`check_module`) runs the per-file rules, using the registry to
  analyse the *bodies* of jitted functions for host syncs and to taint values
  returned by jitted calls at the call site.

Rules emitted here:

``jit-host-sync``          host transfer (``np.asarray``/``float``/``.item``…)
                           on a traced value inside a jitted function
``jit-if-on-tracer``       python ``if`` on a traced value inside a jitted
                           function (``is None`` tests are exempt)
``host-sync-in-loop``      device fetch inside a python loop on the host side
``jit-in-loop``            ``jax.jit`` constructed inside a loop body
``jit-dynamic-static-args`` ``static_argnums``/``static_argnames`` that is not
                           a hashable literal
``jit-missing-donate``     jit threading a KV ``cache`` parameter without
                           ``donate_argnums``
``wall-clock-timer``       ``time.time()`` where a duration/timeout is being
                           measured (statements touching an exempted
                           cross-process anchor — ``deadline_ts``,
                           ``wall_anchor`` — are allowed)
``span-not-ended``         a ``start_span(...)`` call whose span is discarded
                           or never ``.end()``-ed on a guaranteed path (use
                           the context manager, or ``end()`` in a
                           ``finally``)
``unbounded-metric-label`` a metric series name or label built from a
                           per-request identifier (``req_id`` etc.) — every
                           request mints a new series and the registry grows
                           without bound
``fetch-inside-jit-scan``  host fetch (``jax.device_get``/``np.asarray``/
                           ``.item()``…) on a traced value inside a
                           ``lax.scan``/``fori_loop``/``while_loop`` body —
                           unlike ``jit-host-sync`` this resolves the body
                           function from the loop *call site*, so it also
                           covers bodies defined at module scope (never
                           lexically inside a jitted def) and lambdas
"""

from __future__ import annotations

import ast
import dataclasses

from .findings import Finding

#: Attribute calls on a traced value that force a device->host transfer.
_SYNC_METHODS = {"item", "tolist", "block_until_ready", "__array__"}
#: Builtins that force a transfer when called on a traced value.
_SYNC_BUILTINS = {"float", "int", "bool"}
#: numpy namespace functions that force a transfer on a traced argument.
_NP_SYNC_FUNCS = {"asarray", "array"}
#: Attribute reads that yield *static* (trace-time) values, breaking taint.
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding", "name"}

#: Names whose presence in a statement exempts it from wall-clock-timer:
#: each is a deliberate cross-process absolute-time anchor (see
#: ``_check_wall_clock``). A wall-clock read anywhere else is a bug.
_WALL_EXEMPT = frozenset({"deadline_ts", "wall_anchor"})

#: lax loop constructs whose body callables run traced on every iteration:
#: maps the construct name to the positional indices of its traced
#: body/cond function arguments (``while_loop`` traces both).
_LAX_LOOP_BODY_ARGS = {"scan": (0,), "fori_loop": (2,), "while_loop": (0, 1)}

#: Metric-registry lookups: the argument is a series *name* (or, for
#: ``labels``, a label value) and must come from a bounded vocabulary.
_METRIC_FUNCS = {"counter", "histogram", "labels"}
#: Per-request identifiers. One series per request = unbounded registry.
_UNBOUNDED_NAMES = frozenset(
    {"req_id", "trace_id", "request_id", "prompt", "prompt_text"}
)


# --------------------------------------------------------------------------
# module import aliases
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Aliases:
    numpy: set[str]
    jax_numpy: set[str]
    jax: set[str]
    time_mods: set[str]
    time_funcs: set[str]  # `from time import time [as t]`
    jit_names: set[str]   # `from jax import jit [as j]`
    partial_names: set[str]
    lax: set[str] = dataclasses.field(default_factory=set)
    #: `from jax.lax import scan [as s]`: bound name -> loop kind
    lax_funcs: dict[str, str] = dataclasses.field(default_factory=dict)
    device_get_names: set[str] = dataclasses.field(default_factory=set)


def collect_aliases(tree: ast.Module) -> Aliases:
    al = Aliases(set(), set(), set(), set(), set(), set(), {"functools.partial"})
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = a.asname or a.name
                if a.name == "numpy":
                    al.numpy.add(name)
                elif a.name == "jax.numpy":
                    al.jax_numpy.add(name)
                elif a.name == "jax":
                    al.jax.add(name)
                elif a.name == "jax.lax":
                    al.lax.add(name)
                elif a.name == "time":
                    al.time_mods.add(name)
                elif a.name == "functools":
                    al.partial_names.add(f"{name}.partial")
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                name = a.asname or a.name
                if node.module == "time" and a.name == "time":
                    al.time_funcs.add(name)
                elif node.module == "jax" and a.name == "jit":
                    al.jit_names.add(name)
                elif node.module == "jax" and a.name == "numpy":
                    al.jax_numpy.add(name)
                elif node.module == "jax" and a.name == "lax":
                    al.lax.add(name)
                elif node.module == "jax" and a.name == "device_get":
                    al.device_get_names.add(name)
                elif node.module == "functools" and a.name == "partial":
                    al.partial_names.add(name)
                elif node.module == "jax.numpy":
                    al.jax_numpy.add(name)
                elif node.module == "jax.lax" and a.name in _LAX_LOOP_BODY_ARGS:
                    al.lax_funcs[name] = a.name
    return al


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - defensive
        return "<expr>"


def _is_jit_func(func: ast.expr, al: Aliases) -> bool:
    """Is this expression ``jax.jit`` (under any alias)?"""
    if isinstance(func, ast.Name):
        return func.id in al.jit_names
    if isinstance(func, ast.Attribute) and func.attr == "jit":
        return isinstance(func.value, ast.Name) and func.value.id in al.jax
    return False


def _is_partial(func: ast.expr, al: Aliases) -> bool:
    return _unparse(func) in al.partial_names


# --------------------------------------------------------------------------
# Pass A: project-wide jit registry
# --------------------------------------------------------------------------

@dataclasses.dataclass
class JitSite:
    """One ``jax.jit(...)`` call (or partial-jit decorator)."""

    path: str
    line: int
    col: int
    target_name: str | None      # simple name of the wrapped function
    bound_pos: int               # positional params bound by partial()
    bound_kw: set[str]           # keyword params bound by partial()
    static_argnums: list[int]
    static_argnames: set[str]
    has_donate: bool
    dynamic_static: ast.expr | None  # non-literal static_arg* expression


@dataclasses.dataclass
class JitRegistry:
    sites: list[JitSite] = dataclasses.field(default_factory=list)
    #: simple names of functions known to be jit-compiled (pass B taints
    #: their call results), including attribute names like ``_decode_group``
    #: for ``self._decode_group = jax.jit(...)``.
    jit_value_names: set[str] = dataclasses.field(default_factory=set)
    #: function simple name -> (FunctionDef, path) for body analysis
    functions: dict[str, tuple[ast.FunctionDef, str]] = dataclasses.field(
        default_factory=dict
    )


def _literal_static(expr: ast.expr) -> bool:
    """True if a static_argnums/static_argnames value is a hashable literal."""
    if isinstance(expr, ast.Constant):
        return isinstance(expr.value, (int, str)) or expr.value is None
    if isinstance(expr, ast.Tuple):
        return all(_literal_static(e) for e in expr.elts)
    return False


def _static_values(expr: ast.expr) -> list:
    if isinstance(expr, ast.Constant):
        return [expr.value]
    if isinstance(expr, (ast.Tuple, ast.List)):
        out = []
        for e in expr.elts:
            out.extend(_static_values(e))
        return out
    return []


def _parse_jit_call(
    call: ast.Call, al: Aliases, path: str, target: ast.expr | None = None
) -> JitSite:
    """Describe one jit call.  ``target`` overrides the wrapped function
    expression (used for decorator sites, where the target is the def)."""
    wrapped = target
    if wrapped is None and call.args:
        wrapped = call.args[0]

    bound_pos, bound_kw = 0, set()
    if isinstance(wrapped, ast.Call) and _is_partial(wrapped.func, al):
        bound_pos = len(wrapped.args) - 1
        bound_kw = {kw.arg for kw in wrapped.keywords if kw.arg}
        wrapped = wrapped.args[0] if wrapped.args else None

    if isinstance(wrapped, ast.Name):
        name = wrapped.id
    elif isinstance(wrapped, ast.Attribute):
        name = wrapped.attr
    elif isinstance(wrapped, ast.FunctionDef):
        name = wrapped.name
    else:
        name = None

    static_argnums: list[int] = []
    static_argnames: set[str] = set()
    has_donate = False
    dynamic_static: ast.expr | None = None
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            if _literal_static(kw.value):
                static_argnums = [
                    v for v in _static_values(kw.value) if isinstance(v, int)
                ]
            else:
                dynamic_static = kw.value
        elif kw.arg == "static_argnames":
            if _literal_static(kw.value):
                static_argnames = {
                    v for v in _static_values(kw.value) if isinstance(v, str)
                }
            else:
                dynamic_static = kw.value
        elif kw.arg in ("donate_argnums", "donate_argnames"):
            has_donate = True

    return JitSite(
        path=path,
        line=call.lineno,
        col=call.col_offset,
        target_name=name,
        bound_pos=bound_pos,
        bound_kw=bound_kw,
        static_argnums=static_argnums,
        static_argnames=static_argnames,
        has_donate=has_donate,
        dynamic_static=dynamic_static,
    )


def collect_jit_registry(
    modules: list[tuple[str, ast.Module]]
) -> JitRegistry:
    """Pass A over ``(path, tree)`` pairs."""
    reg = JitRegistry()
    for path, tree in modules:
        al = collect_aliases(tree)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if isinstance(node, ast.FunctionDef):
                    reg.functions.setdefault(node.name, (node, path))
                # @jax.jit / @partial(jax.jit, ...) decorators
                for dec in node.decorator_list:
                    site = None
                    if isinstance(dec, ast.Call) and _is_partial(dec.func, al):
                        if dec.args and _is_jit_func(dec.args[0], al):
                            inner = ast.Call(
                                func=dec.args[0],
                                args=[],
                                keywords=dec.keywords,
                            )
                            ast.copy_location(inner, dec)
                            site = _parse_jit_call(inner, al, path, target=node)
                    elif _is_jit_func(dec, al):
                        site = JitSite(
                            path, dec.lineno, dec.col_offset, node.name,
                            0, set(), [], set(), False, None,
                        )
                    elif isinstance(dec, ast.Call) and _is_jit_func(dec.func, al):
                        site = _parse_jit_call(dec, al, path, target=node)
                    if site is not None:
                        reg.sites.append(site)
                        reg.jit_value_names.add(node.name)
            elif isinstance(node, ast.Call) and _is_jit_func(node.func, al):
                site = _parse_jit_call(node, al, path)
                reg.sites.append(site)
                if site.target_name:
                    reg.jit_value_names.add(site.target_name)
        # names the jitted callables are *stored under* also taint call sites:
        # ``self._decode = jax.jit(self._decode_impl)`` makes ``self._decode``
        # a jit-returning callable.
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if _is_jit_func(node.value.func, al):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            reg.jit_value_names.add(tgt.id)
                        elif isinstance(tgt, ast.Attribute):
                            reg.jit_value_names.add(tgt.attr)
    return reg


# --------------------------------------------------------------------------
# taint-based host-sync analysis inside jitted function bodies
# --------------------------------------------------------------------------

class _TaintVisitor(ast.NodeVisitor):
    """Forward taint propagation through one function body.

    Parameters that reach the jit boundary are tracers (seeds); anything
    computed from a tracer is tainted, *except* static attribute reads
    (``x.shape`` etc.), which are trace-time constants.
    """

    def __init__(self, al: Aliases, seeds: set[str]):
        self.al = al
        self.tainted = set(seeds)

    # -- expression taint -------------------------------------------------
    def expr_tainted(self, node: ast.expr) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self.expr_tainted(node.value)
        if isinstance(node, ast.Call):
            # a call is tainted if it consumes a tracer or comes from the
            # device namespace (jnp.zeros(...) etc. are tracers inside jit)
            func = node.func
            if isinstance(func, ast.Attribute):
                root = func.value
                if isinstance(root, ast.Name) and root.id in self.al.jax_numpy:
                    return True
                if node.args and func.attr in _STATIC_ATTRS:
                    return False
            return any(self.expr_tainted(a) for a in node.args) or any(
                kw.value is not None and self.expr_tainted(kw.value)
                for kw in node.keywords
            )
        if isinstance(node, (ast.BinOp,)):
            return self.expr_tainted(node.left) or self.expr_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr_tainted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr_tainted(v) for v in node.values)
        if isinstance(node, ast.Compare):
            return self.expr_tainted(node.left) or any(
                self.expr_tainted(c) for c in node.comparators
            )
        if isinstance(node, ast.Subscript):
            return self.expr_tainted(node.value)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr_tainted(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return any(
                self.expr_tainted(e) for e in (node.test, node.body, node.orelse)
            )
        if isinstance(node, ast.Starred):
            return self.expr_tainted(node.value)
        return False

    # -- assignments spread taint ----------------------------------------
    def _bind(self, target: ast.expr, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, tainted)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, tainted)

    def visit_Assign(self, node: ast.Assign) -> None:
        t = self.expr_tainted(node.value)
        for tgt in node.targets:
            self._bind(tgt, t)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        if self.expr_tainted(node.value):
            self._bind(node.target, True)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._bind(node.target, self.expr_tainted(node.value))
        self.generic_visit(node)


class _JitBodyChecker(_TaintVisitor):
    """Flags host syncs and ``if``-on-tracer inside a jitted function."""

    def __init__(self, al: Aliases, seeds: set[str], path: str):
        super().__init__(al, seeds)
        self.path = path
        self.findings: list[Finding] = []

    def _flag(self, node: ast.AST, rule: str, msg: str) -> None:
        self.findings.append(
            Finding(rule, self.path, node.lineno, node.col_offset, msg)
        )

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Name) and func.id in _SYNC_BUILTINS:
            if node.args and self.expr_tainted(node.args[0]):
                self._flag(
                    node, "jit-host-sync",
                    f"{func.id}() on traced value "
                    f"`{_unparse(node.args[0])}` forces a device sync "
                    "inside jit",
                )
        elif isinstance(func, ast.Attribute):
            root = func.value
            if (
                isinstance(root, ast.Name)
                and root.id in self.al.numpy
                and func.attr in _NP_SYNC_FUNCS
                and node.args
                and self.expr_tainted(node.args[0])
            ):
                self._flag(
                    node, "jit-host-sync",
                    f"{root.id}.{func.attr}() on traced value "
                    f"`{_unparse(node.args[0])}` forces a device sync "
                    "inside jit",
                )
            elif func.attr in _SYNC_METHODS and self.expr_tainted(root):
                self._flag(
                    node, "jit-host-sync",
                    f"`.{func.attr}()` on traced value `{_unparse(root)}` "
                    "forces a device sync inside jit",
                )
        self.generic_visit(node)

    def visit_If(self, node: ast.If) -> None:
        test = node.test
        is_none_test = isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
        )
        # isinstance() on a traced argument branches on PYTREE STRUCTURE
        # (e.g. dense KVCache vs PagedKVCache NamedTuples) — resolved at
        # trace time, never a tracer bool.
        is_type_test = (
            isinstance(test, ast.Call)
            and isinstance(test.func, ast.Name)
            and test.func.id == "isinstance"
        )
        if not is_none_test and not is_type_test and self.expr_tainted(test):
            self._flag(
                node, "jit-if-on-tracer",
                f"python `if` on traced value `{_unparse(test)}` — control "
                "flow must use lax.cond/jnp.where inside jit",
            )
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        # nested defs (lax.while_loop/scan bodies): their params are tracers
        inner = _JitBodyChecker(
            self.al,
            self.tainted | {a.arg for a in node.args.args},
            self.path,
        )
        for stmt in node.body:
            inner.visit(stmt)
        self.findings.extend(inner.findings)


def _seed_params(fn: ast.FunctionDef, site: JitSite) -> set[str]:
    params = [a.arg for a in fn.args.args]
    seeds = set(params[site.bound_pos:])
    seeds -= site.bound_kw
    seeds -= site.static_argnames
    for idx in site.static_argnums:
        if 0 <= idx < len(params):
            seeds.discard(params[idx])
    seeds.discard("self")
    return seeds


# --------------------------------------------------------------------------
# fetch-inside-jit-scan: host fetches inside lax loop bodies
# --------------------------------------------------------------------------

def _lax_loop_kind(func: ast.expr, al: Aliases) -> str | None:
    """``scan``/``fori_loop``/``while_loop`` if ``func`` is that lax
    construct under any alias, else None."""
    if isinstance(func, ast.Name):
        return al.lax_funcs.get(func.id)
    if isinstance(func, ast.Attribute) and func.attr in _LAX_LOOP_BODY_ARGS:
        root = func.value
        if isinstance(root, ast.Name) and root.id in al.lax:
            return func.attr
        if (
            isinstance(root, ast.Attribute)
            and root.attr == "lax"
            and isinstance(root.value, ast.Name)
            and root.value.id in al.jax
        ):
            return func.attr
    return None


class _ScanBodyChecker(_TaintVisitor):
    """Flags host fetches on traced values inside a lax loop body.

    ``jit-host-sync`` only sees bodies lexically nested inside a
    registered jitted def; loop bodies are frequently module-level
    functions handed to ``lax.scan`` (or lambdas), which that pass never
    enters. Here the body is resolved from the loop *call site*, its
    parameters are seeded as tracers, and any fetch — ``jax.device_get``,
    ``np.asarray``, ``.item()``, ``float()`` … — is a finding: under
    tracing the fetch cannot happen per-iteration at all (it escapes the
    trace or crashes), so the value must be returned from the loop and
    fetched once on the host.
    """

    def __init__(self, al: Aliases, seeds: set[str], path: str, kind: str):
        super().__init__(al, seeds)
        self.path = path
        self.kind = kind
        self.findings: list[Finding] = []

    def _flag(self, node: ast.AST, what: str, expr: str) -> None:
        self.findings.append(Finding(
            "fetch-inside-jit-scan", self.path, node.lineno, node.col_offset,
            f"{what} on traced value `{expr}` inside a lax.{self.kind} "
            "body — a per-iteration fetch cannot run under tracing; return "
            "the value from the loop and fetch it once on the host",
        ))

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Attribute):
            root = func.value
            if (
                func.attr == "device_get"
                and isinstance(root, ast.Name)
                and root.id in self.al.jax
                and node.args
                and self.expr_tainted(node.args[0])
            ):
                self._flag(
                    node, f"{root.id}.device_get()", _unparse(node.args[0])
                )
            elif (
                isinstance(root, ast.Name)
                and root.id in self.al.numpy
                and func.attr in _NP_SYNC_FUNCS
                and node.args
                and self.expr_tainted(node.args[0])
            ):
                self._flag(
                    node, f"{root.id}.{func.attr}()", _unparse(node.args[0])
                )
            elif func.attr in _SYNC_METHODS and self.expr_tainted(root):
                self._flag(node, f"`.{func.attr}()`", _unparse(root))
        elif isinstance(func, ast.Name):
            if func.id in self.al.device_get_names and node.args and (
                self.expr_tainted(node.args[0])
            ):
                self._flag(node, "device_get()", _unparse(node.args[0]))
            elif func.id in _SYNC_BUILTINS and node.args and (
                self.expr_tainted(node.args[0])
            ):
                self._flag(node, f"{func.id}()", _unparse(node.args[0]))
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        inner = _ScanBodyChecker(
            self.al,
            self.tainted | {a.arg for a in node.args.args},
            self.path,
            self.kind,
        )
        for stmt in node.body:
            inner.visit(stmt)
        self.findings.extend(inner.findings)


def _check_loop_body(
    path: str,
    al: Aliases,
    reg: JitRegistry,
    kind: str,
    body: ast.expr,
    seen: set[tuple[str, int]],
) -> list[Finding]:
    """Resolve one loop-body argument expression and check it."""
    bound_pos, bound_kw = 0, set()
    if isinstance(body, ast.Call) and _is_partial(body.func, al):
        bound_pos = max(len(body.args) - 1, 0)
        bound_kw = {kw.arg for kw in body.keywords if kw.arg}
        body = body.args[0] if body.args else None

    if isinstance(body, ast.Lambda):
        seeds = {a.arg for a in body.args.args[bound_pos:]} - bound_kw
        checker = _ScanBodyChecker(al, seeds, path, kind)
        checker.visit(body.body)
        return checker.findings

    if isinstance(body, ast.Name):
        name = body.id
    elif isinstance(body, ast.Attribute):
        name = body.attr
    else:
        return []
    entry = reg.functions.get(name)
    if entry is None:
        return []
    fn, fn_path = entry
    # Only analyse bodies defined in the module being checked: findings
    # anchor at the body's own source, and cross-module dedup happens by
    # each module checking (exactly) its own defs.
    if fn_path != path or (name, fn.lineno) in seen:
        return []
    seen.add((name, fn.lineno))
    params = [a.arg for a in fn.args.args]
    seeds = set(params[bound_pos:]) - bound_kw
    seeds.discard("self")
    checker = _ScanBodyChecker(al, seeds, path, kind)
    for stmt in fn.body:
        checker.visit(stmt)
    return checker.findings


def _check_scan_sites(
    path: str, tree: ast.Module, al: Aliases, reg: JitRegistry
) -> list[Finding]:
    findings: list[Finding] = []
    seen: set[tuple[str, int]] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        kind = _lax_loop_kind(node.func, al)
        if kind is None:
            continue
        for idx in _LAX_LOOP_BODY_ARGS[kind]:
            if idx < len(node.args):
                findings.extend(
                    _check_loop_body(path, al, reg, kind, node.args[idx], seen)
                )
    return findings


# --------------------------------------------------------------------------
# Pass B: per-module rules
# --------------------------------------------------------------------------

class _ModuleChecker(ast.NodeVisitor):
    """Rules that depend only on local context plus the jit registry."""

    def __init__(self, path: str, al: Aliases, reg: JitRegistry):
        self.path = path
        self.al = al
        self.reg = reg
        self.findings: list[Finding] = []
        self.loop_depth = 0
        self._parents: dict[ast.AST, ast.AST] = {}
        #: locals holding device values (results of jitted/jnp calls)
        self.device_vals: set[str] = set()

    def check(self, tree: ast.Module) -> list[Finding]:
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node
        self.visit(tree)
        return self.findings

    def _flag(self, node: ast.AST, rule: str, msg: str) -> None:
        self.findings.append(
            Finding(rule, self.path, node.lineno, node.col_offset, msg)
        )

    def _enclosing_stmt(self, node: ast.AST) -> ast.AST:
        cur = node
        while cur in self._parents and not isinstance(cur, ast.stmt):
            cur = self._parents[cur]
        return cur

    def _in_jit_body(self, node: ast.AST) -> bool:
        cur: ast.AST | None = node
        while cur is not None:
            if (
                isinstance(cur, ast.FunctionDef)
                and cur.name in self.reg.jit_value_names
            ):
                return True
            cur = self._parents.get(cur)
        return False

    # -- loops ------------------------------------------------------------
    def visit_For(self, node: ast.For) -> None:
        self._visit_loop(node)

    def visit_While(self, node: ast.While) -> None:
        self._visit_loop(node)

    def _visit_loop(self, node: ast.For | ast.While) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    # -- device-value tracking (host side) --------------------------------
    def _call_returns_device_value(self, call: ast.Call) -> bool:
        func = call.func
        if isinstance(func, ast.Name):
            name = func.id
        elif isinstance(func, ast.Attribute):
            if isinstance(func.value, ast.Name) and (
                func.value.id in self.al.jax_numpy
            ):
                return True
            name = func.attr
        else:
            return False
        return name in self.reg.jit_value_names

    def visit_Assign(self, node: ast.Assign) -> None:
        tainted = False
        if isinstance(node.value, ast.Call):
            tainted = self._call_returns_device_value(node.value)
        elif isinstance(node.value, ast.Name):
            tainted = node.value.id in self.device_vals
        for tgt in node.targets:
            names = []
            if isinstance(tgt, ast.Name):
                names = [tgt.id]
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                names = [e.id for e in tgt.elts if isinstance(e, ast.Name)]
            for n in names:
                if tainted:
                    self.device_vals.add(n)
                else:
                    self.device_vals.discard(n)
        self.generic_visit(node)

    def _is_device_expr(self, node: ast.expr) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.device_vals
        if isinstance(node, ast.Call):
            return self._call_returns_device_value(node)
        if isinstance(node, ast.Subscript):
            return self._is_device_expr(node.value)
        return False

    # -- calls ------------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        func = node.func

        # jit-in-loop: constructing a jit inside a loop recompiles every pass
        if _is_jit_func(func, self.al) and self.loop_depth > 0:
            self._flag(
                node, "jit-in-loop",
                "jax.jit constructed inside a loop — hoist it so the "
                "compile cache is reused",
            )

        if _is_jit_func(func, self.al):
            site = _parse_jit_call(node, self.al, self.path)
            self._check_jit_site(node, site)

        # host-sync-in-loop (only outside jitted bodies; inside them the
        # body checker raises jit-host-sync instead)
        if self.loop_depth > 0 and not self._in_jit_body(node):
            if (
                isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Name)
                and func.value.id in self.al.numpy
                and func.attr in _NP_SYNC_FUNCS
                and node.args
                and self._is_device_expr(node.args[0])
            ):
                self._flag(
                    node, "host-sync-in-loop",
                    f"{func.value.id}.{func.attr}() fetches device value "
                    f"`{_unparse(node.args[0])}` every loop iteration",
                )
            elif (
                isinstance(func, ast.Attribute)
                and func.attr in _SYNC_METHODS
                and self._is_device_expr(func.value)
            ):
                self._flag(
                    node, "host-sync-in-loop",
                    f"`.{func.attr}()` blocks on device value "
                    f"`{_unparse(func.value)}` every loop iteration",
                )

        # wall-clock-timer
        self._check_wall_clock(node)
        self.generic_visit(node)

    def _check_jit_site(self, node: ast.Call, site: JitSite) -> None:
        if site.dynamic_static is not None:
            self._flag(
                node, "jit-dynamic-static-args",
                "static_argnums/static_argnames must be a hashable literal, "
                f"got `{_unparse(site.dynamic_static)}` — dynamic statics "
                "recompile on every new value",
            )
        # cache-threading jits must donate the cache buffer
        target = (
            self.reg.functions.get(site.target_name)
            if site.target_name
            else None
        )
        if target is not None and not site.has_donate:
            params = [a.arg for a in target[0].args.args]
            if "cache" in params:
                self._flag(
                    node, "jit-missing-donate",
                    f"jit of `{site.target_name}` threads a `cache` argument "
                    "without donate_argnums — the KV cache is copied every "
                    "step",
                )

    def _check_wall_clock(self, node: ast.Call) -> None:
        func = node.func
        is_wall = (
            isinstance(func, ast.Attribute)
            and func.attr == "time"
            and isinstance(func.value, ast.Name)
            and func.value.id in self.al.time_mods
        ) or (isinstance(func, ast.Name) and func.id in self.al.time_funcs)
        if not is_wall:
            return
        # Wall clock is legal only where two processes must agree on an
        # absolute time — the exemption table names those anchors: the
        # cross-process request deadline, and the flight recorder's ONE
        # per-export wall stamp (all trace durations stay monotonic; the
        # anchor alone converts them at stitch time). Any statement
        # mentioning an exempted name is allowed.
        stmt = self._enclosing_stmt(node)
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.Attribute) and sub.attr in _WALL_EXEMPT:
                return
            if isinstance(sub, ast.Name) and sub.id in _WALL_EXEMPT:
                return
            if isinstance(sub, ast.Constant) and sub.value in _WALL_EXEMPT:
                return
        self._flag(
            node, "wall-clock-timer",
            "time.time() measures wall clock, which steps under NTP — use "
            "time.monotonic() for durations/timeouts (wall clock is legal "
            "only for the cross-process anchors "
            f"{', '.join(sorted(_WALL_EXEMPT))})",
        )


# --------------------------------------------------------------------------
# span-not-ended
# --------------------------------------------------------------------------

def _is_start_span(call: ast.AST) -> bool:
    if not isinstance(call, ast.Call):
        return False
    f = call.func
    return (
        isinstance(f, ast.Attribute) and f.attr == "start_span"
    ) or (isinstance(f, ast.Name) and f.id == "start_span")


def _iter_guaranteed(body: list[ast.stmt]):
    """Statements guaranteed to execute when ``body`` is entered and runs
    to completion: the body's own statements, descending into ``finally``
    blocks and ``with`` bodies — but NOT into ``if``/``for``/``while``/
    ``try`` bodies, which may not run (or not run to the end)."""
    for stmt in body:
        yield stmt
        if isinstance(stmt, ast.Try):
            yield from _iter_guaranteed(stmt.finalbody)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            yield from _iter_guaranteed(stmt.body)


def _ends_span(stmt: ast.stmt, name: str) -> bool:
    """``stmt`` is a simple statement calling ``<name>.end(...)``."""
    if not isinstance(stmt, (ast.Expr, ast.Assign, ast.Return)):
        return False
    for sub in ast.walk(stmt):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr == "end"
            and isinstance(sub.func.value, ast.Name)
            and sub.func.value.id == name
        ):
            return True
    return False


class _SpanChecker(ast.NodeVisitor):
    """A span left open never records its duration — the request's
    timeline silently loses the phase. Flag ``start_span`` calls whose
    result is discarded, or bound to a name with no ``.end()`` in a
    guaranteed-execution position afterwards. ``with start_span(...)``
    is the blessed form (``Span.__exit__`` always ends; exceptions get an
    ``error`` attr); so is returning the span to the caller."""

    def __init__(self, path: str):
        self.path = path
        self.findings: list[Finding] = []

    def _check_body(self, body: list[ast.stmt]) -> None:
        for idx, stmt in enumerate(body):
            if isinstance(stmt, ast.Expr) and _is_start_span(stmt.value):
                self.findings.append(Finding(
                    "span-not-ended", self.path, stmt.lineno,
                    stmt.col_offset,
                    "start_span(...) result discarded — the span can never "
                    "be ended; use `with ...start_span(...)` or bind and "
                    "`.end()` it",
                ))
            elif (
                isinstance(stmt, ast.Assign)
                and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and _is_start_span(stmt.value)
            ):
                name = stmt.targets[0].id
                if not any(
                    _ends_span(s, name)
                    for s in _iter_guaranteed(body[idx + 1:])
                ):
                    self.findings.append(Finding(
                        "span-not-ended", self.path, stmt.lineno,
                        stmt.col_offset,
                        f"span `{name}` has no `.end()` on a guaranteed "
                        "path — end it in a `finally` or use the context "
                        "manager",
                    ))

    def generic_visit(self, node: ast.AST) -> None:
        for field in ("body", "orelse", "finalbody"):
            body = getattr(node, field, None)
            if isinstance(body, list) and body and (
                isinstance(body[0], ast.stmt)
            ):
                self._check_body(body)
        super().generic_visit(node)


# --------------------------------------------------------------------------
# unbounded-metric-label
# --------------------------------------------------------------------------

def _unbounded_ref(node: ast.expr) -> ast.AST | None:
    """First sub-expression referencing a per-request identifier, if any.

    Walks the whole argument subtree, so f-strings, ``str(...)`` wraps and
    ``+``-concatenation are all seen through.
    """
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in _UNBOUNDED_NAMES:
            return sub
        if isinstance(sub, ast.Attribute) and sub.attr in _UNBOUNDED_NAMES:
            return sub
    return None


class _MetricLabelChecker(ast.NodeVisitor):
    """A windowed series keyed by a per-request value never aggregates:
    each request mints a fresh ring, memory grows with traffic, and every
    export ships the full registry. Series names and label values must come
    from a bounded vocabulary; the identifier belongs in the *trace*
    (``trace.record(req_id, ...)`` is fine — traces are per-request by
    design and bounded by the recorder's ring)."""

    def __init__(self, path: str):
        self.path = path
        self.findings: list[Finding] = []

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        is_metric = isinstance(func, ast.Attribute) and (
            func.attr in _METRIC_FUNCS
        )
        if is_metric:
            for arg in [*node.args, *(kw.value for kw in node.keywords)]:
                ref = _unbounded_ref(arg)
                if ref is not None:
                    self._flag(node, func.attr, ref)
                    break
        else:
            # labels=... keyword on any metric-ish constructor
            for kw in node.keywords:
                if kw.arg == "labels":
                    ref = _unbounded_ref(kw.value)
                    if ref is not None:
                        self._flag(node, "labels=", ref)
                        break
        self.generic_visit(node)

    def _flag(self, node: ast.Call, where: str, ref: ast.AST) -> None:
        self.findings.append(Finding(
            "unbounded-metric-label", self.path, node.lineno,
            node.col_offset,
            f"`{where}` derives a series name/label from per-request value "
            f"`{_unparse(ref)}` — one series per request grows the registry "
            "without bound; use a bounded name and put the id in the trace",
        ))


def check_module(
    path: str, tree: ast.Module, reg: JitRegistry
) -> list[Finding]:
    """Run every JAX rule over one module."""
    al = collect_aliases(tree)
    findings = _ModuleChecker(path, al, reg).check(tree)

    span_checker = _SpanChecker(path)
    span_checker.visit(tree)
    findings.extend(span_checker.findings)

    metric_checker = _MetricLabelChecker(path)
    metric_checker.visit(tree)
    findings.extend(metric_checker.findings)

    findings.extend(_check_scan_sites(path, tree, al, reg))

    # analyse jitted function bodies defined in this module
    seen: set[tuple[str, int]] = set()
    for site in reg.sites:
        if not site.target_name:
            continue
        entry = reg.functions.get(site.target_name)
        if entry is None:
            continue
        fn, fn_path = entry
        if fn_path != path or (site.target_name, fn.lineno) in seen:
            continue
        seen.add((site.target_name, fn.lineno))
        checker = _JitBodyChecker(al, _seed_params(fn, site), path)
        for stmt in fn.body:
            checker.visit(stmt)
        findings.extend(checker.findings)
    return findings
