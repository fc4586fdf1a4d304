"""planelint Family A: hot-path residency + launch-accounting rules.

JT1xx rules over the checker's device hot paths. The analysis is a
per-function, statement-ordered taint walk: names assigned from jax /
jitted-callable / sharded-factory calls are *device values*; the ONE
sanctioned way to materialize them on the host is the
``wgl_bitset._host_get`` funnel (which pays and counts the host
sync). Any other coercion — ``float()``/``int()``/``bool()``,
``np.asarray``, ``.item()``, iteration, comparison, boolean context —
is an implicit host sync the residency metric never sees.

Rules:

- JT101 implicit host sync outside the ``_host_get`` funnel (also:
  ``_host_get`` called per-element inside a loop/comprehension — N
  syncs where one tuple fetch pays the floor once).
- JT102 bare ``.block_until_ready()`` (an uncounted sync barrier).
- JT103 device dispatch with no launch accounting in the enclosing
  function (``_bump_launch``/``LAUNCH_STATS``/``note_sharded_launch``).
- JT104 bare ``jax.device_get`` outside the funnel and outside a
  thunk passed to a chaos guard (``resilient_call`` /
  ``run_with_deadline`` / ``_guard``).
- JT105 donation misuse: a name passed at a ``donate_argnums``
  position and then read again in the same block.
- JT106 jit-cache-key hazards: mutable default args on jitted
  functions; jitted bodies closing over mutable module globals.
- JT107 raw tunable read: a perf-registry knob's module constant
  (W_BUCKETS, GRAPH_BUCKETS, ...) read directly inside a function
  body instead of resolving through ``jepsen_tpu.perf.knobs`` — a
  persisted tuned profile could never retune that path. Module-level
  reads and signature defaults (evaluated at def time) are the
  sanctioned "document the registry default" spellings, and a
  function that itself calls ``resolve()`` is a resolution site
  (the raw constant is its registry-miss fallback).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from jepsen_tpu.analysis.callgraph import (
    _dotted,
    _last_seg,
    reachable_closure,
)
from jepsen_tpu.analysis.findings import Finding

#: host coercers whose call on a device value forces a sync
_COERCERS = {"float", "int", "bool", "complex", "str"}
#: numpy entry points that materialize their argument
_NP_COERCERS = {"asarray", "array", "ascontiguousarray", "copy"}
#: builtins that iterate their argument
_ITERATORS = {
    "list", "tuple", "set", "sorted", "sum", "max", "min", "any",
    "all", "frozenset",
}
#: jax.* attributes that do NOT produce device values
_JAX_HOST = {
    "jax.device_get", "jax.devices", "jax.local_devices",
    "jax.default_backend", "jax.jit", "jax.config.update",
    "jax.process_index", "jax.device_count",
}
#: jax.* namespaces that are host-side pytree plumbing, not device ops
_JAX_HOST_PREFIXES = ("jax.tree_util.", "jax.tree.")
#: the sanctioned funnel (and its qualified spellings)
_LAUNDER = {"_host_get", "device_get"}
#: guard callables whose thunk args are sanctioned crossings (JT104)
_GUARDS = {"resilient_call", "run_with_deadline", "_guard", "guard"}
#: launch-accounting entry points (JT103)
_ACCOUNTING = {"_bump_launch", "note_sharded_launch"}
#: factory prefixes returning device callables
_FACTORY_PREFIXES = ("make_sharded_",)

#: fallback catalog for JT107 when the registry itself won't import
#: (linting a tree mid-refactor must not crash the lint)
_KNOB_CONST_FALLBACK = frozenset({
    "W_BUCKETS", "ROWS_BUCKET_GROWTH", "GRAPH_BUCKETS",
    "PACKED_WORD_MAX_N", "STREAM_TAIL_BUCKET",
})


def _registry_constants() -> Set[str]:
    """Module-constant names the perf-knob registry supersedes
    (knobs with ``const=None`` have no raw-constant spelling to
    misread). perf/knobs.py is pure stdlib, so the lint reads the
    registry directly and can never drift from it."""
    try:
        from jepsen_tpu.perf import knobs as _perf_knobs

        consts = {
            k.const for k in _perf_knobs.KNOBS.values() if k.const
        }
        return consts or set(_KNOB_CONST_FALLBACK)
    except Exception:
        return set(_KNOB_CONST_FALLBACK)


def _is_jit_wrapper_call(call: ast.Call) -> Optional[ast.Call]:
    """``jax.jit(...)`` or ``functools.partial(jax.jit, ...)(...)``:
    returns the call node carrying the jit keywords (donate_argnums
    etc.), else None."""
    fd = _dotted(call.func)
    if fd in ("jax.jit", "jit"):
        return call
    # functools.partial(jax.jit, ...)(impl)
    if isinstance(call.func, ast.Call):
        inner = call.func
        if _dotted(inner.func) in ("functools.partial", "partial"):
            if inner.args and _dotted(inner.args[0]) in (
                "jax.jit", "jit"
            ):
                return inner
    return None


def _donate_positions(jit_call: ast.Call) -> Tuple[int, ...]:
    for kw in jit_call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for e in v.elts:
                    if isinstance(e, ast.Constant) and isinstance(
                        e.value, int
                    ):
                        out.append(e.value)
                return tuple(out)
    return ()


def _decorator_jit_call(dec: ast.expr) -> Optional[ast.Call]:
    """The jit-keyword-carrying call for a jit decorator spelling:
    ``@jax.jit``, ``@jax.jit(...)``, or
    ``@functools.partial(jax.jit, ...)``."""
    if _dotted(dec) in ("jax.jit", "jit"):
        return ast.Call(func=dec, args=[], keywords=[])
    if isinstance(dec, ast.Call):
        if _dotted(dec.func) in ("jax.jit", "jit"):
            return dec
        if _dotted(dec.func) in ("functools.partial", "partial"):
            if dec.args and _dotted(dec.args[0]) in ("jax.jit", "jit"):
                return dec
    return None


def _is_mutable_literal(node: ast.AST) -> bool:
    if isinstance(node, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                         ast.ListComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        return _last_seg(node.func) in (
            "dict", "list", "set", "OrderedDict", "defaultdict",
            "Counter", "deque",
        )
    return False


class ModuleInfo:
    """Module prepass: jitted callables (+ donate positions), factory-
    built device callables, device-returning helper defs, and mutable
    module globals (the jit-cache-key hazard surface)."""

    def __init__(self, tree: ast.Module):
        #: name -> donate positions (may be empty tuple)
        self.jitted: Dict[str, Tuple[int, ...]] = {}
        #: plain defs whose return value flows from a device call
        self.device_returning: Set[str] = set()
        #: module globals bound to mutable literals
        self.mutable_globals: Set[str] = set()
        #: impl functions consumed by a module-level jit wrapper
        self.jit_impls: Set[str] = set()
        #: functions whose bodies only ever run under jax tracing
        #: (reachable from a jit impl): host-coercion rules off
        self.traced: Set[str] = set()

        for node in tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if isinstance(tgt, ast.Name):
                    if isinstance(node.value, ast.Call):
                        jc = _is_jit_wrapper_call(node.value)
                        if jc is not None:
                            self.jitted[tgt.id] = _donate_positions(jc)
                            for a in node.value.args:
                                n = _dotted(a)
                                if n:
                                    self.jit_impls.add(n)
                            continue
                    if _is_mutable_literal(node.value):
                        self.mutable_globals.add(tgt.id)
            elif isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    jc = _decorator_jit_call(dec)
                    if jc is not None:
                        self.jitted[node.name] = _donate_positions(jc)
                        self.jit_impls.add(node.name)
                        break

        # second pass: traced closure. Seed with every function handed
        # to a jit wrapper ANYWHERE in the module (including
        # ``return jax.jit(fn)`` inside a factory), then grow to every
        # module function reachable from a traced body: those defs run
        # only under jax tracing, where a comparison builds a device
        # expression instead of syncing the host.
        defs_by_name: Dict[str, List[ast.FunctionDef]] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef):
                defs_by_name.setdefault(node.name, []).append(node)
        seeds = set(self.jit_impls)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                jc = _is_jit_wrapper_call(node)
                if jc is not None:
                    for a in node.args:
                        n = _dotted(a)
                        if n:
                            seeds.add(n.rsplit(".", 1)[-1])
        # the shared interprocedural fixpoint (callgraph.py) with the
        # funnel/accounting/guard names exempted: crossing one of them
        # is leaving traced code.
        self.traced = reachable_closure(
            defs_by_name,
            seeds,
            exempt=frozenset(_LAUNDER | _ACCOUNTING | _GUARDS),
        )

        # third pass: device-returning plain defs (one level deep)
        for node in tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            if node.name in self.jitted or node.name in self.jit_impls:
                continue
            if self._returns_device(node):
                self.device_returning.add(node.name)

    def _returns_device(self, fn: ast.FunctionDef) -> bool:
        for stmt in ast.walk(fn):
            if not isinstance(stmt, ast.Return) or stmt.value is None:
                continue
            for sub in ast.walk(stmt.value):
                if isinstance(sub, ast.Call) and self.is_device_call(
                    sub, set(), set()
                ):
                    return True
        return False

    def is_device_call(
        self,
        call: ast.Call,
        device_callables: Set[str],
        local_device_returning: Set[str],
    ) -> bool:
        """Does this call produce device-resident values?"""
        fd = _dotted(call.func)
        if fd is not None:
            if fd in _JAX_HOST or fd.startswith(_JAX_HOST_PREFIXES):
                return False
            root = fd.split(".", 1)[0]
            if root in ("jnp", "jax", "lax", "pltpu"):
                return True
            seg = fd.rsplit(".", 1)[-1]
            if seg in self.jitted or seg in self.jit_impls:
                return True
            if fd in device_callables or seg in self.device_returning:
                return True
            if fd in local_device_returning:
                return True
        # pl.pallas_call(...)(args): call whose func is itself a call
        if isinstance(call.func, ast.Call):
            inner = _dotted(call.func.func)
            if inner is not None and (
                inner.endswith("pallas_call")
                or inner.split(".", 1)[0] in ("jax", "jnp", "pl")
            ):
                return True
        return False

    def is_launch_call(
        self, call: ast.Call, device_callables: Set[str],
        local_device_returning: Set[str],
    ) -> bool:
        """A launch = dispatching a compiled computation (jitted name,
        factory-built sharded callable, pallas invocation) — NOT plain
        jnp array ops, which fuse into an enclosing launch."""
        fd = _dotted(call.func)
        if fd is not None:
            seg = fd.rsplit(".", 1)[-1]
            if seg in self.jitted:
                return True
            if fd in device_callables:
                return True
        if isinstance(call.func, ast.Call):
            inner = _dotted(call.func.func)
            if inner is not None and inner.endswith("pallas_call"):
                return True
        return False


def _is_factory_call(call: ast.Call) -> bool:
    seg = _last_seg(call.func)
    return bool(seg) and seg.startswith(_FACTORY_PREFIXES)


def _is_launder_call(call: ast.Call) -> bool:
    fd = _dotted(call.func)
    if fd is None:
        return False
    return fd.rsplit(".", 1)[-1] in _LAUNDER


class _FunctionScan:
    """Statement-ordered walk of one function body (nested defs
    included) tracking tainted names, local device callables, and
    donated buffers."""

    def __init__(self, checker: "HotPathChecker", symbol: str,
                 fn_name: str):
        self.c = checker
        self.symbol = symbol
        self.fn_name = fn_name
        self.tainted: Set[str] = set()
        self.device_callables: Set[str] = set()
        self.local_device_returning: Set[str] = set()
        self.donated: Set[str] = set()
        self.saw_launch: Optional[ast.Call] = None
        self.saw_accounting = False
        self.guard_depth = 0
        self.loop_depth = 0

    # -- findings ------------------------------------------------------

    def flag(self, rule: str, node: ast.AST, message: str,
             severity: str = "error") -> None:
        self.c.add(rule, node, message, self.symbol, severity)

    def jt104(self, node: ast.Call) -> None:
        if self.guard_depth > 0:
            return
        self.flag(
            "JT104", node,
            "bare jax.device_get outside the _host_get funnel and "
            "outside a chaos-guarded thunk — the crossing is neither "
            "counted nor covered by the resilience ladder",
        )

    # -- statements ----------------------------------------------------

    def run(self, body: List[ast.stmt]) -> None:
        self.block(body)
        if self.saw_launch is not None and not self.saw_accounting:
            self.flag(
                "JT103", self.saw_launch,
                "device dispatch with no launch accounting in "
                "this function (call _bump_launch/LAUNCH_STATS or "
                "note_sharded_launch so the residency metric sees it)",
            )

    def block(self, stmts: List[ast.stmt]) -> None:
        donated_before = set(self.donated)
        for stmt in stmts:
            self.stmt(stmt)
        # donations made inside this block don't poison siblings of
        # the enclosing block (a donating call behind `if` must not
        # flag the non-donating fallthrough path)
        self.donated = donated_before

    def stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.FunctionDef):
            self.nested_def(stmt)
            return
        if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            self.assign(stmt)
            return
        if isinstance(stmt, ast.Expr):
            self.expr(stmt.value)
            return
        if isinstance(stmt, ast.Return):
            if stmt.value is not None:
                self.expr(stmt.value)
            return
        if isinstance(stmt, (ast.If, ast.While)):
            t = self.expr(stmt.test)
            if t:
                self.flag(
                    "JT101", stmt.test,
                    "boolean coercion of a device value syncs the "
                    "host — fetch through _host_get first",
                )
            if isinstance(stmt, ast.While):
                self.loop_depth += 1
            self.block(stmt.body)
            self.block(stmt.orelse)
            if isinstance(stmt, ast.While):
                self.loop_depth -= 1
            return
        if isinstance(stmt, ast.For):
            if self.expr(stmt.iter):
                self.flag(
                    "JT101", stmt.iter,
                    "iterating a device value pulls it element-wise "
                    "device->host — fetch through _host_get "
                    "first",
                )
                self.untaint_target(stmt.iter)
            self.bind_targets(stmt.target, tainted=False)
            self.loop_depth += 1
            self.block(stmt.body)
            self.block(stmt.orelse)
            self.loop_depth -= 1
            return
        if isinstance(stmt, ast.With):
            for item in stmt.items:
                self.expr(item.context_expr)
                if item.optional_vars is not None:
                    self.bind_targets(item.optional_vars, tainted=False)
            self.block(stmt.body)
            return
        if isinstance(stmt, ast.Try):
            self.block(stmt.body)
            for h in stmt.handlers:
                self.block(h.body)
            self.block(stmt.orelse)
            self.block(stmt.finalbody)
            return
        if isinstance(stmt, (ast.Raise, ast.Assert)):
            for sub in ast.iter_child_nodes(stmt):
                if isinstance(sub, ast.expr):
                    self.expr(sub)
            return
        # imports, pass, global, del, etc: nothing to track
        return

    def nested_def(self, fn: ast.FunctionDef) -> None:
        # a nested def returning device values makes its name a local
        # device-returning callable for the rest of the function
        sub = _FunctionScan(self.c, f"{self.symbol}.{fn.name}", fn.name)
        sub.tainted = set(self.tainted)  # closure reads
        sub.device_callables = set(self.device_callables)
        sub.local_device_returning = set(self.local_device_returning)
        sub.guard_depth = self.guard_depth
        sub.block(fn.body)
        # accounting/launches inside the nested def belong to the
        # enclosing function's JT103 story (check_steps_bitset's
        # nested `scan` both launches and bumps)
        if sub.saw_launch is not None and self.saw_launch is None:
            self.saw_launch = sub.saw_launch
        self.saw_accounting = (
            self.saw_accounting or sub.saw_accounting
        )
        for stmt in ast.walk(fn):
            if isinstance(stmt, ast.Return) and stmt.value is not None:
                for node in ast.walk(stmt.value):
                    if isinstance(node, ast.Call) and (
                        self.c.info.is_device_call(
                            node, self.device_callables,
                            self.local_device_returning,
                        )
                    ):
                        self.local_device_returning.add(fn.name)
                        return

    def assign(self, stmt: ast.stmt) -> None:
        value = stmt.value
        if value is None:  # bare annotation
            return
        targets = (
            stmt.targets if isinstance(stmt, ast.Assign)
            else [stmt.target]
        )
        if isinstance(stmt, ast.AugAssign):
            # x += tainted keeps/creates taint
            t = self.expr(value)
            if isinstance(stmt.target, ast.Name):
                if t:
                    self.tainted.add(stmt.target.id)
                if self.expr(stmt.target):
                    pass  # reading own value: no extra signal
            return

        # classify the RHS before binding
        if isinstance(value, ast.Call):
            jc = _is_jit_wrapper_call(value)
            if jc is not None or _is_factory_call(value):
                for a in value.args:
                    self.expr(a)
                for tgt in targets:
                    if isinstance(tgt, ast.Name):
                        self.device_callables.add(tgt.id)
                        self.tainted.discard(tgt.id)
                return
        tainted = self.expr(value)
        for tgt in targets:
            if tainted and isinstance(tgt, (ast.Tuple, ast.List)):
                # tuple-unpacking a device-call result yields pytree
                # CONTAINERS (tuples of arrays): iterating/repacking
                # them is host-level bookkeeping, not a sync. Their
                # elements' fetch sites are still guarded by the
                # device_get/_host_get/block_until_ready rules.
                self.bind_targets(tgt, tainted=False)
            else:
                self.bind_targets(tgt, tainted=tainted)

    def bind_targets(self, tgt: ast.expr, tainted: bool) -> None:
        if isinstance(tgt, ast.Name):
            if tainted:
                self.tainted.add(tgt.id)
            else:
                self.tainted.discard(tgt.id)
            self.donated.discard(tgt.id)
            self.device_callables.discard(tgt.id)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for e in tgt.elts:
                self.bind_targets(e, tainted)
        elif isinstance(tgt, ast.Starred):
            self.bind_targets(tgt.value, tainted)
        elif isinstance(tgt, (ast.Attribute, ast.Subscript)):
            self.expr(tgt.value)

    def untaint_target(self, node: ast.expr) -> None:
        if isinstance(node, ast.Name):
            self.tainted.discard(node.id)

    # -- expressions ---------------------------------------------------

    def expr(self, node: ast.expr) -> bool:
        """Scan an expression: emit findings for triggers, return
        whether the expression's VALUE is device-resident."""
        if isinstance(node, ast.Call):
            return self.call(node)
        if isinstance(node, ast.Name):
            if node.id in self.donated and isinstance(
                node.ctx, ast.Load
            ):
                self.flag(
                    "JT105", node,
                    f"'{node.id}' was donated to a donate_argnums "
                    "callee above — its buffer is dead; rebuild it "
                    "before reuse",
                )
                self.donated.discard(node.id)
            return node.id in self.tainted
        if isinstance(node, ast.Subscript):
            t = self.expr(node.value)
            self.expr(node.slice)
            return t
        if isinstance(node, ast.Attribute):
            return self.expr(node.value)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            out = False
            for e in node.elts:
                out = self.expr(e) or out
            return out
        if isinstance(node, ast.Dict):
            out = False
            for k in node.keys:
                if k is not None:
                    out = self.expr(k) or out
            for v in node.values:
                out = self.expr(v) or out
            return out
        if isinstance(node, ast.BinOp):
            lt = self.expr(node.left)
            rt = self.expr(node.right)
            return lt or rt
        if isinstance(node, ast.UnaryOp):
            t = self.expr(node.operand)
            if isinstance(node.op, ast.Not) and t:
                self.flag(
                    "JT101", node,
                    "boolean coercion of a device value syncs the "
                    "host — fetch through _host_get first",
                )
                return False
            return t
        if isinstance(node, ast.BoolOp):
            ts = [self.expr(v) for v in node.values]
            if any(ts):
                self.flag(
                    "JT101", node,
                    "boolean coercion of a device value syncs the "
                    "host — fetch through _host_get first",
                )
            return False
        if isinstance(node, ast.Compare):
            lt = self.expr(node.left)
            rts = [self.expr(c) for c in node.comparators]
            if lt or any(rts):
                self.flag(
                    "JT101", node,
                    "comparison on a device value syncs the host — "
                    "fetch through _host_get first",
                )
            return False
        if isinstance(node, ast.IfExp):
            if self.expr(node.test):
                self.flag(
                    "JT101", node.test,
                    "boolean coercion of a device value syncs the "
                    "host — fetch through _host_get first",
                )
            bt = self.expr(node.body)
            ot = self.expr(node.orelse)
            return bt or ot
        if isinstance(node, (ast.GeneratorExp, ast.ListComp,
                             ast.SetComp, ast.DictComp)):
            return self.comprehension(node)
        if isinstance(node, ast.JoinedStr):
            for v in node.values:
                self.expr(v)
            return False
        if isinstance(node, ast.FormattedValue):
            self.expr(node.value)
            return False
        if isinstance(node, ast.Lambda):
            sub = _FunctionScan(
                self.c, f"{self.symbol}.<lambda>", "<lambda>"
            )
            sub.tainted = set(self.tainted)
            sub.device_callables = set(self.device_callables)
            sub.local_device_returning = set(self.local_device_returning)
            sub.guard_depth = self.guard_depth
            sub.expr(node.body)
            if sub.saw_launch is not None and self.saw_launch is None:
                self.saw_launch = sub.saw_launch
            self.saw_accounting = (
                self.saw_accounting or sub.saw_accounting
            )
            return False
        if isinstance(node, (ast.Constant, ast.Slice)):
            if isinstance(node, ast.Slice):
                for part in (node.lower, node.upper, node.step):
                    if part is not None:
                        self.expr(part)
            return False
        if isinstance(node, ast.Await):
            return self.expr(node.value)
        if isinstance(node, ast.NamedExpr):
            t = self.expr(node.value)
            self.bind_targets(node.target, tainted=t)
            return t
        return False

    def comprehension(self, node: ast.expr) -> bool:
        for gen in node.generators:
            if self.expr(gen.iter):
                self.flag(
                    "JT101", gen.iter,
                    "iterating a device value pulls it element-wise "
                    "device->host — fetch through _host_get "
                    "first",
                )
                self.untaint_target(gen.iter)
            self.bind_targets(gen.target, tainted=False)
            for cond in gen.ifs:
                self.expr(cond)
        self.loop_depth += 1
        try:
            if isinstance(node, ast.DictComp):
                self.expr(node.key)
                self.expr(node.value)
            else:
                self.expr(node.elt)
        finally:
            self.loop_depth -= 1
        return False

    def call(self, node: ast.Call) -> bool:
        fd = _dotted(node.func)
        seg = fd.rsplit(".", 1)[-1] if fd else _last_seg(node.func)

        # the funnel (and plain device_get): launders taint. Called
        # per element inside a loop it pays the sync floor N times —
        # the batched tuple fetch exists exactly for this.
        if isinstance(node.func, (ast.Name, ast.Attribute)) and (
            seg in _LAUNDER
        ):
            if seg == "device_get" and fd == "jax.device_get":
                self.jt104(node)
            if seg == "_host_get" and self.loop_depth > 0:
                self.flag(
                    "JT101", node,
                    "_host_get inside a loop/comprehension pays the "
                    "sync floor per element — batch into ONE tuple "
                    "fetch (_host_get((a, b, ...)))",
                )
            for a in node.args:
                self._scan_arg(a)
            return False

        # chaos guards: their thunk args are sanctioned crossings
        if seg in _GUARDS:
            self.guard_depth += 1
            try:
                for a in node.args:
                    self.expr(a)
                for kw in node.keywords:
                    self.expr(kw.value)
            finally:
                self.guard_depth -= 1
            return False

        # launch accounting (JT103 evidence)
        if seg in _ACCOUNTING:
            for a in node.args:
                self.expr(a)
            self.saw_accounting = True
            return False

        # bare sync barrier
        if isinstance(node.func, ast.Attribute) and (
            node.func.attr == "block_until_ready"
        ):
            self.flag(
                "JT102", node,
                "bare .block_until_ready() is an uncounted sync "
                "barrier — route the fetch through _host_get",
            )
            self.expr(node.func.value)
            return True

        # .item(): the classic scalar pull
        if isinstance(node.func, ast.Attribute) and (
            node.func.attr == "item"
        ):
            if self.expr(node.func.value):
                self.flag(
                    "JT101", node,
                    ".item() on a device value syncs the host — "
                    "fetch through _host_get first",
                )
            return False

        # host coercers / numpy materializers / iterating builtins
        if fd is not None:
            is_coercer = fd in _COERCERS
            is_np = (
                fd.split(".", 1)[0] in ("np", "numpy")
                and seg in _NP_COERCERS
            )
            is_iter = fd in _ITERATORS
            if is_coercer or is_np or is_iter:
                hit = False
                for a in node.args:
                    if self.expr(a):
                        hit = True
                        self.untaint_target(a)
                if hit:
                    what = (
                        "iterates" if is_iter else "materializes"
                    )
                    self.flag(
                        "JT101", node,
                        f"{fd}() {what} a device value — an implicit "
                        "host sync outside the _host_get funnel",
                    )
                return False

        # device-producing calls
        info = self.c.info
        if info.is_device_call(
            node, self.device_callables, self.local_device_returning
        ):
            launch = info.is_launch_call(
                node, self.device_callables,
                self.local_device_returning,
            )
            if launch and self.saw_launch is None:
                self.saw_launch = node
            for a in node.args:
                self._scan_arg(a)
            for kw in node.keywords:
                self.expr(kw.value)
            # donation marking AFTER the arg scan: the donating call
            # site itself reads the buffer legally; only LATER reads
            # touch a dead buffer
            if launch:
                self._check_donation(node)
            return True

        # unknown call: scan args, assume host result (a device value
        # passed into an opaque callee is that callee's problem)
        for a in node.args:
            self._scan_arg(a)
        for kw in node.keywords:
            self.expr(kw.value)
        return False

    def _scan_arg(self, a: ast.expr) -> None:
        """Scan a call argument: passing a tainted value *as an
        argument* is fine (no coercion happens at the call site)."""
        if isinstance(a, ast.Starred):
            a = a.value
        if isinstance(a, ast.Name):
            # still a donated-read though
            self.expr(a)
            return
        self.expr(a)

    def _check_donation(self, node: ast.Call) -> None:
        fd = _dotted(node.func)
        if fd is None:
            return
        seg = fd.rsplit(".", 1)[-1]
        positions = self.c.info.jitted.get(seg)
        if not positions:
            return
        for pos in positions:
            if pos < len(node.args):
                a = node.args[pos]
                if isinstance(a, ast.Name):
                    self.donated.add(a.id)


class HotPathChecker:
    """Run the JT1xx rules over one parsed module."""

    def __init__(self, tree: ast.Module, rel: str):
        self.tree = tree
        self.rel = rel
        self.info = ModuleInfo(tree)
        self.findings: List[Finding] = []

    def add(self, rule: str, node: ast.AST, message: str,
            symbol: str, severity: str = "error") -> None:
        self.findings.append(
            Finding(
                rule=rule,
                file=self.rel,
                line=getattr(node, "lineno", 0),
                col=getattr(node, "col_offset", 0),
                severity=severity,
                message=message,
                symbol=symbol,
            )
        )

    def run(self) -> List[Finding]:
        for node in self.tree.body:
            if isinstance(node, ast.FunctionDef):
                self._function(node, node.name)
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, ast.FunctionDef):
                        self._function(
                            sub, f"{node.name}.{sub.name}"
                        )
        self._jit_cache_hazards()
        self._knob_const_reads()
        return self.findings

    def _function(self, fn: ast.FunctionDef, symbol: str) -> None:
        scan = _FunctionScan(self, symbol, fn.name)
        if (
            fn.name in self.info.jit_impls
            or fn.name in self.info.jitted
            or fn.name in self.info.traced
        ):
            # jitted bodies (and helpers reachable from them) trace on
            # device: host-coercion taint rules do not apply inside
            # (JT106 covers their hazards), and a jit impl IS the
            # launch — it cannot account itself.
            return
        if fn.name == "_host_get":
            # the funnel itself is the sanctioned crossing
            return
        scan.run(fn.body)

    def _jit_cache_hazards(self) -> None:
        jit_names = set(self.info.jit_impls) | set(self.info.jitted)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            if node.name not in jit_names:
                continue
            args = node.args
            for a, default in zip(
                args.args[len(args.args) - len(args.defaults):],
                args.defaults,
            ):
                if _is_mutable_literal(default):
                    self.add(
                        "JT106", default,
                        f"jitted function '{node.name}' has a mutable "
                        f"default for '{a.arg}' — defaults enter the "
                        "jit cache key by identity and go stale",
                        node.name,
                        severity="warning",
                    )
            for kw, default in zip(args.kwonlyargs, args.kw_defaults):
                if default is not None and _is_mutable_literal(default):
                    self.add(
                        "JT106", default,
                        f"jitted function '{node.name}' has a mutable "
                        f"default for '{kw.arg}' — defaults enter the "
                        "jit cache key by identity and go stale",
                        node.name,
                        severity="warning",
                    )
            seen: Set[str] = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and isinstance(
                    sub.ctx, ast.Load
                ):
                    if (
                        sub.id in self.info.mutable_globals
                        and sub.id not in seen
                    ):
                        seen.add(sub.id)
                        self.add(
                            "JT106", sub,
                            f"jitted function '{node.name}' closes "
                            f"over mutable module global '{sub.id}' — "
                            "mutation after first trace is silently "
                            "ignored (stale jit cache)",
                            node.name,
                            severity="warning",
                        )


    def _knob_const_reads(self) -> None:
        """JT107: a perf-registry tunable read as a raw module
        constant inside a function body. Module-level reads and
        signature defaults evaluate at def time and are the sanctioned
        way to publish the registry default; a function that itself
        resolves through the registry is a resolution site, where the
        raw constant is the legitimate registry-miss fallback. One
        finding per (function, constant)."""
        consts = _registry_constants()
        if not consts:
            return
        targets: List[Tuple[ast.FunctionDef, str]] = []
        for node in self.tree.body:
            if isinstance(node, ast.FunctionDef):
                targets.append((node, node.name))
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, ast.FunctionDef):
                        targets.append(
                            (sub, f"{node.name}.{sub.name}")
                        )
        for fn, symbol in targets:
            self._knob_reads_in(fn, symbol, consts)

    def _knob_reads_in(
        self, fn: ast.FunctionDef, symbol: str, consts: Set[str]
    ) -> None:
        skip: Set[int] = set()  # nodes inside nested-def defaults
        resolves = False
        for sub in ast.walk(fn):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defaults = list(sub.args.defaults) + [
                    d for d in sub.args.kw_defaults if d is not None
                ]
                for d in defaults:
                    for n in ast.walk(d):
                        skip.add(id(n))
            elif isinstance(sub, ast.Call):
                if _last_seg(sub.func) == "resolve":
                    resolves = True
        if resolves:
            return
        seen: Set[str] = set()
        for stmt in fn.body:
            for sub in ast.walk(stmt):
                if id(sub) in skip:
                    continue
                if (
                    isinstance(sub, ast.Name)
                    and isinstance(sub.ctx, ast.Load)
                    and sub.id in consts
                    and sub.id not in seen
                ):
                    seen.add(sub.id)
                    self.add(
                        "JT107", sub,
                        f"'{symbol}' reads tunable '{sub.id}' as a "
                        "raw module constant — registry knobs resolve "
                        "through jepsen_tpu.perf.knobs (a persisted "
                        "profile retunes them; the constant is only "
                        "the registry default)",
                        symbol,
                        severity="warning",
                    )


def check_hotpath(tree: ast.Module, rel: str) -> List[Finding]:
    return HotPathChecker(tree, rel).run()
