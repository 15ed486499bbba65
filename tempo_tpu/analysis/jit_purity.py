"""Jit-purity lint: no host round-trips inside kernel functions.

TiLT (arxiv 2301.12030) gets this property by CONSTRUCTION — queries
lower to kernels with static shapes and no host round-trips mid-kernel.
This codebase writes its kernels by hand, so the same discipline is
enforced as lint over every function that reaches ``jax.jit`` or
``shard_map_compat`` (including nested defs like vmap/fori_loop bodies
and same-module helpers such as ``multi_entry_mask``):

  - no clock reads (``time.time()`` traces once and freezes — the value
    is a compile-time constant, almost never what the author meant);
  - no ``.item()`` / ``int()`` / ``float()`` on tracer values (host
    sync mid-trace: TracerConversionError at best, a silent d2h fence
    at worst);
  - no ``np.asarray`` / ``np.array`` on tracers (host materialization);
  - no Python ``if``/``while`` on tracer values (ConcretizationTypeError
    — the branch must be ``jnp.where`` / ``lax.cond``). ``x is None``
    tests are exempt: None-ness is static at trace time.

Cache-key hygiene rides along: a ``jax.jit`` kernel's keyword-only args
are this codebase's shape-affecting knobs (``n_terms``, ``top_k``,
``n_needle_max``) — every one must be in ``static_argnames``, or each
distinct VALUE becomes a silent retrace. The pow2-padding helpers
(``_pow2``, ``stack_queries``, ``stage_host``) exist so those statics
take log-many values; the checker pins the static declaration, bench
pins the compile counts.

Taint model (deliberately simple, tuned to this codebase's kernels):
parameters minus statics are tracers; assignments propagate taint,
EXCEPT through ``.shape``/``.ndim``/``.dtype``/``.size``/``len()``
reads, which are static under jit. Closure variables from an enclosing
kernel keep the enclosing classification.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from .core import Checker, Finding, Module, Package

_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size"}
_CLOCK_MODS = {"time", "_time"}
_NP_NAMES = {"np", "numpy"}
_NP_HOST_FNS = {"asarray", "array", "frombuffer", "copy"}
# packed-residency width-descriptor parameter names (search/packing.py
# unpack helpers + the kernels' `widths` static) AND the structural
# query engine's plan descriptors (search/structural.py `plan` — the
# compiled query tree the kernel lowering recurses over at trace time):
# a descriptor decides SHAPES and branch structure at trace time, so a
# tracer reaching one is a guaranteed ConcretizationTypeError — and a
# non-static python value would silently retrace per distinct value.
# The rule only fires for helpers that actually BRANCH on the parameter
# (descriptor dispatchers) — a numeric parameter that merely shares a
# name (`def weighted(x, w)`) is ordinary traced data, not a
# descriptor. `span_sharded` is the span-layout descriptor (segment-
# aligned span sharding): the dist kernels and any helper that selects
# the replicated-vs-sharded evaluation placement branch on it at trace
# time — a tracer reaching it would pick a layout per VALUE, exactly
# the retrace/concretization failure the widths rule exists for. The
# stacked plan descriptor (plan-shape stacking) rides the existing
# `plan` entry: the coalesced kernels thread the same static plan.
# `bucket` is the shape-bucket descriptor (shape-bucketed cross-plan
# stacking): the bucketed evaluator unpacks slot tiers and the has-
# relations arm from it at trace time. `shard_tail` is the ragged-tail
# layout descriptor (remainder-shard staging): the dist kernels select
# the tail-masking arm on it — both decide branch structure exactly
# like `span_sharded` and must stay in the static jit key. `tier` is
# the hot-tier page-capacity descriptor (live-tier rolling stages): the
# hot dispatch selects the capacity-masking arm on it at trace time,
# and keeping it static is what makes absorbs within a capacity tier
# re-enter the same compiled kernel instead of retracing per size.
# `buckets` is the analytics count kernel's two-limb latency-threshold
# descriptor and `agg`/`n_keys` the ?agg= dense key-space sizes
# (search/analytics.py): all three select the aggregate-reduction arm
# and size its key range at trace time, so they belong to the static
# jit key for exactly the `widths`/`plan` reason.
_DESCRIPTOR_PARAMS = {"w", "dw", "widths", "plan", "span_sharded",
                      "bucket", "shard_tail", "tier", "buckets", "agg",
                      "n_keys"}


def _branches_on_param(helper: ast.AST, param: str) -> bool:
    """Does the helper's body test `param` in an if/while condition (or
    compare it / call methods on it inside one)? That is the descriptor-
    dispatcher shape the taint rule exists for."""
    for node in ast.walk(helper):
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            for n in ast.walk(node.test):
                if isinstance(n, ast.Name) and n.id == param:
                    return True
    return False


@dataclass
class _KernelRoot:
    mod: Module
    qual: str
    node: ast.AST
    statics: frozenset       # static (non-tracer) parameter names
    via: str                 # "jax.jit" | "shard_map"


def _decorator_jit_statics(dec: ast.AST):
    """static_argnames from @jax.jit / @functools.partial(jax.jit, ...);
    None when the decorator isn't a jit form."""
    if isinstance(dec, ast.Attribute) and dec.attr == "jit":
        return frozenset()
    if isinstance(dec, ast.Name) and dec.id == "jit":
        return frozenset()
    if isinstance(dec, ast.Call):
        fn = dec.func
        is_partial = (isinstance(fn, ast.Attribute)
                      and fn.attr == "partial") or \
                     (isinstance(fn, ast.Name) and fn.id == "partial")
        if is_partial and dec.args:
            inner = dec.args[0]
            if (isinstance(inner, ast.Attribute) and inner.attr == "jit") \
                    or (isinstance(inner, ast.Name) and inner.id == "jit"):
                for kw in dec.keywords:
                    if kw.arg in ("static_argnames", "static_argnums"):
                        names = set()
                        for el in ast.walk(kw.value):
                            if isinstance(el, ast.Constant) \
                                    and isinstance(el.value, str):
                                names.add(el.value)
                        return frozenset(names)
                return frozenset()
        # jax.jit(fn, static_argnames=...) used as a decorator factory
        if isinstance(fn, ast.Attribute) and fn.attr == "jit":
            names = set()
            for kw in dec.keywords:
                for el in ast.walk(kw.value):
                    if isinstance(el, ast.Constant) \
                            and isinstance(el.value, str):
                        names.add(el.value)
            return frozenset(names)
    return None


def _params(func: ast.AST) -> list:
    a = func.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
            + ([a.vararg.arg] if a.vararg else [])
            + [p.arg for p in a.kwonlyargs]
            + ([a.kwarg.arg] if a.kwarg else []))


class JitPurityChecker(Checker):
    id = "jit-purity"
    helper_depth = 2

    def check(self, pkg: Package) -> list[Finding]:
        findings: list[Finding] = []
        roots = list(self._roots(pkg))
        seen: set = set()
        for root in roots:
            self._check_kernel(pkg, root.mod, root.qual, root.node,
                               root.statics, findings, seen,
                               depth=0, root_desc=root.via)
            if root.via == "jax.jit":
                self._check_static_decl(root, findings)
        return findings

    # ---- discovery ----

    def _roots(self, pkg: Package):
        for mod, qual, node in pkg.functions():
            statics = None
            for dec in getattr(node, "decorator_list", []):
                statics = _decorator_jit_statics(dec)
                if statics is not None:
                    break
            if statics is not None:
                yield _KernelRoot(mod, qual, node, statics, "jax.jit")
        # functions passed (by name) to shard_map_compat/shard_map:
        # resolve within the defining scope — the idiom is a nested
        # shard_fn def handed to the wrapper a few lines later
        for mod, qual, node in pkg.functions():
            local_defs = {
                ch.name: ch for ch in ast.walk(node)
                if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef))
                and ch is not node
            }
            for call in ast.walk(node):
                if not isinstance(call, ast.Call) or not call.args:
                    continue
                fn = call.func
                name = fn.attr if isinstance(fn, ast.Attribute) else (
                    fn.id if isinstance(fn, ast.Name) else "")
                if name not in ("shard_map_compat", "shard_map"):
                    continue
                arg0 = call.args[0]
                if isinstance(arg0, ast.Name) and arg0.id in local_defs:
                    yield _KernelRoot(
                        mod, f"{qual}.{arg0.id}", local_defs[arg0.id],
                        frozenset(), "shard_map")

    # ---- per-kernel analysis ----

    def _check_kernel(self, pkg: Package, mod: Module, qual: str,
                      func: ast.AST, statics: frozenset, findings: list,
                      seen: set, depth: int, root_desc: str,
                      closure_tainted: frozenset = frozenset()) -> None:
        key = (mod.dotted, qual, statics)
        if key in seen:
            return
        seen.add(key)
        tainted = set(p for p in _params(func) if p not in statics)
        tainted |= set(closure_tainted)

        def expr_tainted(expr: ast.AST) -> bool:
            """Does this expression carry tracer data? Names read only
            through shape/dtype accessors or len() don't."""
            stack = [(expr, False)]
            while stack:
                node, shielded = stack.pop()
                if isinstance(node, ast.Attribute) \
                        and node.attr in _SHAPE_ATTRS:
                    shielded = True
                elif isinstance(node, ast.Call):
                    fn = node.func
                    if isinstance(fn, ast.Name) and fn.id == "len":
                        shielded = True
                elif isinstance(node, ast.Name) and not shielded:
                    if node.id in tainted:
                        return True
                elif isinstance(node, (ast.Lambda, ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                stack.extend((c, shielded)
                             for c in ast.iter_child_nodes(node))
            return False

        def is_none_test(test: ast.AST) -> bool:
            """`x is None` / `x is not None` (possibly and-ed): static
            at trace time."""
            if isinstance(test, ast.BoolOp):
                return all(is_none_test(v) for v in test.values)
            if isinstance(test, ast.UnaryOp) \
                    and isinstance(test.op, ast.Not):
                return is_none_test(test.operand)
            return (isinstance(test, ast.Compare)
                    and len(test.ops) == 1
                    and isinstance(test.ops[0], (ast.Is, ast.IsNot))
                    and isinstance(test.comparators[0], ast.Constant)
                    and test.comparators[0].value is None)

        def flag(node, kind: str, msg: str, hint: str) -> None:
            findings.append(Finding(
                checker=self.id, path=mod.rel, line=node.lineno,
                message=f"{qual}() [reaches {root_desc}]: {msg}",
                hint=hint,
                key=f"{kind}:{qual}:{msg[:60]}"))

        def walk(stmts) -> None:
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    # vmap/fori_loop body: same kernel context; its own
                    # params are tracers, closure taint flows in
                    self._check_kernel(
                        pkg, mod, f"{qual}.{stmt.name}", stmt,
                        frozenset(), findings, seen, depth, root_desc,
                        closure_tainted=frozenset(tainted))
                    continue
                if isinstance(stmt, (ast.If, ast.While)):
                    if not is_none_test(stmt.test) \
                            and expr_tainted(stmt.test):
                        kw = ("while" if isinstance(stmt, ast.While)
                              else "if")
                        flag(stmt, "tracer-branch",
                             f"Python `{kw}` on a tracer value — the "
                             "branch runs at TRACE time, not on device "
                             "(ConcretizationTypeError or a silently "
                             "frozen branch)",
                             "use jnp.where / jax.lax.cond / "
                             "jax.lax.fori_loop, or make the value a "
                             "static_argnames kwarg")
                    walk(stmt.body)
                    walk(stmt.orelse)
                    continue
                if isinstance(stmt, (ast.For, ast.AsyncFor)):
                    if expr_tainted(stmt.iter):
                        flag(stmt, "tracer-iter",
                             "Python `for` over a tracer — the loop "
                             "unrolls at trace time over unknown length",
                             "use jax.lax.fori_loop / scan")
                    else:
                        # loop variables of a static-range loop stay
                        # static (for t in range(n_terms))
                        pass
                    walk(stmt.body)
                    walk(stmt.orelse)
                    continue
                if isinstance(stmt, ast.Try):
                    for block in (stmt.body, stmt.orelse, stmt.finalbody):
                        walk(block)
                    for h in stmt.handlers:
                        walk(h.body)
                    continue
                if isinstance(stmt, ast.With):
                    walk(stmt.body)
                    continue
                # taint propagation through simple assignment
                if isinstance(stmt, ast.Assign) and stmt.value is not None:
                    src_tainted = expr_tainted(stmt.value)
                    for tgt in stmt.targets:
                        for nm in ast.walk(tgt):
                            if isinstance(nm, ast.Name):
                                if src_tainted:
                                    tainted.add(nm.id)
                                else:
                                    tainted.discard(nm.id)
                self._scan_calls(pkg, mod, qual, stmt, tainted,
                                 expr_tainted, flag, findings, seen,
                                 depth, root_desc)

        walk(getattr(func, "body", []))

    def _scan_calls(self, pkg, mod, qual, stmt, tainted, expr_tainted,
                    flag, findings, seen, depth, root_desc) -> None:
        for node in ast.walk(stmt):
            if isinstance(node, (ast.Lambda,)):
                continue
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute):
                if fn.attr in ("time", "perf_counter", "monotonic") \
                        and isinstance(fn.value, ast.Name) \
                        and fn.value.id in _CLOCK_MODS:
                    flag(node, "clock",
                         f"clock read (time.{fn.attr}()) inside a jit "
                         "body — traces ONCE and freezes as a constant",
                         "take timestamps outside the kernel and pass "
                         "them in as arguments")
                elif fn.attr == "item":
                    flag(node, "item",
                         ".item() inside a jit body — host sync on a "
                         "tracer",
                         "keep the value on device; sync after the "
                         "kernel returns")
                elif fn.attr in _NP_HOST_FNS \
                        and isinstance(fn.value, ast.Name) \
                        and fn.value.id in _NP_NAMES \
                        and any(expr_tainted(a) for a in node.args):
                    flag(node, "np-host",
                         f"np.{fn.attr}() on a tracer inside a jit body "
                         "— host materialization mid-trace",
                         "use jnp (stays on device), or hoist the "
                         "conversion out of the kernel")
            elif isinstance(fn, ast.Name):
                if fn.id in ("int", "float", "bool") and node.args \
                        and expr_tainted(node.args[0]):
                    flag(node, "scalar-sync",
                         f"{fn.id}() on a tracer inside a jit body — "
                         "forces a host sync (TracerConversionError "
                         "under jit)",
                         "keep it as a 0-d device array, or make the "
                         "source value static")
                elif depth < self.helper_depth:
                    callee = self._resolve_helper(pkg, mod, fn.id)
                    if callee is not None:
                        helper_mod, helper_qual, helper_node = callee
                        # width descriptors must be STATIC: a helper
                        # whose descriptor param receives tracer data
                        # would branch on it at trace time (the packed-
                        # residency unpack helpers all do; helpers that
                        # never branch on the name are exempt)
                        hp = _params(helper_node)
                        bad = [
                            hp[i] for i, a in enumerate(node.args)
                            if i < len(hp) and hp[i] in _DESCRIPTOR_PARAMS
                            and expr_tainted(a)
                            and _branches_on_param(helper_node, hp[i])
                        ] + [
                            kw.arg for kw in node.keywords
                            if kw.arg in _DESCRIPTOR_PARAMS
                            and expr_tainted(kw.value)
                            and _branches_on_param(helper_node, kw.arg)
                        ]
                        for p in bad:
                            flag(node, "descriptor-taint",
                                 f"passes tracer data as width "
                                 f"descriptor {p!r} of {fn.id}() — "
                                 "descriptors select shapes/branches "
                                 "at trace time and must be static",
                                 "thread the descriptor through "
                                 "static_argnames (the `widths` jit "
                                 "static) instead of a traced value")
                        statics = self._classify_call(helper_node, node,
                                                      expr_tainted)
                        self._check_kernel(
                            pkg, helper_mod, helper_qual, helper_node,
                            statics, findings, seen, depth + 1,
                            root_desc)

    def _resolve_helper(self, pkg: Package, mod: Module, name: str):
        """A called helper analyzed in kernel context: same module
        first, then an imported package symbol."""
        for m, qual, node in pkg.functions():
            if m is mod and qual == name:
                return (m, qual, node)
        for stmt in ast.walk(mod.tree):
            if isinstance(stmt, ast.ImportFrom) and stmt.module:
                for alias in stmt.names:
                    if (alias.asname or alias.name) != name:
                        continue
                    base = stmt.module
                    if stmt.level:
                        parts = mod.dotted.split(".")
                        parts = parts[: len(parts) - stmt.level]
                        base = ".".join(parts + [stmt.module])
                    target = pkg.by_dotted.get(base)
                    if target is None:
                        continue
                    for m, qual, node in pkg.functions():
                        if m is target and qual == alias.name:
                            return (m, qual, node)
        return None

    @staticmethod
    def _classify_call(helper: ast.AST, call: ast.Call,
                       expr_tainted) -> frozenset:
        """Helper params bound to NON-tracer actuals are static for
        this call's analysis, and so are params the call leaves at
        their defaults (Python constants) — unless it spreads *args or
        **kwargs, which may bind any of them."""
        params = _params(helper)
        statics = set()
        bound = set()
        for i, arg in enumerate(call.args):
            if i < len(params):
                bound.add(params[i])
                if not expr_tainted(arg):
                    statics.add(params[i])
        for kw in call.keywords:
            if kw.arg:
                bound.add(kw.arg)
                if not expr_tainted(kw.value):
                    statics.add(kw.arg)
        spreads = any(isinstance(a, ast.Starred) for a in call.args) \
            or any(kw.arg is None for kw in call.keywords)
        if not spreads:
            statics |= set(params) - bound
        return frozenset(statics)

    # ---- cache-key hygiene ----

    def _check_static_decl(self, root: _KernelRoot,
                           findings: list) -> None:
        """Keyword-only args of a jit kernel are the shape-affecting
        knobs in this codebase (n_terms, top_k, ...): each must be
        declared static, or every distinct value silently retraces AND
        the pow2-padding discipline (dict_probe._pow2 bucketing) stops
        bounding the compile count."""
        kwonly = [p.arg for p in root.node.args.kwonlyargs]
        missing = [p for p in kwonly if p not in root.statics]
        for p in missing:
            findings.append(Finding(
                checker=self.id, path=root.mod.rel,
                line=root.node.lineno,
                message=(f"{root.qual}() keyword-only arg {p!r} is not "
                         "in static_argnames — shape-affecting kwargs "
                         "must be static or every value retraces"),
                hint="add it to static_argnames and route callers "
                     "through the pow2-padding helpers so it takes "
                     "log-many values",
                key=f"static-decl:{root.qual}:{p}"))
