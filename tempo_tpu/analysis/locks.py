"""Lock-order analyzer: the PR 1 deadlock class, caught before it ships.

Three findings, in escalating severity:

``blocking-under-lock``
    a call that can block indefinitely (``Future.result``, ``.join``,
    ``block_until_ready`` / the profiler fence, an unbounded
    ``.acquire()`` or ``.wait()``, ``time.sleep``, a device d2h sync
    helper) made while lexically holding a known lock. This is the
    shape that turned PR 1's interleaved shard_map dispatch into a
    multi-minute zero-CPU hang, and the class PR 9's bounded
    ``dispatch_lock`` wait can only detect AFTER the stall started.

``lock-reacquire``
    a non-reentrant lock acquired while already held (directly or
    through a call chain) — self-deadlock.

``lock-cycle``
    the acquisition graph (edge A→B = B taken while A held, lexically
    or through resolved same-class/same-module calls) contains an
    inter-lock cycle — two threads walking the cycle from different
    ends deadlock.

Lock identity is CLASS-scoped (``module:Class.attr``) or module-scoped
(``module:name``) — every instance of a class shares one node, which is
exactly the granularity a lock-ORDER discipline is defined at. Aliases
resolve through assignment (``self._dispatch_lock = mesh.dispatch_lock``)
and ``threading.Condition(self._lock)`` (the condition IS that lock).
Calls resolve within the package (same scope, same class, same module,
or an imported module/symbol); unresolvable receivers contribute
nothing — the analyzer under-approximates rather than guessing.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from .core import Checker, Finding, Module, Package

_LOCK_FACTORIES = {"Lock", "RLock", "Condition"}

# attribute-call names that can block indefinitely (receiver-typed
# refinements below: set_result is not result; cv.wait on the HELD
# condition releases it; a timeout argument bounds the wait)
_BLOCKING_ATTRS = {
    "result": "Future.result() parks this thread until another delivers",
    "join": "join() waits for another thread to finish",
    "block_until_ready": "device sync: waits for the kernel/transfer",
    "fence": "profiler fence = block_until_ready on the kernel outputs",
    "fetch": "d2h sync: the one blocking fetch of a launch's output",
    "item": "device scalar sync: .item() waits for the device value",
    "wait": "unbounded wait() parks this thread",
    "acquire": "unbounded acquire() can park this thread forever",
    "sleep": "sleeping while holding a lock stalls every waiter",
}
# module-level helper functions that synchronize with the device (d2h)
_BLOCKING_NAMES = {
    "host_scan": "runs the full host-path kernel + d2h sync",
    "fetch_scan_out": "d2h sync of a dispatch's output",
    "fence_arrays": "block_until_ready over kernel outputs",
}


@dataclass
class _LockDef:
    lock_id: str
    kind: str               # Lock | RLock | Condition
    mod: str                # dotted module
    line: int


@dataclass
class _FuncInfo:
    key: tuple              # (dotted_module, qualname)
    node: ast.AST
    mod: Module
    cls: str | None         # enclosing class name, if a method
    acquires: set = field(default_factory=set)      # direct lock ids
    blocks: list = field(default_factory=list)      # direct block reasons
    calls: set = field(default_factory=set)         # resolved callee keys
    # transitive closures (fixpoint-filled)
    all_acquires: set = field(default_factory=set)
    may_block: str | None = None    # reason string, if any
    # False ⇒ no with/acquire anywhere: the interprocedural re-scan can
    # skip it (no held region is possible, so no findings or edges)
    hold_potential: bool = False


class _Symbols:
    """The package's lock + import + function tables (one build)."""

    def __init__(self, pkg: Package):
        self.pkg = pkg
        self.locks: dict[str, _LockDef] = {}
        self.global_locks: dict[tuple, str] = {}   # (dotted, name) -> id
        self.class_locks: dict[tuple, str] = {}    # (dotted, cls, attr) -> id
        self.attr_index: dict[str, list] = {}      # attr -> [lock ids]
        self.imports: dict[tuple, object] = {}     # (dotted, alias) -> target
        self.funcs: dict[tuple, _FuncInfo] = {}
        self._build()

    # ---- construction ----

    def _build(self) -> None:
        for mod in self.pkg.modules:
            self._collect_imports(mod)
        for mod in self.pkg.modules:
            self._collect_lock_defs(mod)
        for mod in self.pkg.modules:
            self._collect_lock_aliases(mod)
        for mod, qual, node in self.pkg.functions():
            cls = None
            if "." in qual:
                # the nearest enclosing CLASS, if any, is the part
                # before the final def for methods; nested functions
                # inherit the method's class for self-resolution
                parts = qual.split(".")
                head = parts[0]
                if (self.class_attr_names(mod.dotted, head)
                        or self._is_class(mod, head)):
                    cls = head
            info = _FuncInfo(key=(mod.dotted, qual), node=node, mod=mod,
                             cls=cls)
            self.funcs[info.key] = info

    def _is_class(self, mod: Module, name: str) -> bool:
        for node in mod.tree.body:
            if isinstance(node, ast.ClassDef) and node.name == name:
                return True
        return False

    def class_attr_names(self, dotted: str, cls: str) -> list:
        return [a for (d, c, a) in self.class_locks if d == dotted
                and c == cls]

    def _collect_imports(self, mod: Module) -> None:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.asname or alias.name.split(".")[0]
                    self.imports[(mod.dotted, name)] = \
                        alias.name if alias.asname else name
            elif isinstance(node, ast.ImportFrom) and node.module:
                base = node.module
                if node.level:
                    parts = mod.dotted.split(".")
                    # level 1 = the containing package of this module
                    parts = parts[: len(parts) - node.level]
                    base = ".".join(parts + [node.module])
                for alias in node.names:
                    name = alias.asname or alias.name
                    target = f"{base}.{alias.name}"
                    # module import vs symbol import: if target names a
                    # package module, the alias IS that module
                    if target in self.pkg.by_dotted:
                        self.imports[(mod.dotted, name)] = target
                    else:
                        self.imports[(mod.dotted, name)] = (base, alias.name)

    def _lock_factory(self, call: ast.AST) -> str | None:
        if not isinstance(call, ast.Call):
            return None
        fn = call.func
        if isinstance(fn, ast.Attribute) and fn.attr in _LOCK_FACTORIES \
                and isinstance(fn.value, ast.Name) \
                and fn.value.id == "threading":
            return fn.attr
        if isinstance(fn, ast.Name) and fn.id in _LOCK_FACTORIES:
            return fn.id
        return None

    def _add_lock(self, lock_id: str, kind: str, mod: Module,
                  line: int, attr: str | None = None) -> None:
        if lock_id not in self.locks:
            self.locks[lock_id] = _LockDef(lock_id, kind, mod.dotted, line)
        if attr is not None:
            self.attr_index.setdefault(attr, [])
            if lock_id not in self.attr_index[attr]:
                self.attr_index[attr].append(lock_id)

    def _collect_lock_defs(self, mod: Module) -> None:
        # module-level: name = threading.Lock()
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                kind = self._lock_factory(node.value)
                if kind:
                    name = node.targets[0].id
                    lock_id = f"{mod.dotted}:{name}"
                    self.global_locks[(mod.dotted, name)] = lock_id
                    self._add_lock(lock_id, kind, mod, node.lineno)
        # class-scoped: self.attr = threading.Lock() anywhere in a method
        for cls_node in mod.tree.body:
            if not isinstance(cls_node, ast.ClassDef):
                continue
            for node in ast.walk(cls_node):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1):
                    continue
                tgt = node.targets[0]
                if not (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    continue
                kind = self._lock_factory(node.value)
                if kind:
                    lock_id = f"{mod.dotted}:{cls_node.name}.{tgt.attr}"
                    key = (mod.dotted, cls_node.name, tgt.attr)
                    if key not in self.class_locks:
                        self.class_locks[key] = lock_id
                        self._add_lock(lock_id, kind, mod, node.lineno,
                                       attr=tgt.attr)

    def _collect_lock_aliases(self, mod: Module) -> None:
        """Second pass: self.attr = <known lock> and Condition(<lock>)."""
        for cls_node in mod.tree.body:
            if not isinstance(cls_node, ast.ClassDef):
                continue
            for node in ast.walk(cls_node):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1):
                    continue
                tgt = node.targets[0]
                if not (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    continue
                key = (mod.dotted, cls_node.name, tgt.attr)
                if key in self.class_locks:
                    # Condition wrapping the class's own lock: the cv IS
                    # that lock for ordering purposes
                    if (self._lock_factory(node.value) == "Condition"
                            and isinstance(node.value, ast.Call)
                            and node.value.args):
                        inner = self.resolve_lock(
                            mod, cls_node.name, node.value.args[0], {})
                        if inner:
                            old = self.class_locks[key]
                            self.class_locks[key] = inner
                            self.locks.pop(old, None)
                            if tgt.attr in self.attr_index:
                                self.attr_index[tgt.attr] = [
                                    inner if x == old else x
                                    for x in self.attr_index[tgt.attr]]
                    continue
                lock_id = self.resolve_lock(mod, cls_node.name,
                                            node.value, {})
                if lock_id:
                    self.class_locks[key] = lock_id
                    self.attr_index.setdefault(tgt.attr, [])
                    if lock_id not in self.attr_index[tgt.attr]:
                        self.attr_index[tgt.attr].append(lock_id)

    # ---- resolution ----

    def resolve_lock(self, mod: Module, cls: str | None, expr: ast.AST,
                     local_aliases: dict) -> str | None:
        """expr -> lock id, or None when it isn't (provably) a lock."""
        if isinstance(expr, ast.Name):
            if expr.id in local_aliases:
                return local_aliases[expr.id]
            hit = self.global_locks.get((mod.dotted, expr.id))
            if hit:
                return hit
            target = self.imports.get((mod.dotted, expr.id))
            if isinstance(target, tuple):
                return self.global_locks.get(target)
            return None
        if isinstance(expr, ast.Attribute):
            base = expr.value
            if isinstance(base, ast.Name) and base.id == "self" and cls:
                hit = self.class_locks.get((mod.dotted, cls, expr.attr))
                if hit:
                    return hit
            if isinstance(base, ast.Name):
                target = self.imports.get((mod.dotted, base.id))
                if isinstance(target, str):
                    hit = self.global_locks.get((target, expr.attr))
                    if hit:
                        return hit
            # attr-unique fallback: exactly one class in the package
            # defines a lock under this attribute name
            cands = self.attr_index.get(expr.attr, [])
            if len(cands) == 1:
                return cands[0]
        return None

    def resolve_call(self, mod: Module, qual: str, cls: str | None,
                     fn: ast.AST) -> tuple | None:
        """callee expr -> function key within the package, or None."""
        if isinstance(fn, ast.Name):
            # nested function in an enclosing scope of `qual`
            parts = qual.split(".")
            for i in range(len(parts), 0, -1):
                cand = (mod.dotted, ".".join(parts[:i] + [fn.id]))
                if cand in self.funcs:
                    return cand
            if (mod.dotted, fn.id) in self.funcs:
                return (mod.dotted, fn.id)
            target = self.imports.get((mod.dotted, fn.id))
            if isinstance(target, tuple):
                cand = (target[0], target[1])
                if cand in self.funcs:
                    return cand
            return None
        if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
            if fn.value.id == "self" and cls:
                cand = (mod.dotted, f"{cls}.{fn.attr}")
                if cand in self.funcs:
                    return cand
                return None
            target = self.imports.get((mod.dotted, fn.value.id))
            if isinstance(target, str):
                cand = (target, fn.attr)
                if cand in self.funcs:
                    return cand
        return None


def _has_timeout(call: ast.Call) -> bool:
    """A BOUNDING timeout argument: `result(None)` / `wait(None)` are
    explicitly unbounded and `acquire(True)` is just blocking=True —
    none of them bound the wait."""
    if call.args:
        a = call.args[0]
        if isinstance(a, ast.Constant) and (a.value is None
                                            or a.value is True):
            return False
        return True
    return any(
        kw.arg in ("timeout", "timeout_s")
        and not (isinstance(kw.value, ast.Constant)
                 and kw.value.value is None)
        for kw in call.keywords)


def _is_nonblocking_acquire(call: ast.Call) -> bool:
    """acquire(False) / acquire(blocking=False) returns immediately."""
    if call.args and isinstance(call.args[0], ast.Constant) \
            and call.args[0].value is False:
        return True
    return any(kw.arg == "blocking"
               and isinstance(kw.value, ast.Constant)
               and kw.value.value is False for kw in call.keywords)


def _call_blocks(call: ast.Call, held_ids: set) -> str | None:
    """Why this call may block forever, or None. `held_ids` exempts
    cv.wait on the held condition (it RELEASES the lock)."""
    fn = call.func
    if isinstance(fn, ast.Name):
        reason = _BLOCKING_NAMES.get(fn.id)
        return f"{fn.id}(): {reason}" if reason else None
    if not isinstance(fn, ast.Attribute):
        return None
    attr = fn.attr
    reason = _BLOCKING_ATTRS.get(attr)
    if reason is None:
        return None
    if attr in ("result", "wait", "acquire", "join") and _has_timeout(call):
        return None        # bounded wait: stalls surface, they don't wedge
    if attr == "acquire" and _is_nonblocking_acquire(call):
        return None        # blocking=False returns immediately
    if attr == "join":
        # str.join / os.path.join take an iterable argument;
        # Thread.join() takes none (the timeout form is exempt above)
        if call.args or call.keywords:
            return None
        if isinstance(fn.value, ast.Constant):
            return None
    if attr == "sleep":
        if not (isinstance(fn.value, ast.Name)
                and fn.value.id in ("time", "_time")):
            return None
    return f".{attr}(): {reason}"


class LockOrderChecker(Checker):
    """See module docstring. New d2h-sync helpers / blocking attribute
    names register in the module-level ``_BLOCKING_NAMES`` /
    ``_BLOCKING_ATTRS`` tables."""

    id = "lock-order"

    def check(self, pkg: Package) -> list[Finding]:
        sym = _Symbols(pkg)
        findings: list[Finding] = []
        edges: dict[tuple, tuple] = {}   # (A, B) -> (rel, line)

        # per-function direct facts
        for info in sym.funcs.values():
            self._scan_function(sym, info, findings, edges)

        # transitive closure: acquires + may_block through resolved calls
        changed = True
        rounds = 0
        while changed and rounds < 50:
            changed = False
            rounds += 1
            for info in sym.funcs.values():
                acq = set(info.acquires)
                blk = info.may_block or (
                    info.blocks[0][0] if info.blocks else None)
                for callee_key in info.calls:
                    callee = sym.funcs.get(callee_key)
                    if callee is None:
                        continue
                    acq |= callee.all_acquires
                    if blk is None and callee.may_block:
                        blk = (f"calls {callee_key[1]}() which may block "
                               f"({callee.may_block})")
                if acq != info.all_acquires:
                    info.all_acquires = acq
                    changed = True
                if blk != info.may_block:
                    info.may_block = blk
                    changed = True

        # second pass: interprocedural edges + blocking through calls.
        # Functions with no with/acquire can hold nothing — skip them.
        for info in sym.funcs.values():
            if info.hold_potential:
                self._scan_function(sym, info, findings, edges,
                                    interprocedural=True)

        findings.extend(self._cycles(edges, sym))
        return findings

    # ---- per-function walk ----

    def _scan_function(self, sym: _Symbols, info: _FuncInfo,
                       findings: list, edges: dict,
                       interprocedural: bool = False) -> None:
        mod, qual = info.mod, info.key[1]
        local_aliases: dict = {}

        def note_edge(held: list, lock_id: str, line: int) -> None:
            for held_id, _ in held:
                if held_id == lock_id:
                    kind = sym.locks.get(lock_id)
                    if kind is not None and kind.kind == "RLock":
                        continue
                    # reacquire findings emit on the interprocedural
                    # pass only (its held-set is a superset — same
                    # stance as the blocking findings). Sound at class
                    # granularity because calls resolve through `self`
                    # or module scope: same instance, same lock object.
                    if interprocedural:
                        findings.append(Finding(
                            checker=self.id, path=mod.rel, line=line,
                            message=(f"{qual}() re-acquires non-reentrant "
                                     f"lock {lock_id} while already "
                                     "holding it — self-deadlock"),
                            hint="split the locked region, or make the "
                                 "inner path a *_locked helper that "
                                 "asserts the caller holds the lock",
                            key=f"reacquire:{qual}:{lock_id}"))
                    continue
                edges.setdefault((held_id, lock_id),
                                 (mod.rel, line, qual))

        def scan_expr(expr: ast.AST, held: list) -> tuple:
            """One walk per statement: flag blocking calls, record
            acquire() edges, collect the call summary, and return the
            (acquired, released) lock ids so the caller can update its
            held-region (lambdas/nested defs excluded: they run later,
            on some other thread's schedule). Direct blocking findings
            emit on the interprocedural pass (whose held-set is a
            superset); summaries fill on the first."""
            acquired: list = []
            released: set = set()
            for node in _walk_no_nested(expr):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                if isinstance(fn, ast.Attribute) \
                        and fn.attr in ("acquire", "release"):
                    lock_id = sym.resolve_lock(mod, info.cls, fn.value,
                                               local_aliases)
                    if lock_id and fn.attr == "release":
                        released.add(lock_id)
                    elif lock_id:
                        info.hold_potential = True
                        if not interprocedural:
                            info.acquires.add(lock_id)
                        note_edge(held, lock_id, node.lineno)
                        acquired.append(lock_id)
                if not interprocedural:
                    why = _call_blocks(node, set())
                    if why:  # feeds the may_block summary
                        info.blocks.append((why, node.lineno))
                    callee = sym.resolve_call(mod, qual, info.cls, fn)
                    if callee:
                        info.calls.add(callee)
                if not held:
                    continue
                held_ids = {h for h, _ in held}
                why = _call_blocks(node, held_ids)
                if why is not None and isinstance(fn, ast.Attribute) \
                        and fn.attr == "wait":
                    # cv.wait on the HELD condition releases it: exempt
                    rid = sym.resolve_lock(mod, info.cls, fn.value,
                                           local_aliases)
                    if rid in held_ids:
                        why = None
                # findings emit on the interprocedural pass only: its
                # held-set is a superset of the first pass's (with-items
                # that are calls resolve there), so emitting once there
                # is complete without double-reporting
                emit = why is not None and interprocedural
                if why is None and interprocedural:
                    callee_key = sym.resolve_call(mod, qual, info.cls, fn)
                    callee = sym.funcs.get(callee_key) if callee_key \
                        else None
                    if callee is not None:
                        for lock_id in callee.all_acquires:
                            note_edge(held, lock_id, node.lineno)
                        if callee.may_block:
                            why = (f"{callee_key[1]}() may block: "
                                   f"{callee.may_block}")
                            emit = True
                if emit:
                    held_desc = ", ".join(sorted(h for h, _ in held))
                    findings.append(Finding(
                        checker=self.id, path=mod.rel,
                        line=node.lineno,
                        message=(f"{qual}() holds {held_desc} across "
                                 f"a blocking call — {why}"),
                        hint="move the blocking call outside the "
                             "locked region (stage under the lock, "
                             "wait outside), or bound the wait with "
                             "a timeout",
                        key=(f"blocking:{qual}:{held_desc}:"
                             f"{_call_desc(node)}")))
            return acquired, released

        def resolve_with_item(item: ast.withitem, held: list,
                              line: int) -> list:
            """A with-item's locks: a lock expr, or a call to a function
            whose (transitive) summary acquires locks."""
            expr = item.context_expr
            lock_id = sym.resolve_lock(mod, info.cls, expr, local_aliases)
            if lock_id:
                if not interprocedural:
                    info.acquires.add(lock_id)
                note_edge(held, lock_id, line)
                return [lock_id]
            if isinstance(expr, ast.Call):
                callee_key = sym.resolve_call(mod, qual, info.cls,
                                              expr.func)
                if callee_key is not None and not interprocedural:
                    # the context call joins the summary: locks a
                    # helper like locked_collective() acquires must
                    # propagate into THIS function's all_acquires, or
                    # cycles through with-item helpers stay invisible
                    # to callers holding other locks
                    info.calls.add(callee_key)
                callee = sym.funcs.get(callee_key) if callee_key else None
                if interprocedural and callee is not None \
                        and callee.all_acquires:
                    for lid in sorted(callee.all_acquires):
                        note_edge(held, lid, line)
                    return sorted(callee.all_acquires)
            return []

        def walk_stmts(stmts: list, held: list) -> None:
            held = list(held)
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.ClassDef)):
                    continue        # walked separately, without `held`
                if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                    # track simple local lock aliases: x = self._lock
                    tgt = (stmt.targets[0] if isinstance(stmt, ast.Assign)
                           and len(stmt.targets) == 1 else
                           stmt.target if isinstance(stmt, ast.AnnAssign)
                           else None)
                    if isinstance(tgt, ast.Name) and stmt.value is not None:
                        lid = sym.resolve_lock(mod, info.cls, stmt.value,
                                               local_aliases)
                        if lid:
                            local_aliases[tgt.id] = lid
                if isinstance(stmt, ast.With):
                    info.hold_potential = True
                    inner = list(held)
                    for item in stmt.items:
                        got = resolve_with_item(item, inner, stmt.lineno)
                        for lid in got:
                            inner.append((lid, stmt.lineno))
                        if isinstance(item.context_expr, ast.Call):
                            for arg in (list(item.context_expr.args)
                                        + [kw.value for kw in
                                           item.context_expr.keywords]):
                                scan_expr(arg, held)
                    walk_stmts(stmt.body, inner)
                    continue
                if isinstance(stmt, (ast.If, ast.While)):
                    scan_expr(stmt.test, held)
                    walk_stmts(stmt.body, held)
                    walk_stmts(stmt.orelse, held)
                    continue
                if isinstance(stmt, (ast.For, ast.AsyncFor)):
                    scan_expr(stmt.iter, held)
                    walk_stmts(stmt.body, held)
                    walk_stmts(stmt.orelse, held)
                    continue
                if isinstance(stmt, ast.Try):
                    walk_stmts(stmt.body, held)
                    for h in stmt.handlers:
                        walk_stmts(h.body, held)
                    walk_stmts(stmt.orelse, held)
                    walk_stmts(stmt.finalbody, held)
                    continue
                acquired, released = scan_expr(stmt, held)
                if released:
                    # release() ends a bare-acquire region at this level
                    held = [(h, ln) for h, ln in held if h not in released]
                for lid in acquired:
                    # a bare .acquire() holds to the end of this block
                    held = held + [(lid, stmt.lineno)]

        body = getattr(info.node, "body", [])
        walk_stmts(body, [])
        if not interprocedural:
            info.all_acquires = set(info.acquires)
            if info.blocks:
                info.may_block = info.blocks[0][0]

    # ---- cycle reporting ----

    def _cycles(self, edges: dict, sym: _Symbols) -> list:
        graph: dict[str, set] = {}
        for (a, b) in edges:
            if a != b:
                graph.setdefault(a, set()).add(b)
                graph.setdefault(b, set())
        for scc in _tarjan(graph):
            if len(scc) < 2:
                continue
            cyc = sorted(scc)
            sites = []
            for (a, b), (rel, line, fq) in sorted(edges.items()):
                if a in scc and b in scc:
                    sites.append(f"{a} -> {b} at {rel}:{line} ({fq})")
            rel0, line0 = "", 0
            for (a, b), (rel, line, _fq) in sorted(edges.items()):
                if a in scc and b in scc:
                    rel0, line0 = rel, line
                    break
            yield Finding(
                checker=self.id, path=rel0, line=line0,
                message=("lock-order cycle: " + " / ".join(sites)
                         + " — two threads entering from different edges "
                           "deadlock"),
                hint="impose one global order (acquire "
                     f"{cyc[0]} first everywhere) or collapse the locks",
                key="cycle:" + "->".join(cyc))


def _call_desc(call: ast.Call) -> str:
    fn = call.func
    if isinstance(fn, ast.Attribute):
        return f".{fn.attr}"
    if isinstance(fn, ast.Name):
        return fn.id
    return "call"


def _walk_no_nested(expr: ast.AST):
    """ast.walk, but do not descend into lambdas/nested defs — their
    bodies execute later, not under the current locks."""
    stack = [expr]
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.Lambda, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _tarjan(graph: dict) -> list:
    """Strongly connected components (iterative Tarjan)."""
    index: dict = {}
    low: dict = {}
    on_stack: set = set()
    stack: list = []
    sccs: list = []
    counter = [0]

    for root in graph:
        if root in index:
            continue
        work = [(root, iter(sorted(graph.get(root, ()))))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            node, it = work[-1]
            advanced = False
            for nxt in it:
                if nxt not in index:
                    index[nxt] = low[nxt] = counter[0]
                    counter[0] += 1
                    stack.append(nxt)
                    on_stack.add(nxt)
                    work.append((nxt, iter(sorted(graph.get(nxt, ())))))
                    advanced = True
                    break
                if nxt in on_stack:
                    low[node] = min(low[node], index[nxt])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = set()
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.add(w)
                    if w == node:
                        break
                sccs.append(scc)
    return sccs
