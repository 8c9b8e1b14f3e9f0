"""lock-discipline: the poor-Python's `-race` for classes that own locks.

Three per-class rules, all derived from the class's own usage (no
annotations):

  1. **unguarded write** — an attribute that is assigned (or mutated via
     list/dict/set methods) inside `with self.<lock>` in one method is
     lock-guarded state; any OTHER method writing it without the lock is
     a data race.  `__init__` is exempt (construction happens-before
     publication).  Helpers whose contract is "caller holds the lock"
     carry an inline `tpu-vet: disable=lock` comment with the reason.

  2. **blocking call under lock** — while holding `with self.<lock>`:
     `time.sleep`, `<clock>.wait_until`, `Thread.join`, `serve_forever`,
     `Event.wait` (does NOT release the lock — unlike `Condition.wait`),
     and blocking `Queue.get/put` (the `_nowait` variants and
     `block=False` are fine).  A lock held across a blocking call stalls
     every thread behind it — the exact failure mode the reference
     avoids by keeping Go's mutexes around pure state transitions.

  3. **lock-order cycle** — a directed graph over (class, lock) nodes:
     edge A→B when B is acquired while A is held, either by nested
     `with` or through a same-class method call (closure over the
     class's own call graph).  Any cycle is a deadlock candidate;
     re-acquiring a non-reentrant Lock/Condition (a self-edge) is
     reported the same way.

With a phase-1 `Project` (v3, ``uses_project``), the cycle graph goes
project-wide and three interprocedural rules join, all riding the
per-function lockset summaries (`FunctionSummary.acquires_trans`,
``may_block``, ``mutates_params``, ``calls_params``):

  4. **cross-module lock-order cycle** — the (owner, lock) graph closes
     over RESOLVED calls anywhere in the project: `self._reg.snapshot()`
     acquiring the registry's lock while this class's lock is held is an
     edge, as is a callback registered with another class and invoked
     under that class's lock (the tenancy ``on_change`` →
     admission/placement shape).  Module-level locks (`_HOST_H2F_LOCK =
     threading.Lock()`) are graph nodes too.

  5. **helper-laundered write** (``lock-helper-mutation``) — passing a
     guarded container (`self.plan`) to a function whose summary says it
     mutates that parameter, at a call site not holding the guarding
     lock, is the same data race as rule 1 one frame removed.

  6. **transitive blocking** (``lock-blocking-transitive`` /
     ``lock-callback-blocking``) — a call made while holding a lock to a
     callee that MAY block (directly or further down), or a registered
     callback that may block invoked under the registrar's lock.

``check(module)`` with no project reproduces the per-class v2 pass
exactly — the both-ways regression tests in tests/test_vet.py rely on
it.  The project-wide graph and findings are derived ONCE per project
(``project.memo``) and sliced per module, so the parallel per-file sweep
pays for phase 2 once.
"""

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ..core import Finding
from ..project import (MUTATORS, FunctionSummary, LockNode, Project,
                       held_lockset, lock_label, lock_node_at)
from ..symbols import (LOCK_KINDS, NON_REENTRANT, ClassInfo, ModuleInfo,
                       dotted, walk_scope)

BLOCKING_NAMES = {"wait_until", "serve_forever"}

CONSTRUCTION = ("__init__", "__new__", "__del__", "__enter__", "__exit__")

# local snapshot spellings that preserve element identity: `cbs =
# list(self._subs)` still iterates the registered callbacks
_SNAPSHOT_FNS = ("list", "tuple", "sorted")


def _self_attr(node: ast.AST) -> Optional[str]:
    d = dotted(node)
    if d and d.startswith("self.") and d.count(".") == 1:
        return d.split(".", 1)[1]
    return None


class LockChecker:
    name = "lock"
    description = ("unguarded writes to lock-guarded attributes, blocking "
                   "calls under a lock, lock-order cycles (project-wide "
                   "with phase 1), helper-laundered writes, transitive "
                   "blocking")
    uses_project = True

    def check(self, module: ModuleInfo,
              project: Optional[Project] = None) -> Iterator[Finding]:
        edges: Dict[Tuple[str, str], List[Tuple[Tuple[str, str], ast.AST]]] = {}
        for cls in module.classes:
            locks = cls.lock_attrs()
            if not locks:
                continue
            yield from self._unguarded_writes(module, cls, locks)
            yield from self._blocking_under_lock(module, cls, locks)
            if project is None:
                self._order_edges(module, cls, locks, edges)
        if project is None:
            yield from self._cycles(module, edges)
            return
        global_pass = project.memo(
            "lock-global", lambda: _GlobalLockPass(self, project))
        yield from global_pass.findings_for(module.rel)

    # -- rule 1: unguarded writes -------------------------------------------

    def _writes(self, cls: ClassInfo, fn: ast.AST):
        """(attr, node) for every mutation of a self attribute in `fn`:
        assignment, augmented assignment, del, subscript store, or a
        mutating method call (append/update/...)."""
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                # unpack tuple/list targets: the snapshot-and-null idiom
                # `local, self.x = self.x, None` writes self.x
                flat = []
                for t in targets:
                    if isinstance(t, (ast.Tuple, ast.List)):
                        flat.extend(t.elts)
                    else:
                        flat.append(t)
                for t in flat:
                    attr = _self_attr(t)
                    if attr:
                        yield attr, node
                    elif isinstance(t, ast.Subscript):
                        attr = _self_attr(t.value)
                        if attr:
                            yield attr, node
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    attr = _self_attr(t)
                    if attr:
                        yield attr, node
                    elif isinstance(t, ast.Subscript):
                        attr = _self_attr(t.value)
                        if attr:
                            yield attr, node
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in MUTATORS:
                attr = _self_attr(node.func.value)
                if attr:
                    yield attr, node

    def _held_locks(self, module: ModuleInfo, node: ast.AST,
                    locks: List[str]) -> Set[str]:
        held = set()
        for d in module.withs_holding(node):
            attr = d.split(".", 1)[1] if d.startswith("self.") else None
            if attr in locks:
                held.add(attr)
        return held

    def _guarded_attrs(self, module: ModuleInfo, cls: ClassInfo,
                       locks: List[str]) -> Set[str]:
        """Attributes this class treats as lock-guarded state: written
        at least once while holding one of the class's locks."""
        guarded: Set[str] = set()
        for name, fn in cls.methods.items():
            for attr, node in self._writes(cls, fn):
                if attr in cls.attr_kinds and \
                        cls.attr_kinds[attr] in LOCK_KINDS:
                    continue            # the lock object itself
                if self._held_locks(module, node, locks):
                    guarded.add(attr)
        return guarded

    def _unguarded_writes(self, module: ModuleInfo, cls: ClassInfo,
                          locks: List[str]) -> Iterator[Finding]:
        guarded = self._guarded_attrs(module, cls, locks)
        if not guarded:
            return
        for name, fn in cls.methods.items():
            if name in CONSTRUCTION:
                continue
            for attr, node in self._writes(cls, fn):
                if attr in guarded \
                        and not self._held_locks(module, node, locks):
                    yield Finding(
                        checker=self.name, code="lock-unguarded-write",
                        message=(f"{cls.name}.{name} mutates self.{attr} "
                                 "without holding the lock that guards it "
                                 "elsewhere in the class"),
                        path=module.rel, line=node.lineno,
                        col=node.col_offset)

    # -- rule 2: blocking calls under a lock --------------------------------

    def _blocking_reason(self, module: ModuleInfo, cls: ClassInfo,
                         node: ast.Call) -> Optional[str]:
        qual = module.resolve(dotted(node.func) or "")
        if qual == "time.sleep":
            return "time.sleep"
        if not isinstance(node.func, ast.Attribute):
            return None
        meth = node.func.attr
        if meth in BLOCKING_NAMES:
            return f".{meth}()"
        recv = _self_attr(node.func.value)
        kind = cls.attr_kinds.get(recv) if recv else None
        if meth == "join" and kind == "thread":
            return f"Thread.join on self.{recv}"
        if meth == "wait" and kind == "event":
            # Event.wait keeps the lock held; Condition.wait releases it
            return f"Event.wait on self.{recv}"
        if meth in ("get", "put") and kind == "queue":
            for kw in node.keywords:
                if kw.arg == "block" \
                        and isinstance(kw.value, ast.Constant) \
                        and kw.value.value is False:
                    return None
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and node.args[0].value is False:
                return None
            return f"blocking Queue.{meth} on self.{recv}"
        return None

    def _blocking_under_lock(self, module: ModuleInfo, cls: ClassInfo,
                             locks: List[str]) -> Iterator[Finding]:
        for name, fn in cls.methods.items():
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                held = self._held_locks(module, node, locks)
                if not held:
                    continue
                # waiting on the very condition you hold is the cv
                # pattern, not a stall: Condition.wait releases it
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("wait", "wait_for"):
                    recv = _self_attr(node.func.value)
                    if recv in held and \
                            cls.attr_kinds.get(recv) == "condition":
                        continue
                reason = self._blocking_reason(module, cls, node)
                if reason:
                    yield Finding(
                        checker=self.name, code="lock-blocking-call",
                        message=(f"{cls.name}.{name} makes a blocking call "
                                 f"({reason}) while holding "
                                 f"self.{sorted(held)[0]}"),
                        path=module.rel, line=node.lineno,
                        col=node.col_offset)

    # -- rule 3 (v2, project=None): per-class lock-order cycles --------------

    def _acquires(self, cls: ClassInfo, locks: List[str]
                  ) -> Dict[str, Set[str]]:
        """method -> locks it may acquire, closed over same-class calls."""
        direct: Dict[str, Set[str]] = {}
        calls: Dict[str, Set[str]] = {}
        for name, fn in cls.methods.items():
            acq, callees = set(), set()
            for node in ast.walk(fn):
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    for item in node.items:
                        attr = _self_attr(item.context_expr)
                        if attr in locks:
                            acq.add(attr)
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.value.id == "self" \
                        and node.func.attr in cls.methods:
                    callees.add(node.func.attr)
            direct[name] = acq
            calls[name] = callees
        closed = {m: set(s) for m, s in direct.items()}
        changed = True
        while changed:
            changed = False
            for m, callees in calls.items():
                for c in callees:
                    extra = closed.get(c, set()) - closed[m]
                    if extra:
                        closed[m] |= extra
                        changed = True
        return closed

    def _order_edges(self, module: ModuleInfo, cls: ClassInfo,
                     locks: List[str], edges) -> None:
        closed = self._acquires(cls, locks)
        for name, fn in cls.methods.items():
            for node in ast.walk(fn):
                acquired: Set[str] = set()
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    for item in node.items:
                        attr = _self_attr(item.context_expr)
                        if attr in locks:
                            acquired.add(attr)
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.value.id == "self" \
                        and node.func.attr in cls.methods:
                    acquired |= closed.get(node.func.attr, set())
                if not acquired:
                    continue
                held = self._held_locks(module, node, locks)
                for h in held:
                    for a in acquired:
                        if a == h and \
                                cls.attr_kinds.get(a) not in NON_REENTRANT:
                            continue    # RLock re-entry is fine
                        src, dst = (cls.name, h), (cls.name, a)
                        edges.setdefault(src, []).append((dst, node))

    def _cycles(self, module: ModuleInfo, edges) -> Iterator[Finding]:
        seen_cycles = set()
        for start in edges:
            stack = [(start, [start])]
            while stack:
                cur, path = stack.pop()
                for dst, node in edges.get(cur, ()):  # noqa: B007
                    if dst == start:
                        cyc = tuple(sorted(set(path)))
                        if cyc in seen_cycles:
                            continue
                        seen_cycles.add(cyc)
                        pretty = " -> ".join(
                            f"{c}.{a}" for c, a in path + [start])
                        yield Finding(
                            checker=self.name, code="lock-order-cycle",
                            message=("lock-order cycle (deadlock "
                                     f"candidate): {pretty}"),
                            path=module.rel, line=node.lineno,
                            col=node.col_offset)
                    elif dst not in path and len(path) < 6:
                        stack.append((dst, path + [dst]))


# -- v3: the project-wide pass ------------------------------------------------


class _GlobalLockPass:
    """Everything the lock checker derives from a whole project, built
    once per `Project` and sliced per module: the global (owner, lock)
    order graph + its cycles, helper-laundered writes, and transitive /
    callback blocking.  Cycle findings attach to the module holding the
    cycle-closing edge; call-site findings attach to the call site's
    module, so per-module suppressions keep their usual scope."""

    def __init__(self, checker: LockChecker, project: Project):
        self.checker = checker
        self.project = project
        # lock node -> kind ("lock" | "rlock" | "condition")
        self.kinds: Dict[LockNode, str] = {}
        # src node -> [(dst node, module rel, line, col)]
        self.edges: Dict[LockNode, List[Tuple[LockNode, str, int, int]]] = {}
        self._findings: Dict[str, List[Finding]] = {}
        self._guarded: Dict[Tuple[str, str], Set[str]] = {}
        self._collect_kinds()
        callbacks = self._callback_tables()
        self._build_edges(callbacks)
        self._cycle_findings()

    def findings_for(self, rel: str) -> List[Finding]:
        return self._findings.get(rel, [])

    def _emit(self, f: Finding) -> None:
        self._findings.setdefault(f.path, []).append(f)

    # -- tables ---------------------------------------------------------------

    def _collect_kinds(self) -> None:
        for m in self.project.modules:
            for name, kind in m.module_locks.items():
                self.kinds[(m.rel, "", name)] = kind
            for cls in m.classes:
                for attr, kind in cls.attr_kinds.items():
                    if kind in LOCK_KINDS:
                        self.kinds[(m.rel, cls.name, attr)] = kind

    def _callback_tables(self):
        """registrars[(rel, "Cls.meth")] -> [(param, attr)] for methods
        that store a parameter into a self container/slot; invokes[(rel,
        Cls, attr)] -> [(held lockset, node, rel)] for sites where that
        attribute's contents (or the attribute itself) are CALLED —
        directly, through a loop, or via a list()/tuple()/sorted()
        snapshot one alias hop away."""
        registrars: Dict[Tuple[str, str], List[Tuple[str, str]]] = {}
        invokes: Dict[Tuple[str, str, str],
                      List[Tuple[Set[LockNode], ast.AST]]] = {}
        for key, s in self.project.functions.items():
            if s.cls is None:
                continue
            m, cls = s.module, s.cls
            params = set(s.params) - {"self"}
            # registration: self.<A>.append(q) / self.<A>[k] = q /
            # self.<A> = q with q a parameter
            for node in walk_scope(s.node):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("append", "add", "insert") \
                        and node.args:
                    attr = _self_attr(node.func.value)
                    arg = node.args[-1]
                    if attr and isinstance(arg, ast.Name) \
                            and arg.id in params:
                        registrars.setdefault(key, []).append((arg.id, attr))
                elif isinstance(node, ast.Assign) \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id in params:
                    for t in node.targets:
                        attr = _self_attr(t)
                        if attr is None and isinstance(t, ast.Subscript):
                            attr = _self_attr(t.value)
                        if attr:
                            registrars.setdefault(key, []).append(
                                (node.value.id, attr))
            # invocation sites of attr contents
            self._invoke_sites(m, cls, s, invokes)
        return registrars, invokes

    def _snapshot_of(self, node: ast.AST) -> Optional[str]:
        """`self.A`, `list(self.A)`, `tuple(self.A)`, `sorted(self.A)`
        -> "A"; None otherwise."""
        attr = _self_attr(node)
        if attr:
            return attr
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in _SNAPSHOT_FNS and len(node.args) == 1:
            return _self_attr(node.args[0])
        return None

    def _invoke_sites(self, m: ModuleInfo, cls: ClassInfo,
                      s: FunctionSummary, invokes) -> None:
        aliases: Dict[str, str] = {}       # local name -> attr
        for node in walk_scope(s.node):
            if isinstance(node, ast.Assign):
                attr = self._snapshot_of(node.value)
                if attr:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            aliases[t.id] = attr
        loopvars: Dict[str, str] = {}      # loop variable -> attr
        for node in walk_scope(s.node):
            if isinstance(node, (ast.For, ast.AsyncFor)) \
                    and isinstance(node.target, ast.Name):
                attr = self._snapshot_of(node.iter)
                if attr is None and isinstance(node.iter, ast.Name):
                    attr = aliases.get(node.iter.id)
                if attr:
                    loopvars[node.target.id] = attr
        for node in walk_scope(s.node):
            if not isinstance(node, ast.Call):
                continue
            attr = None
            f = node.func
            if isinstance(f, ast.Name) and f.id in loopvars:
                attr = loopvars[f.id]
            elif isinstance(f, ast.Subscript):
                attr = self._snapshot_of(f.value)
            else:
                d = _self_attr(f)
                # calling the slot itself: `self._on_change(...)`
                if d and cls.attr_kinds.get(d) is None \
                        and d not in cls.methods:
                    attr = d
            if attr is None:
                continue
            held = held_lockset(m, cls, node)
            invokes.setdefault((m.rel, cls.name, attr), []).append(
                (held, node))

    # -- the global order graph ----------------------------------------------

    def _add_edge(self, src: LockNode, dst: LockNode, rel: str,
                  node: ast.AST) -> None:
        if src == dst and self.kinds.get(dst) not in NON_REENTRANT:
            return                          # RLock re-entry is fine
        self.edges.setdefault(src, []).append(
            (dst, rel, node.lineno, node.col_offset))

    def _build_edges(self, callbacks) -> None:
        registrars, invokes = callbacks
        proj = self.project
        for key, s in proj.functions.items():
            m, cls = s.module, s.cls
            # nested `with` acquisitions
            for node in walk_scope(s.node):
                if not isinstance(node, (ast.With, ast.AsyncWith)):
                    continue
                acquired = set()
                for item in node.items:
                    d = dotted(item.context_expr)
                    ln = lock_node_at(m, cls, d) if d else None
                    if ln is not None:
                        acquired.add(ln)
                if not acquired:
                    continue
                held = held_lockset(m, cls, node)
                for h in held:
                    for a in acquired:
                        self._add_edge(h, a, m.rel, node)
            # resolved calls: the callee's transitive lockset is acquired
            # while everything at the site is held; blocking callees under
            # a held lock are findings in their own right
            for call, ckey in s.calls:
                callee = proj.functions.get(ckey) if ckey else None
                if callee is None:
                    continue
                held = held_lockset(m, cls, call)
                if held:
                    for h in held:
                        for a in callee.acquires_trans:
                            self._add_edge(h, a, m.rel, call)
                    self._transitive_blocking(s, call, callee, held)
                self._helper_mutation(s, call, callee)
                self._callback_registration(
                    s, call, ckey, callee, registrars, invokes)

    # -- interprocedural findings --------------------------------------------

    def _transitive_blocking(self, s: FunctionSummary, call: ast.Call,
                             callee: FunctionSummary,
                             held: Set[LockNode]) -> None:
        if callee.may_block is None:
            return
        # the per-class rule 2 already covers direct blocking primitives
        if s.cls is not None and self.checker._blocking_reason(
                s.module, s.cls, call) is not None:
            return
        label = lock_label(sorted(held)[0])
        self._emit(Finding(
            checker=self.checker.name, code="lock-blocking-transitive",
            message=(f"{s.qual} calls {callee.display}, which may block "
                     f"({callee.may_block}), while holding {label}"),
            path=s.module.rel, line=call.lineno, col=call.col_offset))

    def _helper_mutation(self, s: FunctionSummary, call: ast.Call,
                         callee: FunctionSummary) -> None:
        cls = s.cls
        if cls is None or not callee.mutates_params:
            return
        locks = cls.lock_attrs()
        if not locks:
            return
        mname = s.qual.rsplit(".", 1)[-1]
        if mname in CONSTRUCTION:
            return
        if self.checker._held_locks(s.module, call, locks):
            return
        gkey = (s.module.rel, cls.name)
        if gkey not in self._guarded:
            self._guarded[gkey] = self.checker._guarded_attrs(
                s.module, cls, locks)
        guarded = self._guarded[gkey]
        for p in callee.mutates_params:
            bound = callee.arg_param(call, p)
            attr = _self_attr(bound) if bound is not None else None
            if attr and attr in guarded:
                self._emit(Finding(
                    checker=self.checker.name, code="lock-helper-mutation",
                    message=(f"{cls.name}.{mname} passes self.{attr} to "
                             f"{callee.display}, which mutates it, without "
                             "holding the lock that guards it elsewhere in "
                             "the class"),
                    path=s.module.rel, line=call.lineno,
                    col=call.col_offset))

    def _callback_registration(self, s: FunctionSummary, call: ast.Call,
                               ckey, callee: FunctionSummary,
                               registrars, invokes) -> None:
        """`other.subscribe(self.on_event)`: every site where the
        registrar's class invokes the stored slot contributes edges from
        the locks held THERE to whatever the callback acquires — and a
        blocking callback invoked under the registrar's lock is the
        listener-under-lock stall outright."""
        regs = registrars.get(ckey)
        if not regs or s.cls is None or callee.cls is None:
            return
        for q, attr in regs:
            bound = callee.arg_param(call, q)
            mattr = _self_attr(bound) if bound is not None else None
            if mattr is None:
                continue
            cb = self.project.functions.get(
                (s.module.rel, f"{s.cls.name}.{mattr}"))
            if cb is None:
                continue
            for held, inode in invokes.get(
                    (callee.module.rel, callee.cls.name, attr), ()):
                for h in held:
                    for a in cb.acquires_trans:
                        self._add_edge(h, a, callee.module.rel, inode)
                if held and cb.may_block is not None:
                    label = lock_label(sorted(held)[0])
                    self._emit(Finding(
                        checker=self.checker.name,
                        code="lock-callback-blocking",
                        message=(f"{s.qual} registers self.{mattr} with "
                                 f"{callee.display}; it is invoked holding "
                                 f"{label} and may block "
                                 f"({cb.may_block})"),
                        path=s.module.rel, line=call.lineno,
                        col=call.col_offset))

    # -- cycles ---------------------------------------------------------------

    def _cycle_findings(self) -> None:
        seen_cycles = set()
        for start in self.edges:
            stack = [(start, [start])]
            while stack:
                cur, path = stack.pop()
                for dst, rel, line, col in self.edges.get(cur, ()):
                    if dst == start:
                        cyc = tuple(sorted(set(path)))
                        if cyc in seen_cycles:
                            continue
                        seen_cycles.add(cyc)
                        pretty = " -> ".join(
                            lock_label(n) for n in path + [start])
                        self._emit(Finding(
                            checker=self.checker.name,
                            code="lock-order-cycle",
                            message=("lock-order cycle (deadlock "
                                     f"candidate): {pretty}"),
                            path=rel, line=line, col=col))
                    elif dst not in path and len(path) < 6:
                        stack.append((dst, path + [dst]))
