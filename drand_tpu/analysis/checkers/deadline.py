"""deadline: every blocking call is reachable from a threaded budget.

r06 wedged for 2h07m on 42 hung probes: each probe's subprocess had no
timeout, the caller had no deadline parameter to thread, and nothing
above it could bound the wait without killing the process.  The fix
pattern (net/client.py `_unary`, resilience budgets) is always the
same shape — a `timeout=`/`deadline=` parameter that
REACHES the blocking primitive — and this checker enforces that shape
statically, the compile-time half of ROADMAP item 1's fail-fast
preflight.

Scope: `net/`, `beacon/`, and the operator tools where r06 actually hung
(`bench.py`, `autotune.py`, `loadgen.py`, `chaos_smoke.py`).  Test code
is exempt (pytest owns the watchdog there).

Codes:

  * ``deadline-unbounded-call`` — a recognized blocking primitive
    (`subprocess.run/call/check_call/check_output`, `urlopen`,
    `socket.create_connection`, `.communicate()`) with no timeout
    argument, or an explicit ``timeout=None``.
  * ``deadline-not-threaded`` — a call omits a parameter the callee's
    phase-1 summary marks ``required_deadline``: the callee passes that
    parameter straight into a blocking call with no fallback, so an
    omitting caller runs unbounded.  (Parameters the callee defaults
    with ``p or DEFAULT`` / ``if p is None`` are self-bounding and never
    required — net/client.py's `timeout or self.timeout` idiom stays
    clean by design.)
"""

import ast
import os
from typing import Iterator, Optional

from ..core import Finding
from ..symbols import ModuleInfo

SCOPES = ("net/", "beacon/")
TOOL_FILES = {"bench.py", "autotune.py", "loadgen.py", "chaos_smoke.py",
              # the fleet harness lives under tests/ but is NOT exempt:
              # pytest's watchdog can't unwedge a supervisor stuck in a
              # subprocess wait — a hung fleet run must die in minutes
              "fleet.py"}

# method-shaped socket blockers: with no `settimeout` discipline in the
# enclosing class these wait forever (the chaos proxy's accept loop and
# pump recv are the canonical sites)
SOCKET_BLOCKERS = ("accept", "recv")


def _is_test_code(rel: str) -> bool:
    base = os.path.basename(rel)
    return base.startswith("test_") or base.endswith("_test.py") \
        or rel.startswith("tests/") or "/tests/" in rel


def _in_scope(rel: str) -> bool:
    if os.path.basename(rel) in TOOL_FILES:
        return True         # before the test exemption: tests/fleet.py
    if _is_test_code(rel):
        return False
    return any(rel.startswith(s) or f"/{s}" in f"/{rel}" for s in SCOPES)


def _has_settimeout(tree: ast.AST) -> bool:
    """True when the subtree ever arms a non-None socket timeout — the
    discipline that turns accept()/recv() into bounded poll slices."""
    for n in ast.walk(tree):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr == "settimeout" and n.args \
                and not (isinstance(n.args[0], ast.Constant)
                         and n.args[0].value is None):
            return True
    return False


class DeadlineChecker:
    name = "deadline"
    description = ("blocking RPC/subprocess calls must be bounded and "
                   "budget/deadline/timeout params threaded from callers")
    uses_project = True

    def check(self, module: ModuleInfo,
              project: Optional[object] = None) -> Iterator[Finding]:
        if not _in_scope(module.rel):
            return
        yield from self._socket_loops(module)
        from ..project import blocking_call
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            info = blocking_call(module, node)
            if info is not None:
                label, expr = info
                if expr is None:
                    yield Finding(
                        checker=self.name, code="deadline-unbounded-call",
                        message=(f"blocking call {label} has no timeout; "
                                 "an unreachable peer holds this thread "
                                 "forever (the r06 hung-probe class)"),
                        path=module.rel, line=node.lineno,
                        col=node.col_offset)
                continue
            if project is None:
                continue
            callee = project.resolve_call(module, node)
            if callee is None or not callee.required_deadline:
                continue
            for p in sorted(callee.required_deadline):
                if callee.arg_param(node, p) is None:
                    yield Finding(
                        checker=self.name, code="deadline-not-threaded",
                        message=(f"call to {callee.display} omits `{p}`, "
                                 "which that function passes straight to a "
                                 "blocking call with no fallback — thread "
                                 "a budget from this caller"),
                        path=module.rel, line=node.lineno,
                        col=node.col_offset)

    def _socket_loops(self, module: ModuleInfo) -> Iterator[Finding]:
        """accept()/recv() with no settimeout discipline in the tightest
        enclosing class (or the module, for free functions): the socket
        blocks forever, so a wedged link hangs supervisor teardown."""
        def walk(node: ast.AST, owner: ast.AST) -> Iterator[Finding]:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.Call) \
                        and isinstance(child.func, ast.Attribute) \
                        and child.func.attr in SOCKET_BLOCKERS \
                        and not _has_settimeout(owner):
                    yield Finding(
                        checker=self.name, code="deadline-unbounded-call",
                        message=(f".{child.func.attr}() with no settimeout "
                                 "discipline in scope; a silent peer holds "
                                 "this thread forever — arm a poll-slice "
                                 "timeout on the socket"),
                        path=module.rel, line=child.lineno,
                        col=child.col_offset)
                nxt = child if isinstance(child, ast.ClassDef) else owner
                yield from walk(child, nxt)
        yield from walk(module.tree, module.tree)
