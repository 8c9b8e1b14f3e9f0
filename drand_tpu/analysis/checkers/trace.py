"""trace-safety: JAX pitfalls in the device code (ops/, crypto/batch.py).

Inside a jitted function every array argument is a tracer: Python `if`/
`while`/`for` on one raises (or silently specializes) at trace time,
`.item()/int()/float()` forces a device sync that kills the whole
pipelined batch, and mutating captured Python state bakes one trace's
view into the compiled program forever (the classic "works once, wrong
on the second call" bug).  The repo has already shipped one of these —
the `crypto/batch.py` pad-lane mask shadowing a traced `n` (CHANGES.md,
PR 1) — which is exactly the class this checker pins down.

Scope: files under ops/ and crypto/batch.py (SCOPES) — the rest of the
codebase is host code where Python control flow is the point.

A second, host-level pass (`sync-in-loop`, ISSUE 10) covers crypto/
orchestration code: synchronous device readback (np.asarray / bool() /
int() / float() / .item() / .block_until_ready() / jax.device_get) on a
device-produced value INSIDE a per-chunk for/while loop serializes the
whole stream — every iteration pays a full device round trip (the r5
finding: one dispatch latency of pure stall per chunk).  Hot-path loops
must stay async (pack/dispatch/resolve with a depth-k window) and sync
once per stream.  Device taint: values from `dispatch_packed`/
`_rlc_dispatch` calls or from invoking a compiled `*_pipeline` object.

Taint: parameters of a jitted function are traced; values derived from
them are traced; `.shape/.ndim/.dtype/.size`, `len()`, and parameters
named in `static_argnums`/`static_argnames` are static and break the
chain.  Conservative by design: only Name-rooted taint is tracked, so a
finding is near-certainly real.
"""

import ast
from typing import Iterator, List, Optional, Set

from ..core import Finding
from ..symbols import ModuleInfo, dotted

SCOPES = ("ops/", "crypto/batch.py")
# the sync-in-loop pass covers the crypto/ hot-path orchestration code
SYNC_SCOPES = ("crypto/",)

STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}
STATIC_CALLS = {"len", "isinstance", "type", "range"}  # range(static) common
CONCRETIZERS = {"int", "float", "bool", "complex"}
CONCRETIZE_METHODS = {"item", "tolist"}
JIT_NAMES = {"jit", "jax.jit", "pjit", "jax.pjit"}

# sync-in-loop: producers whose results are device values (async until
# read), sync sinks that force the readback, and the unambiguous
# blocking calls that are findings on their own
SYNC_PRODUCER_METHODS = {"dispatch_packed", "_rlc_dispatch"}
SYNC_SINKS = {"bool", "int", "float"}
SYNC_SINK_METHODS = {"item", "tolist"}
SYNC_BLOCKERS = {"jax.block_until_ready", "jax.device_get"}

# host-hash-in-loop (ISSUE 14): per-lane host hashing inside a loop on a
# hot-path module is O(n) GIL-bound work per chunk — the exact stage the
# device hash-to-field front removed from steady-state packing.  Flags
# direct hashlib constructions AND the known host hash-to-field/digest
# helpers when called per element in a for/while/comprehension.
# Sanctioned sites (the parity oracle and the below-threshold host
# fallback) carry justified `tpu-vet: disable=trace` suppressions.
HASH_SCOPES = ("ops/", "crypto/batch.py", "crypto/partials.py",
               "crypto/verify_service.py")
HOST_HASH_HELPERS = {"hash_to_field_fp", "hash_to_field_fp2",
                     "expand_message_xmd", "hash_to_curve_g1",
                     "hash_to_curve_g2", "digest_beacon"}


def _in_scope(rel: str) -> bool:
    return any(rel.startswith(s) or f"/{s}" in f"/{rel}" for s in SCOPES) \
        or rel.endswith("batch.py") and "crypto" in rel


def _in_sync_scope(rel: str) -> bool:
    return any(rel.startswith(s) or f"/{s}" in f"/{rel}"
               for s in SYNC_SCOPES)


def _in_hash_scope(rel: str) -> bool:
    return any(rel.startswith(s) or f"/{s}" in f"/{rel}"
               for s in HASH_SCOPES)


class TraceChecker:
    name = "trace"
    description = ("Python control flow on tracers, .item()/int() inside "
                   "jit, mutated captured state")

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if _in_sync_scope(module.rel):
            yield from self._check_sync_loops(module)
        if _in_hash_scope(module.rel):
            yield from self._check_hash_loops(module)
        if not _in_scope(module.rel):
            return
        for fn, static in self._jitted_functions(module):
            yield from self._check_jitted(module, fn, static)

    # -- host-hash-in-loop (hot-path pack stage pass) ------------------------

    _LOOPY = (ast.For, ast.While, ast.ListComp, ast.SetComp,
              ast.GeneratorExp, ast.DictComp)

    def _check_hash_loops(self, module: ModuleInfo) -> Iterator[Finding]:
        jitted = {fn for fn, _ in self._jitted_functions(module)}
        seen = set()
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node not in jitted:
                for f in self._hash_loops_in(module, node):
                    key = (f.line, f.col)
                    if key not in seen:         # nested loops: flag once
                        seen.add(key)
                        yield f

    def _is_host_hash_call(self, module: ModuleInfo,
                           call: ast.Call) -> Optional[str]:
        d = module.resolve(dotted(call.func) or "") or ""
        if d.startswith("hashlib."):
            return d
        leaf = d.rsplit(".", 1)[-1]
        if leaf in HOST_HASH_HELPERS:
            return leaf + "()"
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in HOST_HASH_HELPERS:
            return call.func.attr + "()"
        return None

    def _hash_loops_in(self, module: ModuleInfo,
                       fn: ast.AST) -> Iterator[Finding]:
        for loop in self._walk_scope(fn):
            if not isinstance(loop, self._LOOPY):
                continue
            for node in self._walk_scope(loop):
                if node is loop or not isinstance(node, ast.Call):
                    continue
                what = self._is_host_hash_call(module, node)
                if what:
                    yield Finding(
                        checker=self.name, code="trace-host-hash-in-loop",
                        message=(f"per-lane host hash {what} inside a "
                                 f"loop in {fn.name}() is O(n) GIL-bound "
                                 "pack work per chunk; ship raw message "
                                 "words and hash on device "
                                 "(ops/h2c.py device hash-to-field), or "
                                 "suppress at a sanctioned parity-oracle/"
                                 "fallback site"),
                        path=module.rel, line=node.lineno,
                        col=node.col_offset)

    # -- sync-in-loop (host orchestration pass) ------------------------------

    @staticmethod
    def _walk_scope(fn: ast.AST):
        """Walk a function's OWN body without descending into nested
        function definitions — each nested function is its own scope and
        gets its own standalone visit (a jitted nested `run` is traced
        device code and must not be judged by host-loop rules through
        its enclosing factory)."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                stack.extend(ast.iter_child_nodes(node))

    def _check_sync_loops(self, module: ModuleInfo) -> Iterator[Finding]:
        jitted = {fn for fn, _ in self._jitted_functions(module)}
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node not in jitted:
                yield from self._sync_loops_in(module, node)

    def _device_tainted(self, module: ModuleInfo, fn: ast.AST) -> Set[str]:
        """Names in `fn` bound to device values (async until read):
        results of dispatch_packed/_rlc_dispatch, or of calling a name
        that was itself bound from a `*_pipeline*` factory call."""
        device_fns: Set[str] = set()
        tainted: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for node in self._walk_scope(fn):
                if not (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)):
                    continue
                callee = dotted(node.value.func) or ""
                leaf = callee.rsplit(".", 1)[-1]
                is_dev = (leaf in SYNC_PRODUCER_METHODS
                          or leaf in device_fns
                          or (isinstance(node.value.func, ast.Name)
                              and node.value.func.id in device_fns))
                is_factory = "_pipeline" in leaf
                for t in node.targets:
                    if not isinstance(t, ast.Name):
                        continue
                    if is_dev and t.id not in tainted:
                        tainted.add(t.id)
                        changed = True
                    if is_factory and t.id not in device_fns:
                        device_fns.add(t.id)
                        changed = True
        self._device_fns = device_fns
        return tainted

    def _is_device_expr(self, module: ModuleInfo, e: ast.AST,
                        tainted: Set[str]) -> bool:
        for sub in ast.walk(e):
            if isinstance(sub, ast.Name) and sub.id in tainted:
                return True
            if isinstance(sub, ast.Call):
                callee = dotted(sub.func) or ""
                leaf = callee.rsplit(".", 1)[-1]
                if leaf in SYNC_PRODUCER_METHODS \
                        or leaf in getattr(self, "_device_fns", set()):
                    return True
        return False

    def _sync_loops_in(self, module: ModuleInfo,
                       fn: ast.AST) -> Iterator[Finding]:
        tainted = self._device_tainted(module, fn)
        for loop in self._walk_scope(fn):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in self._walk_scope(loop):
                if node is loop or not isinstance(node, ast.Call):
                    continue
                d = module.resolve(dotted(node.func) or "")
                if d in SYNC_BLOCKERS:
                    yield self._sync_finding(module, fn, node,
                                             d.rsplit(".", 1)[-1] + "()")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "block_until_ready":
                    yield self._sync_finding(module, fn, node,
                                             ".block_until_ready()")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in SYNC_SINK_METHODS \
                        and self._is_device_expr(module, node.func.value,
                                                 tainted):
                    yield self._sync_finding(module, fn, node,
                                             f".{node.func.attr}()")
                elif ((isinstance(node.func, ast.Name)
                       and node.func.id in SYNC_SINKS)
                      or d == "numpy.asarray") and node.args \
                        and self._is_device_expr(module, node.args[0],
                                                 tainted):
                    label = d.rsplit(".", 1)[-1] if d == "numpy.asarray" \
                        else node.func.id
                    yield self._sync_finding(module, fn, node,
                                             f"{label}()")

    def _sync_finding(self, module: ModuleInfo, fn: ast.AST,
                      node: ast.AST, what: str) -> Finding:
        return Finding(
            checker=self.name, code="trace-sync-in-loop",
            message=(f"synchronous device readback {what} inside a "
                     f"per-chunk loop in {fn.name}() serializes the "
                     "stream (one interconnect round trip per "
                     "iteration); keep the loop async "
                     "(pack/dispatch/resolve, depth-k window) and sync "
                     "once per stream"),
            path=module.rel, line=node.lineno, col=node.col_offset)

    # -- jit discovery -------------------------------------------------------

    def _jit_decorator(self, module: ModuleInfo,
                       dec: ast.AST) -> Optional[ast.Call]:
        """Returns the jit Call node (for static_arg* extraction) or a
        dummy marker when the decorator is a bare `@jit`."""
        d = dotted(dec)
        if d and module.resolve(d) in JIT_NAMES:
            return ast.Call(func=dec, args=[], keywords=[])
        if isinstance(dec, ast.Call):
            d = dotted(dec.func)
            if d and module.resolve(d) in JIT_NAMES:
                return dec
            # functools.partial(jax.jit, static_argnums=...)
            if d and module.resolve(d).endswith("partial") and dec.args:
                inner = dotted(dec.args[0])
                if inner and module.resolve(inner) in JIT_NAMES:
                    return dec
        return None

    def _static_params(self, fn: ast.AST, call: ast.Call) -> Set[str]:
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        static: Set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                nums: List[int] = []
                if isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, int):
                    nums = [kw.value.value]
                elif isinstance(kw.value, (ast.Tuple, ast.List)):
                    nums = [e.value for e in kw.value.elts
                            if isinstance(e, ast.Constant)]
                for n in nums:
                    if 0 <= n < len(params):
                        static.add(params[n])
            elif kw.arg == "static_argnames":
                if isinstance(kw.value, ast.Constant):
                    static.add(str(kw.value.value))
                elif isinstance(kw.value, (ast.Tuple, ast.List)):
                    static |= {str(e.value) for e in kw.value.elts
                               if isinstance(e, ast.Constant)}
        return static

    def _jitted_functions(self, module: ModuleInfo):
        # decorated defs
        wrapped: Set[str] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d and module.resolve(d) in JIT_NAMES and node.args:
                    inner = node.args[0]
                    if isinstance(inner, ast.Name):
                        wrapped.add(inner.id)   # f2 = jax.jit(f)
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for dec in node.decorator_list:
                call = self._jit_decorator(module, dec)
                if call is not None:
                    yield node, self._static_params(node, call)
                    break
            else:
                if node.name in wrapped:
                    yield node, set()

    # -- taint + findings ----------------------------------------------------

    def _is_static_expr(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Attribute) and node.attr in STATIC_ATTRS:
            return True
        if isinstance(node, ast.Subscript):
            # x.shape[0] is static
            return self._is_static_expr(node.value)
        if isinstance(node, ast.Call):
            d = dotted(node.func)
            if d and d.rsplit(".", 1)[-1] in STATIC_CALLS | {"shape"}:
                return True
        return False

    def _mentions_tainted(self, node: ast.AST, tainted: Set[str]
                          ) -> Optional[str]:
        for sub in ast.walk(node):
            if self._is_static_expr(sub):
                continue
            if isinstance(sub, ast.Name) and sub.id in tainted:
                # static wrapper anywhere above this name?
                if self._under_static(node, sub):
                    continue
                return sub.id
        return None

    def _under_static(self, root: ast.AST, target: ast.AST) -> bool:
        """True when `target` only appears under a static extractor
        (shape/ndim/dtype/len) within `root`."""
        parents = {}
        for n in ast.walk(root):
            for c in ast.iter_child_nodes(n):
                parents[id(c)] = n
        cur = parents.get(id(target))
        while cur is not None:
            if self._is_static_expr(cur):
                return True
            cur = parents.get(id(cur))
        return False

    def _check_jitted(self, module: ModuleInfo, fn: ast.AST,
                      static: Set[str]) -> Iterator[Finding]:
        params = {a.arg for a in fn.args.posonlyargs + fn.args.args
                  + fn.args.kwonlyargs} - static - {"self"}
        tainted: Set[str] = set(params)
        # fixpoint over simple assignments: y = f(x) with x tainted -> y
        changed = True
        while changed:
            changed = False
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) \
                        and not self._is_static_expr(node.value) \
                        and self._mentions_tainted(node.value, tainted):
                    for t in node.targets:
                        if isinstance(t, ast.Name) and t.id not in tainted:
                            tainted.add(t.id)
                            changed = True
                elif isinstance(node, (ast.For,)) \
                        and isinstance(node.target, ast.Name) \
                        and node.target.id not in tainted \
                        and not self._is_static_expr(node.iter) \
                        and self._mentions_tainted(node.iter, tainted):
                    tainted.add(node.target.id)
                    changed = True

        locals_: Set[str] = set(params) | static
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        locals_.add(t.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                locals_.add(node.name)

        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                hit = self._mentions_tainted(node.test, tainted)
                if hit:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    yield Finding(
                        checker=self.name, code="trace-python-branch",
                        message=(f"Python `{kind}` on traced value `{hit}` "
                                 f"inside jitted {fn.name}(); use "
                                 "lax.cond/select or a mask"),
                        path=module.rel, line=node.lineno,
                        col=node.col_offset)
            elif isinstance(node, ast.For):
                hit = self._mentions_tainted(node.iter, tainted)
                if hit:
                    yield Finding(
                        checker=self.name, code="trace-python-loop",
                        message=(f"Python `for` over traced value `{hit}` "
                                 f"inside jitted {fn.name}(); use "
                                 "lax.scan/fori_loop"),
                        path=module.rel, line=node.lineno,
                        col=node.col_offset)
            elif isinstance(node, ast.Call):
                d = dotted(node.func) or ""
                if isinstance(node.func, ast.Name) \
                        and node.func.id in CONCRETIZERS and node.args:
                    hit = self._mentions_tainted(node.args[0], tainted)
                    if hit:
                        yield Finding(
                            checker=self.name, code="trace-concretize",
                            message=(f"{node.func.id}() on traced value "
                                     f"`{hit}` inside jitted {fn.name}() "
                                     "forces a trace-time concretization"),
                            path=module.rel, line=node.lineno,
                            col=node.col_offset)
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in CONCRETIZE_METHODS:
                    hit = self._mentions_tainted(node.func.value, tainted)
                    if hit:
                        yield Finding(
                            checker=self.name, code="trace-concretize",
                            message=(f".{node.func.attr}() on traced value "
                                     f"`{hit}` inside jitted {fn.name}() "
                                     "forces a device sync"),
                            path=module.rel, line=node.lineno,
                            col=node.col_offset)
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("append", "extend", "update",
                                               "add", "insert", "pop",
                                               "setdefault") \
                        and isinstance(node.func.value, ast.Name):
                    name = node.func.value.id
                    if name not in locals_ and name not in module.imports \
                            and name not in module.module_defs:
                        yield Finding(
                            checker=self.name, code="trace-captured-mutation",
                            message=(f"jitted {fn.name}() mutates captured "
                                     f"state `{name}.{node.func.attr}(...)`; "
                                     "one trace's view is baked into the "
                                     "compiled program"),
                            path=module.rel, line=node.lineno,
                            col=node.col_offset)
