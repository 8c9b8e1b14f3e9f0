"""Shared AST/symbol pass: every checker reads one `ModuleInfo`.

The pass is done ONCE per file (parse, parent links, import table, class
attribute typing) so five checkers cost roughly one; checkers stay pure
consumers and never re-walk for bookkeeping.  Everything here is plain
`ast` — target files are parsed, never imported, so analyzing the JAX
kernels does not pull in JAX.
"""

import ast
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

# attribute kinds recognized by the class-attribute typing pass; the lock
# and store checkers key on these.  The `common.make_*` factories are the
# sanitizer-instrumentable spellings (drand_tpu/common.py): they MUST be
# typed here or converting a runtime module to the factory would silently
# drop it out of the whole lock analysis.
KIND_BY_CALL = {
    "threading.Lock": "lock",
    "threading.RLock": "rlock",
    "threading.Condition": "condition",
    "threading.Event": "event",
    "threading.Thread": "thread",
    "queue.Queue": "queue",
    "queue.LifoQueue": "queue",
    "queue.PriorityQueue": "queue",
    "queue.SimpleQueue": "queue",
    "sqlite3.connect": "sqlite_conn",
    "make_lock": "lock",
    "make_rlock": "rlock",
    "make_condition": "condition",
    "common.make_lock": "lock",
    "common.make_rlock": "rlock",
    "common.make_condition": "condition",
    "drand_tpu.common.make_lock": "lock",
    "drand_tpu.common.make_rlock": "rlock",
    "drand_tpu.common.make_condition": "condition",
}

LOCK_KINDS = ("lock", "rlock", "condition")
# re-entrant acquisitions of these kinds self-deadlock (threading.Lock and
# a default Condition are non-recursive); RLock is re-entrant by design
NON_REENTRANT = ("lock", "condition")


def dotted(node: ast.AST) -> Optional[str]:
    """`self._store._conn` -> "self._store._conn"; None for anything that
    is not a pure Name/Attribute chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclass
class ClassInfo:
    name: str
    node: ast.ClassDef
    # attribute name -> kind (see KIND_BY_CALL) for `self.X = <ctor>()`
    attr_kinds: Dict[str, str] = field(default_factory=dict)
    # attribute name -> the full resolved constructor qualname
    attr_ctors: Dict[str, str] = field(default_factory=dict)
    methods: Dict[str, ast.FunctionDef] = field(default_factory=dict)
    base_names: List[str] = field(default_factory=list)

    def lock_attrs(self) -> List[str]:
        return [a for a, k in self.attr_kinds.items() if k in LOCK_KINDS]


def walk_scope(fn: ast.AST):
    """Walk a function's OWN body without descending into nested function
    definitions — each nested def is its own scope (a jitted nested `run`
    must not be judged by its enclosing factory's rules, a closure's
    returns are not the factory's returns)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


class ModuleInfo:
    """One parsed file + the symbol facts checkers share."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel.replace("\\", "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.parent: Dict[int, ast.AST] = {}
        self.imports: Dict[str, str] = {}
        self.classes: List[ClassInfo] = []
        self.module_defs: set = set()      # top-level def/class/assign names
        self.module_locks: Dict[str, str] = {}   # top-level lock name -> kind
        self._build()

    @property
    def dotted(self) -> str:
        """Module path as a dotted name relative to the scanned root
        ("net/client.py" -> "net.client", "crypto/__init__.py" ->
        "crypto") — the key the project-wide symbol table matches import
        targets against (by suffix, so absolute and relative spellings of
        the same module meet at one entry)."""
        rel = self.rel[:-3] if self.rel.endswith(".py") else self.rel
        if rel.endswith("/__init__"):
            rel = rel[:-len("/__init__")]
        return rel.replace("/", ".")

    def defs_by_qual(self) -> Dict[str, Tuple[Optional[ClassInfo], ast.AST]]:
        """Project-addressable definitions: top-level functions by name,
        class methods as "Class.method".  Nested defs are closures — not
        addressable across modules — and stay out."""
        out: Dict[str, Tuple[Optional[ClassInfo], ast.AST]] = {}
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out[node.name] = (None, node)
        for info in self.classes:
            for mname, fn in info.methods.items():
                out[f"{info.name}.{mname}"] = (info, fn)
        return out

    # -- construction --------------------------------------------------------

    def _build(self) -> None:
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parent[id(child)] = node
        self._collect_imports(self.tree)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ClassDef):
                self.classes.append(self._class_info(node))
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                self.module_defs.add(node.name)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.module_defs.add(t.id)
                # module-level locks (`_HOST_H2F_LOCK = threading.Lock()`) are
                # lockset members for the interprocedural lock analysis
                if isinstance(node.value, ast.Call):
                    ctor = self.resolve(dotted(node.value.func) or "")
                    kind = KIND_BY_CALL.get(ctor)
                    if kind in LOCK_KINDS:
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                self.module_locks[t.id] = kind
            elif isinstance(node, ast.AnnAssign) \
                    and isinstance(node.target, ast.Name):
                self.module_defs.add(node.target.id)

    def _collect_imports(self, tree: ast.AST) -> None:
        """Import table covering function-local imports too (this codebase
        defers heavy imports into functions as a matter of style)."""
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".")[0]
                    target = alias.name if alias.asname else \
                        alias.name.split(".")[0]
                    self.imports[local] = target
            elif isinstance(node, ast.ImportFrom):
                # relative imports keep their tail ("..beacon.clock" ->
                # "beacon.clock"); checkers match on suffixes
                mod = node.module or ""
                for alias in node.names:
                    local = alias.asname or alias.name
                    self.imports[local] = f"{mod}.{alias.name}" if mod \
                        else alias.name

    def _class_info(self, node: ast.ClassDef) -> ClassInfo:
        info = ClassInfo(name=node.name, node=node)
        for b in node.bases:
            d = dotted(b)
            if d:
                info.base_names.append(d.split(".")[-1])
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info.methods[item.name] = item
        # type `self.X = <ctor>(...)` wherever it appears in the class —
        # threads and queues are routinely created outside __init__.  The
        # ctor qualname is kept for EVERY constructor-shaped assignment
        # (kind or not): `self._reg = Registry()` is how the project-wide
        # resolver follows `self._reg.method()` across modules.
        for fn in info.methods.values():
            for sub in ast.walk(fn):
                if not isinstance(sub, ast.Assign):
                    continue
                if not isinstance(sub.value, ast.Call):
                    continue
                ctor = self.resolve(dotted(sub.value.func) or "")
                kind = KIND_BY_CALL.get(ctor)
                for t in sub.targets:
                    d = dotted(t)
                    if d and d.startswith("self.") and d.count(".") == 1:
                        attr = d.split(".", 1)[1]
                        if ctor:
                            info.attr_ctors.setdefault(attr, ctor)
                        if kind is not None:
                            info.attr_kinds[attr] = kind
                            info.attr_ctors[attr] = ctor
        return info

    # -- queries -------------------------------------------------------------

    def resolve(self, name: str) -> str:
        """Rewrite the head of a dotted chain through the import table:
        `_t.monotonic` -> `time.monotonic` after `import time as _t`."""
        if not name:
            return name
        head, _, tail = name.partition(".")
        target = self.imports.get(head)
        if target is None:
            return name
        return f"{target}.{tail}" if tail else target

    def enclosing(self, node: ast.AST, *types) -> Optional[ast.AST]:
        cur = self.parent.get(id(node))
        while cur is not None:
            if isinstance(cur, types):
                return cur
            cur = self.parent.get(id(cur))
        return None

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        return self.enclosing(node, ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)

    def enclosing_class(self, node: ast.AST) -> Optional[ClassInfo]:
        cls = self.enclosing(node, ast.ClassDef)
        if cls is None:
            return None
        for info in self.classes:
            if info.node is cls:
                return info
        return None

    def withs_holding(self, node: ast.AST) -> List[str]:
        """Dotted context-manager expressions of every `with` enclosing
        `node` within its own function (lock-holding analysis)."""
        held: List[str] = []
        fn = self.enclosing_function(node)
        cur = self.parent.get(id(node))
        while cur is not None and cur is not fn:
            if isinstance(cur, (ast.With, ast.AsyncWith)):
                for item in cur.items:
                    d = dotted(item.context_expr)
                    if d:
                        held.append(d)
            cur = self.parent.get(id(cur))
        return held

    def functions(self) -> Iterator[Tuple[Optional[ClassInfo], ast.AST]]:
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield self.enclosing_class(node), node
