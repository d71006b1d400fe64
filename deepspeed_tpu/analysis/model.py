"""The package model dslint rules run against.

One parse pass over every ``.py`` file builds a :class:`PackageModel`:
modules with their import alias tables, every function/method (including
nested defs and lambdas) with the calls it makes, a best-effort resolved
call graph, the *traced set* (functions whose bodies execute under a JAX
trace — ``@jax.jit`` decorations, callables handed to
``lax.scan``/``shard_map``/``pallas_call``/... and everything they
transitively call inside the package), and the lock model (lock
attributes per class, ``with <lock>:`` regions per function).

Everything here is pure stdlib ``ast`` — importing the analyzed code
would drag in jax and break the "lint anywhere" contract, so nothing is
ever executed or imported.

Call resolution is deliberately conservative and graded:

* **strong** — same-module names, ``self.method``, package-module
  qualified attributes (``mod.func`` through the import table), receiver
  attributes whose class annotates their type (``replica.serving`` where
  some ``__init__`` declares ``serving: ServingEngine``), constructor
  calls;
* **weak** — a bare method name defined by exactly one class in the
  package.

Rules choose the confidence they need: traced-set propagation follows
both (a wrongly-traced host helper surfaces as an obvious
false-positive and gets tuned; a missed traced callee silently hides a
host sync), while messages always carry the propagation path so a human
can audit the chain.
"""

from __future__ import annotations

import ast
import os
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

# Final attribute names that take a callable and trace its body. Maps
# name -> indices of positional args that are traced callables (None =
# all positional args from that transform are callables, used by
# cond/switch branches).
_TRANSFORM_CALLABLE_ARGS: Dict[str, Tuple[Optional[int], ...]] = {
    "jit": (0,),
    "pjit": (0,),
    "vmap": (0,),
    "pmap": (0,),
    "grad": (0,),
    "value_and_grad": (0,),
    "remat": (0,),
    "checkpoint": (0,),
    "shard_map": (0,),
    "pallas_call": (0,),
    "custom_vjp": (0,),
    "custom_jvp": (0,),
    "scan": (0,),
    "associative_scan": (0,),
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "cond": (1, 2, 3),
    "switch": (None,),   # every positional arg after the index is a branch
}

# Decorator names that mark the decorated function itself as traced.
_TRACING_DECORATORS = {"jit", "pjit", "remat", "checkpoint",
                       "custom_vjp", "custom_jvp", "kernel"}

_LOCK_CONSTRUCTORS = {"Lock", "RLock", "Condition", "Semaphore",
                      "BoundedSemaphore"}

# Method names that collide with builtin container/file/thread APIs
# (``dict.get``, ``arr.at[i].set``, ``q.put``, ``f.write``, ...): a bare
# name match against a package class method would hijack nearly every
# call site, so these never resolve weakly. The second group are names
# the serving/region layers made common since PR 7 (``clock.pump`` vs
# ``fleet.step``, router/ring ``route``, cell-digest ``publish``, ...):
# several are no longer unique, but blocklisting keeps a future
# refactor from silently re-uniquifying one and hijacking its call
# sites (the PR-15 model spot-check pins this).
_WEAK_RESOLVE_BLOCKLIST = {
    "get", "set", "put", "pop", "update", "items", "keys", "values",
    "append", "extend", "remove", "discard", "clear", "copy", "close",
    "open", "read", "write", "flush", "join", "wait", "send", "recv",
    "next", "count", "index", "sort", "reverse", "split", "strip",
    "add", "insert", "setdefault", "start", "stop", "run", "result",
    "acquire", "release", "reshape", "astype", "item", "mean", "sum",
    "step", "route", "adopt", "evacuate", "publish",
}

# Attribute constructor types whose internal state is thread-safe by
# contract (queue.Queue hand-off, GIL-atomic deque append/popleft):
# the races rule treats accesses to these attributes as synchronized.
_SAFE_CONTAINER_CTORS = {"Queue", "LifoQueue", "PriorityQueue",
                         "SimpleQueue", "deque"}

#: annotation heads whose subscript carries the element/value type
#: (``Dict[str, Replica]`` -> ``Replica``; the VALUE side for mappings)
_CONTAINER_ANNOTATIONS = {"Dict", "dict", "List", "list", "Set", "set",
                          "Sequence", "Deque", "Mapping", "OrderedDict",
                          "DefaultDict", "defaultdict", "FrozenSet",
                          "Iterable", "Tuple", "tuple"}


def final_attr_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` -> ``c``; ``name`` -> ``name``; else None."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` -> ``"a.b.c"`` when the chain is pure Name/Attribute."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def annotation_types(node: ast.AST) -> Tuple[Optional[str], Optional[str]]:
    """(direct type name, container element/value type name) read off an
    annotation expression. ``Optional[T]`` unwraps to ``T``;
    ``Dict[K, V]`` yields the VALUE side; anything else best-effort."""
    if isinstance(node, ast.Subscript):
        head = final_attr_name(node.value)
        sl = node.slice
        if isinstance(sl, ast.Index):          # pragma: no cover (py<3.9)
            sl = sl.value
        if head == "Optional":
            return annotation_types(sl)
        if head in _CONTAINER_ANNOTATIONS:
            if isinstance(sl, ast.Tuple) and sl.elts:
                return None, final_attr_name(sl.elts[-1])
            return None, final_attr_name(sl)
        return None, None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        # string annotation: take the bare head ("ServingCell")
        name = node.value.strip().split("[")[0].split(".")[-1]
        return (name or None), None
    return final_attr_name(node), None


@dataclass
class CallSite:
    #: the ``ast.Call`` node — or, for ``is_property`` sites, the
    #: ``ast.Attribute`` load that invokes a ``@property`` getter
    node: ast.AST
    #: dotted text of the callee expression (``self._engine.put``) or None
    text: Optional[str]
    #: resolved FunctionInfo keys
    targets: List[str] = field(default_factory=list)
    weak: bool = False
    #: an attribute read resolved to a @property getter: it IS a call
    #: (the lock-discipline/races transitive walks follow it — a fleet
    #: gauge pass reading ``r.serving.queue_depth`` under the fleet lock
    #: acquires the replica lock through exactly this edge), but it is
    #: excluded from traced-set propagation (that set was tuned on
    #: explicit calls; property edges would need their own triage)
    is_property: bool = False


@dataclass
class LockRegion:
    """One ``with <lock>:`` block."""
    lock_key: str           # "module::Class.attr" or "module::NAME"
    with_node: ast.With
    lineno: int


@dataclass
class FunctionInfo:
    key: str                # "module::Qual.Path"
    module: str             # module key (display-relative path based)
    name: str               # bare name
    qualname: str           # "Class.method", "outer.<locals>.inner", ...
    class_key: Optional[str]
    node: ast.AST           # FunctionDef / AsyncFunctionDef / Lambda
    lineno: int
    calls: List[CallSite] = field(default_factory=list)
    lock_regions: List[LockRegion] = field(default_factory=list)
    #: why this function is traced, None if host-side ("@jax.jit", or a
    #: "via <caller key>" chain element added during propagation)
    traced_reason: Optional[str] = None
    decorator_names: Set[str] = field(default_factory=set)
    #: thread roles that may execute this function ("main" = any caller
    #: thread; other roles are named after discovered thread entry
    #: points — see ThreadEntry / _propagate_roles). Empty = unreached.
    thread_roles: Set[str] = field(default_factory=set)

    @property
    def is_property_getter(self) -> bool:
        return bool(self.decorator_names
                    & {"property", "cached_property"})


@dataclass
class ThreadEntry:
    """One discovered thread entry point: the target of a
    ``threading.Thread(target=...)``, a ``weakref.finalize`` callback,
    or a ``threading.Timer`` body. ``role`` is the thread's declared
    ``name=`` when it is a string constant (``"serving-driver"``),
    else a name derived from the target."""

    role: str
    func_key: str
    kind: str            # "thread" | "finalizer" | "timer"
    module: str
    lineno: int


@dataclass
class ClassInfo:
    key: str                # "module::Name"
    name: str
    module: str
    node: ast.ClassDef
    methods: Dict[str, str] = field(default_factory=dict)   # name -> func key
    #: attr name -> class name (unresolved text) from annotations or
    #: ``self.x = ClassName(...)`` / ``self.x = param`` with an annotation
    attr_types: Dict[str, str] = field(default_factory=dict)
    #: attr name -> ELEMENT/VALUE class name for annotated containers
    #: (``self._replicas: Dict[str, Replica]`` -> ``Replica``), so
    #: ``self._replicas.get(k)`` / ``for r in self._replicas.values()``
    #: type their results
    attr_elem_types: Dict[str, str] = field(default_factory=dict)
    #: attr name -> constructor name for threading primitives
    lock_attrs: Dict[str, str] = field(default_factory=dict)
    #: attr name -> "Event" for threading.Event attributes (wall-clock
    #: rule: raw ``event.wait`` bypasses the injectable clock seam)
    event_attrs: Dict[str, str] = field(default_factory=dict)
    base_names: List[str] = field(default_factory=list)


@dataclass
class ModuleInfo:
    key: str                # display-relative posix path, e.g. "deepspeed_tpu/serving/server.py"
    path: str               # absolute path
    tree: ast.Module
    source_lines: List[str]
    #: comment text by line number (from tokenize), for suppressions
    comments: Dict[int, str] = field(default_factory=dict)
    #: import alias -> real dotted module ("np" -> "numpy")
    alias_to_module: Dict[str, str] = field(default_factory=dict)
    #: from-import: local name -> (dotted module, original name)
    name_imports: Dict[str, Tuple[str, str]] = field(default_factory=dict)
    functions: List[str] = field(default_factory=list)      # func keys
    classes: List[str] = field(default_factory=list)        # class keys
    module_locks: Dict[str, str] = field(default_factory=dict)  # NAME -> ctor

    def line(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.source_lines):
            return self.source_lines[lineno - 1]
        return ""


class PackageModel:
    def __init__(self) -> None:
        self.modules: Dict[str, ModuleInfo] = {}
        self.functions: Dict[str, FunctionInfo] = {}
        self.classes: Dict[str, ClassInfo] = {}
        # bare method name -> set of func keys (for weak resolution)
        self.method_index: Dict[str, Set[str]] = {}
        # attr name -> set of annotated type names (for receiver typing)
        self.attr_type_index: Dict[str, Set[str]] = {}
        # class bare name -> set of class keys
        self.class_index: Dict[str, Set[str]] = {}
        # module-level function bare name -> keys (diagnostics only)
        self.function_index: Dict[str, Set[str]] = {}
        # discovered thread entry points (the thread model's roots)
        self.thread_entries: List[ThreadEntry] = []

    # -- queries --------------------------------------------------------
    def functions_in(self, module_key: str) -> Iterator[FunctionInfo]:
        mod = self.modules.get(module_key)
        if mod is None:
            return
        for k in mod.functions:
            yield self.functions[k]

    def resolve_class(self, name: str) -> Optional[ClassInfo]:
        keys = self.class_index.get(name, set())
        if len(keys) == 1:
            return self.classes[next(iter(keys))]
        return None

    def is_traced(self, func_key: str) -> bool:
        f = self.functions.get(func_key)
        return f is not None and f.traced_reason is not None


# ----------------------------------------------------------------------
# collection
# ----------------------------------------------------------------------

def _iter_py_files(paths: Sequence[str]) -> Iterator[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in {"__pycache__", ".git"})
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def _display_key(path: str, base: str) -> str:
    rel = os.path.relpath(path, base)
    return rel.replace(os.sep, "/")


def _read_comments(path: str) -> Dict[int, str]:
    comments: Dict[int, str] = {}
    try:
        with tokenize.open(path) as fh:
            for tok in tokenize.generate_tokens(fh.readline):
                if tok.type == tokenize.COMMENT:
                    comments[tok.start[0]] = tok.string
    except (tokenize.TokenizeError, SyntaxError, OSError):
        pass
    return comments


class _Collector(ast.NodeVisitor):
    """First pass over one module: functions, classes, imports, locks."""

    def __init__(self, pkg: PackageModel, mod: ModuleInfo) -> None:
        self.pkg = pkg
        self.mod = mod
        self.class_stack: List[ClassInfo] = []
        self.func_stack: List[FunctionInfo] = []

    # -- imports --------------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            local = alias.asname or alias.name.split(".")[0]
            self.mod.alias_to_module[local] = (alias.name if alias.asname
                                               else alias.name.split(".")[0])
            if alias.asname:
                self.mod.alias_to_module[alias.asname] = alias.name

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        src = ("." * node.level) + (node.module or "")
        for alias in node.names:
            local = alias.asname or alias.name
            self.mod.name_imports[local] = (src, alias.name)

    # -- defs -----------------------------------------------------------
    def _qual_prefix(self) -> str:
        if self.func_stack:
            return self.func_stack[-1].qualname + ".<locals>."
        if self.class_stack:
            return self.class_stack[-1].name + "."
        return ""

    def _add_function(self, node: ast.AST, name: str) -> FunctionInfo:
        qual = self._qual_prefix() + name
        key = f"{self.mod.key}::{qual}"
        # a redefinition (same name at same scope) gets a line suffix
        if key in self.pkg.functions:
            key = f"{key}@{getattr(node, 'lineno', 0)}"
        class_key = (self.class_stack[-1].key
                     if self.class_stack and not self.func_stack else None)
        info = FunctionInfo(key=key, module=self.mod.key, name=name,
                            qualname=qual, class_key=class_key, node=node,
                            lineno=getattr(node, "lineno", 0))
        self.pkg.functions[key] = info
        self.mod.functions.append(key)
        if class_key is not None:
            cls = self.classes_top()
            cls.methods.setdefault(name, key)
            self.pkg.method_index.setdefault(name, set()).add(key)
        else:
            self.pkg.function_index.setdefault(name, set()).add(key)
        return info

    def classes_top(self) -> ClassInfo:
        return self.class_stack[-1]

    def _visit_funcdef(self, node) -> None:
        info = self._add_function(node, node.name)
        for dec in node.decorator_list:
            dn = final_attr_name(dec if not isinstance(dec, ast.Call)
                                 else dec.func)
            if dn:
                info.decorator_names.add(dn)
            if isinstance(dec, ast.Call):
                # @partial(jax.jit, ...) / @functools.partial(jit, ...)
                if (final_attr_name(dec.func) == "partial" and dec.args
                        and final_attr_name(dec.args[0]) in
                        _TRACING_DECORATORS):
                    info.decorator_names.add(final_attr_name(dec.args[0]))
        if info.decorator_names & _TRACING_DECORATORS:
            deco = sorted(info.decorator_names & _TRACING_DECORATORS)[0]
            info.traced_reason = f"decorated @{deco}"
        self.func_stack.append(info)
        for child in ast.iter_child_nodes(node):
            if child in node.decorator_list:
                continue
            self.visit(child)
        self.func_stack.pop()

    visit_FunctionDef = _visit_funcdef
    visit_AsyncFunctionDef = _visit_funcdef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        info = self._add_function(node, f"<lambda>@{node.lineno}")
        self.func_stack.append(info)
        self.generic_visit(node)
        self.func_stack.pop()

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        if self.func_stack or self.class_stack:
            # nested classes: record but don't model methods specially
            key = f"{self.mod.key}::{self._qual_prefix()}{node.name}"
        else:
            key = f"{self.mod.key}::{node.name}"
        cls = ClassInfo(key=key, name=node.name, module=self.mod.key,
                        node=node,
                        base_names=[b for b in
                                    (final_attr_name(x) for x in node.bases)
                                    if b])
        self.pkg.classes[key] = cls
        self.mod.classes.append(key)
        self.pkg.class_index.setdefault(node.name, set()).add(key)
        # class-body annotations: ``serving: ServingEngine``
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name):
                t, elem = annotation_types(stmt.annotation)
                if t:
                    cls.attr_types[stmt.target.id] = t
                if elem:
                    cls.attr_elem_types[stmt.target.id] = elem
        self.class_stack.append(cls)
        saved, self.func_stack = self.func_stack, []
        self.generic_visit(node)
        self.func_stack = saved
        self.class_stack.pop()
        for attr, tname in cls.attr_types.items():
            self.pkg.attr_type_index.setdefault(attr, set()).add(tname)

    # -- assignments: lock attrs + attr types ---------------------------
    def _record_self_assign(self, target: ast.AST, value: ast.AST) -> None:
        if not (isinstance(target, ast.Attribute)
                and isinstance(target.value, ast.Name)
                and target.value.id == "self" and self.class_stack):
            return
        cls = self.class_stack[-1]
        attr = target.attr
        if isinstance(value, ast.Call):
            ctor = final_attr_name(value.func)
            if ctor in _LOCK_CONSTRUCTORS and self._is_threading(value.func):
                cls.lock_attrs[attr] = ctor
            elif ctor in ("named_lock", "named_rlock") \
                    and self._is_locksan(value.func):
                # the runtime lock-order sanitizer's construction seam
                # (resilience/locksan.py): statically these ARE the
                # serving locks — the lock model must keep seeing them
                cls.lock_attrs[attr] = ("RLock" if ctor == "named_rlock"
                                        else "Lock")
            elif ctor == "Event" and self._is_threading(value.func):
                cls.event_attrs[attr] = ctor
            elif ctor in _SAFE_CONTAINER_CTORS:
                cls.attr_types.setdefault(attr, ctor)
            elif ctor and ctor[:1].isupper():
                cls.attr_types.setdefault(attr, ctor)
        elif isinstance(value, ast.Name) and self.func_stack:
            # ``self.x = x`` with an annotated parameter ``x: T``
            fn = self.func_stack[-1].node
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for arg in (list(fn.args.posonlyargs) + list(fn.args.args)
                            + list(fn.args.kwonlyargs)):
                    if arg.arg == value.id and arg.annotation is not None:
                        t = final_attr_name(arg.annotation)
                        if t:
                            cls.attr_types.setdefault(attr, t)

    def _is_threading(self, func_expr: ast.AST) -> bool:
        """``threading.Lock`` / aliased module / from-imported name."""
        if isinstance(func_expr, ast.Attribute) and isinstance(
                func_expr.value, ast.Name):
            real = self.mod.alias_to_module.get(func_expr.value.id,
                                                func_expr.value.id)
            return real == "threading" or real.startswith("threading.")
        if isinstance(func_expr, ast.Name):
            imp = self.mod.name_imports.get(func_expr.id)
            return bool(imp and imp[0].lstrip(".") == "threading")
        return False

    def _is_locksan(self, func_expr: ast.AST) -> bool:
        """Constructed via resilience/locksan.py's named_lock/named_rlock
        (any import flavor)."""
        if isinstance(func_expr, ast.Attribute) and isinstance(
                func_expr.value, ast.Name):
            real = self.mod.alias_to_module.get(func_expr.value.id,
                                                func_expr.value.id)
            return real.split(".")[-1] == "locksan"
        if isinstance(func_expr, ast.Name):
            imp = self.mod.name_imports.get(func_expr.id)
            return bool(imp and imp[0].lstrip(".").split(".")[-1]
                        == "locksan")
        return False

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._record_self_assign(t, node.value)
            if (isinstance(t, ast.Name) and not self.func_stack
                    and not self.class_stack
                    and isinstance(node.value, ast.Call)):
                ctor = final_attr_name(node.value.func)
                if ctor in _LOCK_CONSTRUCTORS and self._is_threading(
                        node.value.func):
                    self.mod.module_locks[t.id] = ctor
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._record_self_assign(node.target, node.value)
        # ``self._replicas: Dict[str, Replica] = {}`` — the annotation
        # types the attribute (and its container elements) even when the
        # assigned value is an empty literal
        if (isinstance(node.target, ast.Attribute)
                and isinstance(node.target.value, ast.Name)
                and node.target.value.id == "self" and self.class_stack):
            cls = self.class_stack[-1]
            t, elem = annotation_types(node.annotation)
            if t:
                cls.attr_types.setdefault(node.target.attr, t)
            if elem:
                cls.attr_elem_types.setdefault(node.target.attr, elem)
        self.generic_visit(node)


# ----------------------------------------------------------------------
# second pass: call sites, lock regions, traced roots
# ----------------------------------------------------------------------

def iter_shallow(node: ast.AST) -> Iterator[ast.AST]:
    """Walk ``node`` without descending into nested function/class
    bodies (their statements belong to their own FunctionInfo)."""
    for child in ast.iter_child_nodes(node):
        yield child
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
            continue
        yield from iter_shallow(child)


class _Resolver:
    def __init__(self, pkg: PackageModel, mod: ModuleInfo) -> None:
        self.pkg = pkg
        self.mod = mod
        # package-internal module resolution: map "…serving.server"-ish
        # suffixes of imported module names to module keys
        self._mod_by_tail: Dict[str, str] = {}
        for key in pkg.modules:
            tail = key[:-3] if key.endswith(".py") else key
            tail = tail.replace("/", ".")
            if tail.endswith(".__init__"):
                tail = tail[: -len(".__init__")]
            self._mod_by_tail[tail] = key

    def module_key_for(self, dotted: str) -> Optional[str]:
        """Best-effort: match an imported dotted module (possibly
        relative, possibly absolute) to an analyzed module key."""
        dotted = dotted.lstrip(".")
        if not dotted:
            return None
        for tail, key in self._mod_by_tail.items():
            if tail == dotted or tail.endswith("." + dotted):
                return key
        return None

    def _module_level_func(self, module_key: str,
                           name: str) -> Optional[str]:
        mod = self.pkg.modules.get(module_key)
        if mod is None:
            return None
        for fk in mod.functions:
            f = self.pkg.functions[fk]
            if f.name == name and f.class_key is None \
                    and "<locals>" not in f.qualname:
                return fk
        return None

    def _class_in_module(self, module_key: str,
                         name: str) -> Optional[ClassInfo]:
        mod = self.pkg.modules.get(module_key)
        if mod is None:
            return None
        for ck in mod.classes:
            if self.pkg.classes[ck].name == name:
                return self.pkg.classes[ck]
        return None

    def _lookup_class_method(self, cls: ClassInfo, name: str,
                             _depth: int = 0) -> Optional[str]:
        if name in cls.methods:
            return cls.methods[name]
        if _depth >= 4:
            return None
        for base in cls.base_names:
            base_cls = (self._class_in_module(cls.module, base)
                        or self.pkg.resolve_class(base))
            if base_cls is not None and base_cls.key != cls.key:
                got = self._lookup_class_method(base_cls, name, _depth + 1)
                if got:
                    return got
        return None

    def resolve(self, call: ast.Call,
                owner: FunctionInfo,
                local_defs: Dict[str, str],
                local_types: Optional[Dict[str, str]] = None) -> CallSite:
        local_types = local_types or {}
        func = call.func
        site = CallSite(node=call, text=dotted_name(func))
        # plain name --------------------------------------------------
        if isinstance(func, ast.Name):
            name = func.id
            if name in local_defs:
                site.targets = [local_defs[name]]
                return site
            fk = self._module_level_func(self.mod.key, name)
            if fk:
                site.targets = [fk]
                return site
            cls = next((self.pkg.classes[ck] for ck in self.mod.classes
                        if self.pkg.classes[ck].name == name), None)
            if cls is None and name in self.mod.name_imports:
                src, orig = self.mod.name_imports[name]
                mk = self.module_key_for(src)
                if mk:
                    fk = self._module_level_func(mk, orig)
                    if fk:
                        site.targets = [fk]
                        return site
                    cls = self._class_in_module(mk, orig)
            if cls is not None:
                init = self._lookup_class_method(cls, "__init__")
                if init:
                    site.targets = [init]
                return site
            return site
        if not isinstance(func, ast.Attribute):
            return site
        # self.method -------------------------------------------------
        recv = func.value
        if isinstance(recv, ast.Name) and recv.id in ("self", "cls") \
                and owner.class_key:
            cls = self.pkg.classes[owner.class_key]
            got = self._lookup_class_method(cls, func.attr)
            if got:
                site.targets = [got]
            return site
        # module-qualified: mod.func / pkg.mod.func -------------------
        dn = dotted_name(recv)
        if dn is not None:
            head = dn.split(".")[0]
            real = self.mod.alias_to_module.get(head)
            if real is not None:
                full = real + dn[len(head):]
                mk = self.module_key_for(full)
                if mk:
                    fk = self._module_level_func(mk, func.attr)
                    if fk:
                        site.targets = [fk]
                        return site
            if head in self.mod.name_imports:
                src, orig = self.mod.name_imports[head]
                mk = self.module_key_for(src.rstrip(".") + "." + orig
                                         if not src.endswith(".")
                                         else src + orig)
                if mk is None:
                    mk = self.module_key_for(orig)
                if mk:
                    fk = self._module_level_func(mk, func.attr)
                    if fk:
                        site.targets = [fk]
                        return site
        # typed receiver attr: x.serving.submit_request ---------------
        if isinstance(recv, ast.Attribute):
            types = self.pkg.attr_type_index.get(recv.attr, set())
            if len(types) == 1:
                cls = self.pkg.resolve_class(next(iter(types)))
                if cls is not None:
                    got = self._lookup_class_method(cls, func.attr)
                    if got:
                        site.targets = [got]
                        return site
        # typed LOCAL receiver: cell.fleet... where the local's type was
        # inferred (annotation, constructor, container element)
        if isinstance(recv, ast.Name) and recv.id in local_types:
            cls = self.pkg.resolve_class(local_types[recv.id])
            if cls is not None:
                got = self._lookup_class_method(cls, func.attr)
                if got:
                    site.targets = [got]
                    return site
        # weak: unique method name ------------------------------------
        # ... but never on the result of a call the model cannot
        # resolve: ``hashlib.sha256(data).digest()`` is a method on an
        # EXTERNAL object, and weak-resolving it to the one package
        # method named ``digest`` (ServingCell.digest) planted a
        # phantom Fleet->Cell edge in the lock graph that no runtime
        # path can ever exercise (race-lane hot-edge gate).
        if isinstance(recv, ast.Call):
            inner = self.resolve(recv, owner, local_defs, local_types)
            if not inner.targets:
                return site
        if func.attr not in _WEAK_RESOLVE_BLOCKLIST:
            keys = self.pkg.method_index.get(func.attr, set())
            if len(keys) == 1:
                site.targets = [next(iter(keys))]
                site.weak = True
        return site

    def resolve_property(self, node: ast.Attribute, owner: FunctionInfo,
                         local_types: Dict[str, str]
                         ) -> Optional[CallSite]:
        """An attribute LOAD that invokes a ``@property`` getter of a
        package class (``cell.digest``, ``r.serving.queue_depth``) is a
        call in disguise — and the serving tier's property getters take
        locks, so the lock-discipline graph and the races rule must see
        the edge. Only strong receiver typings resolve (self, typed
        local, typed attribute); a miss returns None."""
        target_cls: Optional[ClassInfo] = None
        recv = node.value
        if isinstance(recv, ast.Name):
            if recv.id in ("self", "cls") and owner.class_key:
                target_cls = self.pkg.classes[owner.class_key]
            elif recv.id in local_types:
                target_cls = self.pkg.resolve_class(local_types[recv.id])
        elif isinstance(recv, ast.Attribute):
            if isinstance(recv.value, ast.Name) \
                    and recv.value.id == "self" and owner.class_key:
                t = self.pkg.classes[owner.class_key].attr_types.get(
                    recv.attr)
                if t:
                    target_cls = self.pkg.resolve_class(t)
            if target_cls is None:
                types = self.pkg.attr_type_index.get(recv.attr, set())
                if len(types) == 1:
                    target_cls = self.pkg.resolve_class(next(iter(types)))
        if target_cls is None:
            return None
        got = self._lookup_class_method(target_cls, node.attr)
        if got is None:
            return None
        tf = self.pkg.functions.get(got)
        if tf is None or not tf.is_property_getter:
            return None
        return CallSite(node=node, text=dotted_name(node), targets=[got],
                        is_property=True)


class _SecondPass:
    def __init__(self, pkg: PackageModel, mod: ModuleInfo) -> None:
        self.pkg = pkg
        self.mod = mod
        self.resolver = _Resolver(pkg, mod)

    def run(self) -> None:
        # map (function node) -> FunctionInfo for this module
        by_node = {self.pkg.functions[k].node: self.pkg.functions[k]
                   for k in self.mod.functions}
        for fk in self.mod.functions:
            f = self.pkg.functions[fk]
            local_defs = self._local_defs(f, by_node)
            self._scan_function(f, local_defs, by_node)
        # module-level transform calls (jitted module constants etc.)
        mod_defs = {self.pkg.functions[k].name: k
                    for k in self.mod.functions
                    if self.pkg.functions[k].class_key is None
                    and "<locals>" not in self.pkg.functions[k].qualname}
        for node in iter_shallow(self.mod.tree):
            if isinstance(node, ast.Call):
                self._mark_transform_args(node, mod_defs, by_node)
                self._mark_thread_entry(node, None, mod_defs, by_node)

    def _local_defs(self, f: FunctionInfo,
                    by_node) -> Dict[str, str]:
        """Names of functions defined lexically inside ``f`` (one level
        is enough: transforms take the directly-nested step fn), plus
        module-level defs."""
        defs: Dict[str, str] = {}
        for k in self.mod.functions:
            g = self.pkg.functions[k]
            if g.class_key is None and "<locals>" not in g.qualname:
                defs.setdefault(g.name, k)
        prefix = f.qualname + ".<locals>."
        for k in self.mod.functions:
            g = self.pkg.functions[k]
            if g.qualname.startswith(prefix) \
                    and "." not in g.qualname[len(prefix):]:
                defs[g.name] = k
        return defs

    def _scan_function(self, f: FunctionInfo,
                       local_defs: Dict[str, str], by_node) -> None:
        if isinstance(f.node, ast.Lambda):
            # a lambda body IS an expression — usually a single Call
            # (``jit(lambda x: helper(x))``); iter_shallow only yields
            # children, so the body node itself must be scanned too or
            # the traced set never reaches ``helper``
            nodes: List[ast.AST] = [f.node.body]
            nodes = nodes + list(iter_shallow(f.node.body))
        else:
            nodes = list(iter_shallow(f.node))
        local_types = self._infer_local_types(f, nodes)
        for node in nodes:
            if isinstance(node, ast.Call):
                site = self.resolver.resolve(node, f, local_defs,
                                             local_types)
                f.calls.append(site)
                self._mark_transform_args(node, local_defs, by_node)
                self._mark_thread_entry(node, f, local_defs, by_node)
            elif isinstance(node, ast.With):
                for item in node.items:
                    lk = self._lock_key(item.context_expr, f)
                    if lk:
                        f.lock_regions.append(LockRegion(
                            lock_key=lk, with_node=node,
                            lineno=node.lineno))
        # property getters invoked by attribute loads: calls in disguise
        # (see Resolver.resolve_property). An attribute that is itself
        # the callee of a Call was already handled above.
        callee_ids = {id(n.func) for n in nodes
                      if isinstance(n, ast.Call)}
        for node in nodes:
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Load)
                    and id(node) not in callee_ids):
                site = self.resolver.resolve_property(node, f, local_types)
                if site is not None:
                    f.calls.append(site)

    def _infer_local_types(self, f: FunctionInfo,
                           nodes: Sequence[ast.AST]) -> Dict[str, str]:
        """Best-effort local-variable typing: parameter annotations,
        annotated assigns, constructor assigns, ``self.attr`` loads of
        typed attributes, and container-element extraction
        (``self._cells.get(k)`` / ``self._cells[k]`` /
        ``for r in self._replicas.values()`` / comprehensions) using
        the class's annotated container value types. Flow-insensitive;
        two lexical passes so a loop over a list built later still
        types."""
        types: Dict[str, str] = {}
        elems: Dict[str, str] = {}   # local list/dict var -> element type
        cls = (self.pkg.classes.get(f.class_key)
               if f.class_key else None)

        def self_attr_type(v: ast.AST) -> Optional[str]:
            if (isinstance(v, ast.Attribute)
                    and isinstance(v.value, ast.Name)
                    and v.value.id == "self" and cls is not None):
                return cls.attr_types.get(v.attr)
            return None

        def self_attr_elem(v: ast.AST) -> Optional[str]:
            if (isinstance(v, ast.Attribute)
                    and isinstance(v.value, ast.Name)
                    and v.value.id == "self" and cls is not None):
                return cls.attr_elem_types.get(v.attr)
            return None

        def elem_type_of(it: ast.AST) -> Optional[str]:
            """Element type of an iterable expression."""
            got = self_attr_elem(it)
            if got:
                return got
            if isinstance(it, ast.Name):
                return elems.get(it.id)
            if isinstance(it, ast.Call):
                fn = it.func
                if isinstance(fn, ast.Attribute) \
                        and fn.attr == "values":
                    return self_attr_elem(fn.value)
                if final_attr_name(fn) in ("list", "sorted", "reversed",
                                           "iter") and it.args:
                    return elem_type_of(it.args[0])
            if isinstance(it, (ast.ListComp, ast.SetComp,
                               ast.GeneratorExp)):
                gen = it.generators[0] if it.generators else None
                if gen is not None and isinstance(it.elt, ast.Name) \
                        and isinstance(gen.target, ast.Name) \
                        and it.elt.id == gen.target.id:
                    return elem_type_of(gen.iter)
            return None

        def value_type(v: ast.AST) -> Optional[str]:
            got = self_attr_type(v)
            if got:
                return got
            if isinstance(v, ast.Name):
                return types.get(v.id)
            if isinstance(v, ast.Subscript):
                return elem_type_of(v.value)
            if isinstance(v, ast.Call):
                fn = v.func
                ctor = final_attr_name(fn)
                if ctor and ctor[:1].isupper() \
                        and self.pkg.class_index.get(ctor):
                    return ctor
                if isinstance(fn, ast.Attribute) and fn.attr == "get":
                    return elem_type_of(fn.value)
                if ctor == "next" and v.args:
                    return elem_type_of(v.args[0])
            return None

        for _pass in range(2):
            for node in nodes:
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    name = node.targets[0].id
                    t = value_type(node.value)
                    if t:
                        types.setdefault(name, t)
                    e = elem_type_of(node.value)
                    if e:
                        elems.setdefault(name, e)
                elif isinstance(node, ast.AnnAssign) \
                        and isinstance(node.target, ast.Name):
                    t, e = annotation_types(node.annotation)
                    if t:
                        types.setdefault(node.target.id, t)
                    if e:
                        elems.setdefault(node.target.id, e)
                elif isinstance(node, (ast.For, ast.AsyncFor)) \
                        and isinstance(node.target, ast.Name):
                    t = elem_type_of(node.iter)
                    if t:
                        types.setdefault(node.target.id, t)
                elif isinstance(node, (ast.ListComp, ast.SetComp,
                                       ast.GeneratorExp, ast.DictComp)):
                    for gen in node.generators:
                        if isinstance(gen.target, ast.Name):
                            t = elem_type_of(gen.iter)
                            if t:
                                types.setdefault(gen.target.id, t)
        if isinstance(f.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for arg in (list(f.node.args.posonlyargs)
                        + list(f.node.args.args)
                        + list(f.node.args.kwonlyargs)):
                if arg.annotation is not None:
                    t, _ = annotation_types(arg.annotation)
                    if t:
                        types[arg.arg] = t
        return types

    def _expr_module(self, func_expr: ast.AST) -> Optional[str]:
        """Real module behind ``alias.attr`` or a from-imported name."""
        if isinstance(func_expr, ast.Attribute) and isinstance(
                func_expr.value, ast.Name):
            return self.mod.alias_to_module.get(func_expr.value.id,
                                                func_expr.value.id)
        if isinstance(func_expr, ast.Name):
            imp = self.mod.name_imports.get(func_expr.id)
            if imp:
                return imp[0].lstrip(".")
        return None

    def _callable_key(self, arg: Optional[ast.AST],
                      owner: Optional[FunctionInfo],
                      local_defs: Dict[str, str],
                      by_node) -> Optional[str]:
        """Resolve a callable-valued expression to a function key (the
        thread-entry version of _mark_callable — prefers the owner
        class over the global unique-name index for ``self.x``)."""
        if arg is None:
            return None
        if isinstance(arg, ast.Lambda):
            got = by_node.get(arg)
            return got.key if got is not None else None
        if isinstance(arg, ast.Name) and arg.id in local_defs:
            return local_defs[arg.id]
        if isinstance(arg, ast.Attribute) and isinstance(
                arg.value, ast.Name):
            if arg.value.id == "self" and owner is not None \
                    and owner.class_key:
                cls = self.pkg.classes[owner.class_key]
                got = self.resolver._lookup_class_method(cls, arg.attr)
                if got:
                    return got
            keys = self.pkg.method_index.get(arg.attr, set())
            if len(keys) == 1:
                return next(iter(keys))
        return None

    def _mark_thread_entry(self, call: ast.Call,
                           owner: Optional[FunctionInfo],
                           local_defs: Dict[str, str], by_node) -> None:
        """Record thread entry points: ``threading.Thread(target=...)``
        (role = the thread's ``name=`` string when constant),
        ``threading.Timer(t, fn)`` and ``weakref.finalize(obj, fn)``."""
        name = final_attr_name(call.func)
        if name in ("Thread", "Timer"):
            if self._expr_module(call.func) != "threading":
                return
            target = None
            role_name = None
            for kw in call.keywords:
                if kw.arg == "target":
                    target = kw.value
                elif kw.arg == "name" \
                        and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    role_name = kw.value.value
            if name == "Timer" and target is None and len(call.args) >= 2:
                target = call.args[1]
            key = self._callable_key(target, owner, local_defs, by_node)
            if key is None:
                return
            role = role_name or f"thread:{self.pkg.functions[key].qualname}"
            self.pkg.thread_entries.append(ThreadEntry(
                role=role, func_key=key,
                kind="thread" if name == "Thread" else "timer",
                module=self.mod.key, lineno=call.lineno))
        elif name == "finalize" and self._expr_module(call.func) \
                == "weakref" and len(call.args) >= 2:
            key = self._callable_key(call.args[1], owner, local_defs,
                                     by_node)
            if key is not None:
                self.pkg.thread_entries.append(ThreadEntry(
                    role="finalizer", func_key=key, kind="finalizer",
                    module=self.mod.key, lineno=call.lineno))

    def _lock_key(self, expr: ast.AST,
                  f: FunctionInfo) -> Optional[str]:
        if isinstance(expr, ast.Attribute) and isinstance(
                expr.value, ast.Name) and expr.value.id == "self" \
                and f.class_key:
            cls = self.pkg.classes[f.class_key]
            if expr.attr in cls.lock_attrs:
                return f"{cls.key}.{expr.attr}"
            # inherited lock attr
            for base in cls.base_names:
                b = self.pkg.resolve_class(base)
                if b and expr.attr in b.lock_attrs:
                    return f"{b.key}.{expr.attr}"
            return None
        if isinstance(expr, ast.Name) \
                and expr.id in self.mod.module_locks:
            return f"{self.mod.key}::{expr.id}"
        if isinstance(expr, ast.Attribute):
            # x.lockattr where type(x) is uniquely annotated
            if isinstance(expr.value, (ast.Name, ast.Attribute)):
                recv_attr = final_attr_name(expr.value)
                types = self.pkg.attr_type_index.get(recv_attr or "", set())
                if len(types) == 1:
                    cls = self.pkg.resolve_class(next(iter(types)))
                    if cls and expr.attr in cls.lock_attrs:
                        return f"{cls.key}.{expr.attr}"
            # unique lock attr name across package classes
            owners = [c for c in self.pkg.classes.values()
                      if expr.attr in c.lock_attrs]
            if len(owners) == 1:
                return f"{owners[0].key}.{expr.attr}"
        return None

    def _mark_transform_args(self, call: ast.Call,
                             local_defs: Dict[str, str],
                             by_node) -> None:
        name = final_attr_name(call.func)
        if name == "partial" and call.args:
            inner = final_attr_name(call.args[0])
            if inner in _TRANSFORM_CALLABLE_ARGS and len(call.args) > 1:
                self._mark_callable(call.args[1], f"partial({inner}, ...)",
                                    local_defs, by_node)
            return
        spec = _TRANSFORM_CALLABLE_ARGS.get(name or "")
        if spec is None:
            return
        if spec == (None,):
            args = call.args[1:]
        else:
            args = [call.args[i] for i in spec if i < len(call.args)]
        for arg in args:
            self._mark_callable(arg, f"passed to {name}()", local_defs,
                                by_node)

    def _mark_callable(self, arg: ast.AST, why: str,
                       local_defs: Dict[str, str], by_node) -> None:
        target: Optional[FunctionInfo] = None
        if isinstance(arg, ast.Lambda):
            target = by_node.get(arg)
        elif isinstance(arg, ast.Name) and arg.id in local_defs:
            target = self.pkg.functions.get(local_defs[arg.id])
        elif isinstance(arg, ast.Attribute) and isinstance(
                arg.value, ast.Name) and arg.value.id == "self":
            keys = self.pkg.method_index.get(arg.attr, set())
            if len(keys) == 1:
                target = self.pkg.functions.get(next(iter(keys)))
        elif isinstance(arg, ast.Call):
            # e.g. jit(partial(step, cfg)) / scan(partial(body, x), ...)
            if final_attr_name(arg.func) == "partial" and arg.args:
                self._mark_callable(arg.args[0], why, local_defs, by_node)
            return
        if target is not None and target.traced_reason is None:
            target.traced_reason = why


def _propagate_traced(pkg: PackageModel) -> None:
    """BFS the call graph from traced roots: anything a traced function
    calls (resolvably, inside the package) also runs under the trace."""
    frontier = [k for k, f in pkg.functions.items()
                if f.traced_reason is not None]
    seen = set(frontier)
    while frontier:
        nxt: List[str] = []
        for k in frontier:
            f = pkg.functions[k]
            for site in f.calls:
                if site.is_property:
                    # property-getter edges feed the lock/races graphs
                    # only — the traced set stays explicit-call based
                    continue
                for t in site.targets:
                    if t in seen:
                        continue
                    g = pkg.functions.get(t)
                    if g is None:
                        continue
                    # constructors aren't traced by being called with
                    # tracer args at build time in practice; skip dunder
                    # targets to cut false chains
                    if g.name.startswith("__") and g.name.endswith("__"):
                        continue
                    g.traced_reason = f"called from traced {f.qualname}"
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt


def _propagate_roles(pkg: PackageModel) -> None:
    """Thread-role propagation over the call graph.

    Seeds: each discovered thread entry gets its role; the externally
    callable surface — public names, dunders, and anything with no
    resolved internal caller (minus the thread entries themselves) —
    gets the synthetic ``"main"`` role (any caller thread). Roles then
    flow caller -> callee to a fixpoint, so a helper reachable from both
    ``step()`` (caller-driven) and the driver loop carries both roles —
    exactly the "accessed from >= 2 threads" precondition the races
    rule tests."""
    entry_keys = set()
    for e in pkg.thread_entries:
        f = pkg.functions.get(e.func_key)
        if f is not None:
            f.thread_roles.add(e.role)
            entry_keys.add(e.func_key)
    incoming: Set[str] = set()
    for f in pkg.functions.values():
        for site in f.calls:
            incoming.update(site.targets)
    for k, f in pkg.functions.items():
        if k in entry_keys:
            continue
        public = (not f.name.startswith("_")
                  or (f.name.startswith("__") and f.name.endswith("__")))
        if public or k not in incoming:
            f.thread_roles.add("main")
    work = [k for k, f in pkg.functions.items() if f.thread_roles]
    while work:
        k = work.pop()
        f = pkg.functions[k]
        for site in f.calls:
            for t in site.targets:
                g = pkg.functions.get(t)
                if g is None:
                    continue
                if not f.thread_roles <= g.thread_roles:
                    g.thread_roles |= f.thread_roles
                    work.append(t)


def build_package_model(paths: Sequence[str],
                        base: Optional[str] = None) -> PackageModel:
    """Parse every ``.py`` under ``paths`` into a PackageModel. ``base``
    anchors display-relative module keys (defaults to the common parent
    of ``paths``)."""
    paths = [os.path.abspath(p) for p in paths]
    if base is None:
        base = os.path.commonpath([p if os.path.isdir(p)
                                   else os.path.dirname(p)
                                   for p in paths]) if paths else os.getcwd()
        base = os.path.dirname(base) if os.path.isdir(base) else base
    pkg = PackageModel()
    for path in _iter_py_files(paths):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                source = fh.read()
            tree = ast.parse(source, filename=path)
        except (SyntaxError, UnicodeDecodeError, OSError):
            continue
        mod = ModuleInfo(key=_display_key(path, base), path=path,
                         tree=tree, source_lines=source.splitlines(),
                         comments=_read_comments(path))
        pkg.modules[mod.key] = mod
        _Collector(pkg, mod).visit(tree)
    for mod in pkg.modules.values():
        _SecondPass(pkg, mod).run()
    _propagate_traced(pkg)
    _propagate_roles(pkg)
    return pkg
