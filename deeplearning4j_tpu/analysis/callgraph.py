"""Whole-repo symbol table + conservative call graph for the linter.

The per-file rules in ``analysis/lint.py`` (DLT001-016) see one module at a
time, so a helper that does ``time.time()`` or ``np.asarray(...)`` two
modules away from the ``jax.jit`` entry point is invisible to them, and the
lock-order rule (DLT004) cannot see a deadlock whose two halves live in two
classes in two files. This module is the substrate that makes the
interprocedural rule families (DLT017/018/019) possible:

- **Module summaries, cached by content hash.** Each ``.py`` file is parsed
  once into a :class:`ModuleSummary` — functions (including nested
  functions, lambdas handed to transforms, and the module body itself as a
  pseudo-function), classes with base lists and ``self.<attr>`` type/lock
  assignments, import aliases, and per-function *facts*: raw call sites
  with the lock-hold stack at each site, host-work hazards, lock
  acquisitions (``with`` blocks AND explicit ``acquire()``/``release()``
  pairs), blocking-I/O calls, thread starts/joins, and waiver comments.
  Summaries are pure data (no AST references) and are cached in-process
  keyed by ``(path, sha1(content))``, so a warm ``lint_paths`` run re-reads
  and re-hashes files but never re-parses an unchanged one.

- **Conservative name resolution.** At graph-build time the raw call sites
  are resolved against the global symbol table: module-level functions
  through import aliases (including one-hop re-exports via package
  ``__init__`` files and relative imports), ``self._method(...)`` edges
  with inherited-method lookup through resolved base classes,
  ``self.<attr>.method(...)`` / ``var.method(...)`` through recorded
  constructor assignments (``self.x = Foo(...)``, ``x = Foo(...)``),
  ``super().method(...)``, ``functools.partial(f, ...)`` targets, and
  functions passed as callbacks to tracing transforms (``jax.jit``,
  ``lax.scan``, ``vmap``, ...) or ``threading.Thread(target=...)``.
  Receivers whose type cannot be established produce NO edge — the graph
  under-approximates rather than inventing edges, so every reported call
  chain is a chain that exists in the source.

- **Traced-entry closure.** Functions jit-decorated or passed to a tracing
  transform anywhere in the repo are *traced entries*; everything reachable
  from them through resolved call edges executes at trace time.
  :meth:`CallGraph.reachable_from_entries` yields each reachable function
  with the full entry→...→function chain for the DLT017 messages.

Build with :func:`build_graph`; clear caches (for cold-run timing) with
:func:`clear_cache`.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = [
    "CallGraph", "ModuleSummary", "FunctionFacts", "ClassFacts",
    "build_graph", "summarize_file", "summarize_source", "clear_cache",
    "discover_files", "TRACING_TRANSFORMS",
]

# Tracing transforms: a function handed to one of these (or decorated with
# one) executes at trace time — the DLT002/DLT017 boundary. Matched against
# BOTH the alias-resolved dotted path and the literal text, the lint.py
# convention.
TRACING_TRANSFORMS = frozenset({
    "jax.jit", "jit", "jax.pmap", "pmap", "jax.vmap", "vmap",
    "jax.grad", "grad", "jax.value_and_grad", "value_and_grad",
    "jax.lax.scan", "lax.scan", "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop", "jax.lax.cond", "lax.cond",
    "jax.lax.map", "lax.map", "jax.checkpoint", "jax.remat",
    "jax.eval_shape", "shard_map", "jax.experimental.shard_map.shard_map",
})

# Blocking-I/O entry points for DLT018's held-lock check. Values are short
# human labels for the message.
_BLOCKING_IO = {
    "urllib.request.urlopen": "urlopen",
    "http.client.HTTPConnection": "HTTPConnection",
    "http.client.HTTPSConnection": "HTTPSConnection",
    "socket.create_connection": "socket.create_connection",
    "requests.get": "requests.get", "requests.post": "requests.post",
    "requests.put": "requests.put", "requests.delete": "requests.delete",
    "requests.request": "requests.request",
    "subprocess.run": "subprocess.run",
    "subprocess.Popen": "subprocess.Popen",
    "subprocess.call": "subprocess.call",
    "subprocess.check_call": "subprocess.check_call",
    "subprocess.check_output": "subprocess.check_output",
}

_CLOCKS = frozenset({
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "datetime.datetime.now",
    "datetime.datetime.utcnow",
})

_HOST_RNG_PREFIXES = ("numpy.random.",)
_HOST_RNG = frozenset({
    "random.random", "random.randint", "random.uniform", "random.gauss",
    "random.choice", "random.shuffle", "random.sample", "random.randrange",
})

_LOCK_CTORS = frozenset({
    "threading.Lock", "threading.RLock", "threading.Condition",
})


# ----------------------------------------------------------- summary data
@dataclasses.dataclass
class RawCall:
    """An unresolved call site: ``kind`` + ``parts`` describe the receiver.

    kinds: ``dotted`` (name or attribute chain rooted at a plain name),
    ``self`` (``self.method()``), ``selfattr`` (``self.<attr>.method()``),
    ``var`` (``<localvar>.method()``), ``super`` (``super().method()``).
    ``callbacks`` holds (kind, parts) refs for functions passed as args
    when the callee is a tracing transform or ``threading.Thread``.
    """
    kind: str
    parts: Tuple[str, ...]
    lineno: int
    held: Tuple[str, ...] = ()
    callbacks: Tuple[Tuple[str, Tuple[str, ...]], ...] = ()


@dataclasses.dataclass
class Hazard:
    kind: str       # clock | rng | np | item | device_get | sync
    detail: str     # e.g. "time.time", "numpy.asarray", ".item()"
    lineno: int


@dataclasses.dataclass
class RawLockOp:
    token: str      # "self.<attr>" or a (possibly dotted) name as written
    lineno: int
    held: Tuple[str, ...]
    via: str        # "with" | "acquire"


@dataclasses.dataclass
class RawIo:
    what: str       # human label, e.g. "urlopen", "queue.get"
    lineno: int
    held: Tuple[str, ...]


@dataclasses.dataclass
class RawThread:
    lineno: int
    daemon: str                      # "true" | "false" | "absent" | "dynamic"
    target: Optional[Tuple[str, Tuple[str, ...]]]  # (kind, parts) ref
    assigned: Optional[str]          # "t" | "self._thread" | None
    direct: bool                     # True when assigned straight to a name


@dataclasses.dataclass
class FunctionFacts:
    qname: str
    name: str
    module: str
    path: str
    lineno: int
    cls: Optional[str] = None            # owning class qname for methods
    scopes: Tuple[str, ...] = ()         # enclosing function qnames, inner first
    calls: List[RawCall] = dataclasses.field(default_factory=list)
    hazards: List[Hazard] = dataclasses.field(default_factory=list)
    lock_ops: List[RawLockOp] = dataclasses.field(default_factory=list)
    io_calls: List[RawIo] = dataclasses.field(default_factory=list)
    thread_starts: List[RawThread] = dataclasses.field(default_factory=list)
    joins: Set[str] = dataclasses.field(default_factory=set)
    daemon_sets: Set[str] = dataclasses.field(default_factory=set)
    returns: Set[str] = dataclasses.field(default_factory=set)
    var_types: Dict[str, str] = dataclasses.field(default_factory=dict)
    traced_decorator: bool = False
    uses_device: bool = False
    is_lambda: bool = False


@dataclasses.dataclass
class ClassFacts:
    qname: str
    name: str
    module: str
    path: str
    lineno: int
    bases: Tuple[str, ...] = ()          # raw dotted base names
    attr_types: Dict[str, str] = dataclasses.field(default_factory=dict)
    lock_attrs: Set[str] = dataclasses.field(default_factory=set)
    methods: Set[str] = dataclasses.field(default_factory=set)


@dataclasses.dataclass
class ModuleSummary:
    path: str
    sha: str
    module: str
    is_pkg: bool
    aliases: Dict[str, str] = dataclasses.field(default_factory=dict)
    functions: Dict[str, FunctionFacts] = dataclasses.field(
        default_factory=dict)
    classes: Dict[str, ClassFacts] = dataclasses.field(default_factory=dict)
    module_locks: Set[str] = dataclasses.field(default_factory=set)
    # waiver comments: line -> rules waived there (() = all rules);
    # file_waivers: rules waived file-wide. Kept here so repo-level rules
    # and the waiver audit never have to re-read the file.
    inline_waivers: Dict[int, Tuple[str, ...]] = dataclasses.field(
        default_factory=dict)
    file_waivers: Set[str] = dataclasses.field(default_factory=set)
    parse_error: Optional[Tuple[int, str]] = None


# ------------------------------------------------------------- name utils
def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def module_name_for(path: str) -> Tuple[str, bool]:
    """Dotted module name for a file, by walking up ``__init__.py`` chains.

    Loose files (no package) get ``<parentdir>.<stem>`` so tools/
    functions have unique qnames without colliding.
    """
    path = os.path.abspath(path)
    base = os.path.basename(path)
    is_pkg = base == "__init__.py"
    parts: List[str] = [] if is_pkg else [base[:-3]]
    d = os.path.dirname(path)
    depth = 0
    while os.path.isfile(os.path.join(d, "__init__.py")) and depth < 32:
        parts.insert(0, os.path.basename(d))
        d = os.path.dirname(d)
        depth += 1
    if depth == 0 and not is_pkg:
        # loose file: qualify with the parent dir for uniqueness
        parent = os.path.basename(os.path.dirname(path))
        if parent:
            parts.insert(0, parent)
    elif is_pkg and not parts:
        parts = [os.path.basename(os.path.dirname(path))]
    return ".".join(parts), is_pkg


def _collect_aliases(tree: ast.Module, module: str,
                     is_pkg: bool) -> Dict[str, str]:
    """local name -> fully qualified target, resolving relative imports
    against the module's own package."""
    package = module if is_pkg else module.rsplit(".", 1)[0] \
        if "." in module else ""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = \
                    a.name if a.asname else a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                pkg_parts = package.split(".") if package else []
                keep = len(pkg_parts) - (node.level - 1)
                anchor = ".".join(pkg_parts[:keep]) if keep > 0 else ""
                base = f"{anchor}.{base}".strip(".") if base else anchor
            if not base:
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                out[a.asname or a.name] = f"{base}.{a.name}"
    return out


_RULE_TOKEN = re.compile(r"DLT\d{3}")


def _collect_waivers(lines: Sequence[str]
                     ) -> Tuple[Dict[int, Tuple[str, ...]], Set[str]]:
    """Waiver comment locations, matching lint.py's ``_waived`` semantics:
    a ``lint: disable=DLT0XX`` line waives the named rules there; a line
    ending in bare ``disable`` waives everything on that line. Tokens must
    be real rule ids (``DLT`` + 3 digits) so prose mentioning the syntax
    (docstrings, this comment) is not mistaken for a waiver."""
    inline: Dict[int, Tuple[str, ...]] = {}
    filewide: Set[str] = set()
    for i, text in enumerate(lines, start=1):
        if "lint: disable-file=" in text:
            for part in text.split("lint: disable-file=")[1].split(","):
                part = part.strip()
                if part:
                    tok = part.split()[0].rstrip(")")
                    if _RULE_TOKEN.fullmatch(tok):
                        filewide.add(tok)
        elif "lint: disable=" in text:
            rules = tuple(sorted(set(
                _RULE_TOKEN.findall(text.split("lint: disable=", 1)[1]))))
            if rules:
                inline[i] = rules
        elif "lint: disable" in text and text.rstrip().endswith("disable"):
            inline[i] = ()  # () means "waive everything on this line"
    return inline, filewide


# ---------------------------------------------------------- the summarizer
class _Summarizer:
    """One pass over a module AST producing a :class:`ModuleSummary`."""

    def __init__(self, path: str, module: str, is_pkg: bool):
        self.path = path
        self.module = module
        self.is_pkg = is_pkg
        self.summary: Optional[ModuleSummary] = None
        self.aliases: Dict[str, str] = {}
        self.fns: Dict[str, FunctionFacts] = {}
        self.classes: Dict[str, ClassFacts] = {}
        self.module_locks: Set[str] = set()

    # -- small helpers -----------------------------------------------------
    def _resolve_alias(self, dotted: Optional[str]) -> str:
        if not dotted:
            return ""
        head, _, rest = dotted.partition(".")
        base = self.aliases.get(head, head)
        return f"{base}.{rest}" if rest else base

    def _ref_of(self, node: ast.AST
                ) -> Optional[Tuple[str, Tuple[str, ...]]]:
        """A (kind, parts) reference for a callable expression."""
        if isinstance(node, ast.Name):
            return ("dotted", (node.id,))
        if isinstance(node, ast.Attribute):
            if isinstance(node.value, ast.Name) and node.value.id == "self":
                return ("self", (node.attr,))
            d = _dotted(node)
            if d:
                return ("dotted", tuple(d.split(".")))
        if isinstance(node, ast.Lambda):
            return None  # handled by the caller (needs a qname)
        if isinstance(node, ast.Call):
            # functools.partial(f, ...) -> f
            q = self._resolve_alias(_dotted(node.func))
            if q.endswith("partial") and node.args:
                return self._ref_of(node.args[0])
        return None

    def _lock_token(self, node: ast.AST) -> Optional[str]:
        """``self._x_lock`` / module-level lock names as raw tokens."""
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and node.value.id == "self":
            return f"self.{node.attr}"
        d = _dotted(node)
        if d:
            return d
        return None

    # -- the walk ----------------------------------------------------------
    def run(self, tree: ast.Module, sha: str,
            lines: Sequence[str]) -> ModuleSummary:
        self.aliases = _collect_aliases(tree, self.module, self.is_pkg)
        inline, filewide = _collect_waivers(lines)
        mod_fn = FunctionFacts(
            qname=f"{self.module}.<module>", name="<module>",
            module=self.module, path=self.path, lineno=1)
        self.fns[mod_fn.qname] = mod_fn
        self._scan_stmts(tree.body, mod_fn, [], cls=None,
                         scopes=(), qprefix=self.module)
        self.summary = ModuleSummary(
            path=self.path, sha=sha, module=self.module, is_pkg=self.is_pkg,
            aliases=self.aliases, functions=self.fns, classes=self.classes,
            module_locks=self.module_locks, inline_waivers=inline,
            file_waivers=filewide)
        return self.summary

    def _visit_class(self, node: ast.ClassDef, qprefix: str,
                     scopes: Tuple[str, ...]):
        qname = f"{qprefix}.{node.name}"
        cf = ClassFacts(
            qname=qname, name=node.name, module=self.module, path=self.path,
            lineno=node.lineno,
            bases=tuple(b for b in (_dotted(x) for x in node.bases) if b))
        self.classes[qname] = cf
        # class body: methods + class-scope statements (run at import)
        holder = self.fns[f"{self.module}.<module>"]
        for stmt in node.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cf.methods.add(stmt.name)
                self._visit_function(stmt, qprefix=qname, cls=cf,
                                     scopes=scopes)
            elif isinstance(stmt, ast.ClassDef):
                self._visit_class(stmt, qname, scopes)
            else:
                self._scan_stmts([stmt], holder, [], cls=cf, scopes=scopes,
                                 qprefix=qname)

    def _traced_decorator(self, fn) -> bool:
        for dec in fn.decorator_list:
            d = dec.func if isinstance(dec, ast.Call) else dec
            q = self._resolve_alias(_dotted(d))
            if q in TRACING_TRANSFORMS or (_dotted(d) or "") \
                    in TRACING_TRANSFORMS:
                return True
            if isinstance(dec, ast.Call) and q.endswith("partial"):
                for a in dec.args:
                    if self._resolve_alias(_dotted(a)) in TRACING_TRANSFORMS:
                        return True
        return False

    def _visit_function(self, node, qprefix: str,
                        cls: Optional[ClassFacts],
                        scopes: Tuple[str, ...]):
        qname = f"{qprefix}.{node.name}"
        ff = FunctionFacts(
            qname=qname, name=node.name, module=self.module, path=self.path,
            lineno=node.lineno, cls=cls.qname if cls else None,
            scopes=scopes, traced_decorator=self._traced_decorator(node))
        self.fns[qname] = ff
        # decorators + defaults evaluate in the ENCLOSING scope
        holder = self.fns.get(scopes[0] if scopes
                              else f"{self.module}.<module>")
        if holder is not None:
            for expr in (node.decorator_list + node.args.defaults
                         + [d for d in node.args.kw_defaults if d]):
                self._scan_expr(expr, holder, [], cls, scopes, qprefix)
        self._scan_stmts(node.body, ff, [], cls=cls,
                         scopes=(qname,) + scopes, qprefix=qname)

    def _visit_lambda(self, node: ast.Lambda, owner: FunctionFacts,
                      cls, scopes, qprefix) -> FunctionFacts:
        qname = f"{owner.qname}.<lambda>L{node.lineno}"
        ff = FunctionFacts(
            qname=qname, name="<lambda>", module=self.module, path=self.path,
            lineno=node.lineno, cls=cls.qname if cls else None,
            scopes=(owner.qname,) + scopes, is_lambda=True)
        self.fns[qname] = ff
        self._scan_expr(node.body, ff, [], cls,
                        (owner.qname,) + scopes, qprefix)
        return ff

    # sequential statement scan: ``held`` is a mutable list so an
    # ``acquire()`` persists across the following sibling statements and a
    # ``release()`` (e.g. in a try/finally) removes it again.
    def _scan_stmts(self, stmts, fn: FunctionFacts, held: List[str],
                    cls, scopes, qprefix):
        for node in stmts:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if fn.name == "<module>" and cls is None:
                    self._visit_function(node, qprefix=self.module, cls=None,
                                         scopes=())
                else:
                    self._visit_function(node, qprefix=fn.qname, cls=cls,
                                         scopes=(fn.qname,) + fn.scopes
                                         if fn.name != "<module>" else ())
                continue
            if isinstance(node, ast.ClassDef):
                self._visit_class(node, qprefix if fn.name == "<module>"
                                  else fn.qname, scopes)
                continue
            if isinstance(node, (ast.With, ast.AsyncWith)):
                acquired: List[str] = []
                for item in node.items:
                    self._scan_expr(item.context_expr, fn, held, cls,
                                    scopes, qprefix, skip_lock_expr=True)
                    tok = self._lock_token(item.context_expr)
                    if tok and self._looks_like_lock(tok, cls):
                        fn.lock_ops.append(RawLockOp(
                            tok, node.lineno, tuple(held + acquired),
                            "with"))
                        acquired.append(tok)
                held.extend(acquired)
                self._scan_stmts(node.body, fn, held, cls, scopes, qprefix)
                for _ in acquired:
                    held.pop()
                continue
            if isinstance(node, ast.Try):
                self._scan_stmts(node.body, fn, held, cls, scopes, qprefix)
                for h in node.handlers:
                    self._scan_stmts(h.body, fn, held, cls, scopes, qprefix)
                self._scan_stmts(node.orelse, fn, held, cls, scopes, qprefix)
                self._scan_stmts(node.finalbody, fn, held, cls, scopes,
                                 qprefix)
                continue
            if isinstance(node, ast.If):
                self._scan_expr(node.test, fn, held, cls, scopes, qprefix)
                self._scan_stmts(node.body, fn, held, cls, scopes, qprefix)
                self._scan_stmts(node.orelse, fn, held, cls, scopes, qprefix)
                continue
            if isinstance(node, (ast.For, ast.AsyncFor)):
                self._scan_expr(node.iter, fn, held, cls, scopes, qprefix)
                self._scan_stmts(node.body, fn, held, cls, scopes, qprefix)
                self._scan_stmts(node.orelse, fn, held, cls, scopes, qprefix)
                continue
            if isinstance(node, ast.While):
                self._scan_expr(node.test, fn, held, cls, scopes, qprefix)
                self._scan_stmts(node.body, fn, held, cls, scopes, qprefix)
                self._scan_stmts(node.orelse, fn, held, cls, scopes, qprefix)
                continue
            # leaf statement: record assignments, then scan expressions
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                self._record_assign(node, fn, cls)
            if isinstance(node, ast.Return) and node.value is not None:
                d = _dotted(node.value)
                if d:
                    fn.returns.add(d)
            self._scan_expr(node, fn, held, cls, scopes, qprefix)

    def _looks_like_lock(self, token: str, cls) -> bool:
        if token.startswith("self."):
            attr = token[5:]
            if cls is not None and attr in cls.lock_attrs:
                return True
            return "lock" in attr.lower() or "cv" == attr.lstrip("_")
        head = token.split(".")[0]
        if token in self.module_locks or head in self.module_locks:
            return True
        # imported module-level lock (resolved against the table later)
        q = self._resolve_alias(token)
        last = q.rsplit(".", 1)[-1].lower()
        return "lock" in last

    def _record_assign(self, node, fn: FunctionFacts, cls):
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        value = node.value
        if value is None:
            return
        # thread daemon flag set post-hoc: t.daemon = True
        for t in targets:
            if isinstance(t, ast.Attribute) and t.attr == "daemon" and \
                    isinstance(value, ast.Constant) and value.value is True:
                recv = _dotted(t.value)
                if recv:
                    fn.daemon_sets.add(recv)
        if not isinstance(value, ast.Call):
            return
        q = self._resolve_alias(_dotted(value.func))
        for t in targets:
            if isinstance(t, ast.Name):
                if q in _LOCK_CTORS:
                    if fn.name == "<module>" and cls is None:
                        self.module_locks.add(t.id)
                elif q:
                    fn.var_types[t.id] = q
            elif isinstance(t, ast.Attribute) and \
                    isinstance(t.value, ast.Name) and t.value.id == "self" \
                    and cls is not None:
                if q in _LOCK_CTORS:
                    cls.lock_attrs.add(t.attr)
                elif q:
                    cls.attr_types[t.attr] = q

    # expression scan: record calls/hazards/io/threads; handle explicit
    # acquire/release; descend into lambdas as separate functions.
    def _scan_expr(self, node, fn: FunctionFacts, held: List[str],
                   cls, scopes, qprefix, skip_lock_expr: bool = False):
        stack = [node]
        while stack:
            n = stack.pop()
            if isinstance(n, ast.Lambda):
                self._visit_lambda(n, fn, cls, scopes, qprefix)
                continue
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue  # handled structurally
            if isinstance(n, ast.Call):
                self._record_call(n, fn, held, cls, scopes, qprefix)
            if isinstance(n, (ast.Attribute, ast.Name)):
                q = self._resolve_alias(_dotted(n))
                if q.startswith(("jax.numpy", "jax.lax")):
                    fn.uses_device = True
            stack.extend(ast.iter_child_nodes(n))

    def _record_call(self, node: ast.Call, fn: FunctionFacts,
                     held: List[str], cls, scopes, qprefix):
        func = node.func
        q = self._resolve_alias(_dotted(func))
        attr = func.attr if isinstance(func, ast.Attribute) else None

        # explicit lock acquire/release
        if attr in ("acquire", "release"):
            tok = self._lock_token(func.value)
            if tok and self._looks_like_lock(tok, cls):
                if attr == "acquire":
                    fn.lock_ops.append(RawLockOp(
                        tok, node.lineno, tuple(held), "acquire"))
                    held.append(tok)
                elif tok in held:
                    held.remove(tok)
                return

        # thread lifecycle observations
        if attr == "join":
            recv = _dotted(func.value)
            if recv:
                fn.joins.add(recv)
        if attr == "setDaemon" and node.args and \
                isinstance(node.args[0], ast.Constant) and \
                node.args[0].value is True:
            recv = _dotted(func.value)
            if recv:
                fn.daemon_sets.add(recv)

        # hazards (host work, for the DLT017 closure)
        if q in _CLOCKS:
            fn.hazards.append(Hazard("clock", q, node.lineno))
        elif q in _HOST_RNG or \
                any(q.startswith(p) for p in _HOST_RNG_PREFIXES) or \
                q == "numpy.random":
            fn.hazards.append(Hazard("rng", q, node.lineno))
        elif q == "numpy" or q.startswith("numpy."):
            fn.hazards.append(Hazard("np", q, node.lineno))
        elif q == "jax.device_get":
            fn.hazards.append(Hazard("device_get", q, node.lineno))
        elif q == "jax.block_until_ready" or attr == "block_until_ready":
            fn.hazards.append(Hazard("sync", "block_until_ready",
                                     node.lineno))
        elif attr == "item" and not node.args and not node.keywords:
            fn.hazards.append(Hazard("item", ".item()", node.lineno))

        # blocking I/O (for DLT018's held-lock check)
        if q in _BLOCKING_IO:
            fn.io_calls.append(RawIo(_BLOCKING_IO[q], node.lineno,
                                     tuple(held)))
        elif attr in ("get", "put") and isinstance(func, ast.Attribute):
            recv = (_dotted(func.value) or "").rsplit(".", 1)[-1].lower()
            if "queue" in recv or recv in ("q", "_q") or \
                    recv.endswith("_q"):
                fn.io_calls.append(RawIo(f"queue.{attr}", node.lineno,
                                         tuple(held)))

        # thread starts
        if q == "threading.Thread":
            daemon = "absent"
            target: Optional[Tuple[str, Tuple[str, ...]]] = None
            for kw in node.keywords:
                if kw.arg == "daemon":
                    daemon = ("true" if isinstance(kw.value, ast.Constant)
                              and kw.value.value is True else
                              "false" if isinstance(kw.value, ast.Constant)
                              and kw.value.value is False else "dynamic")
                elif kw.arg == "target":
                    target = self._ref_of(kw.value)
            assigned, direct = self._assign_target_of(node)
            fn.thread_starts.append(RawThread(
                node.lineno, daemon, target, assigned, direct))

        # callbacks handed to tracing transforms / Thread target edges
        short = _dotted(func) or ""
        if q in TRACING_TRANSFORMS or short in TRACING_TRANSFORMS:
            cbs = []
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(arg, ast.Lambda):
                    lam = self._visit_lambda(arg, fn, cls, scopes, qprefix)
                    cbs.append(("dotted", (lam.qname,)))
                    continue
                ref = self._ref_of(arg)
                if ref:
                    cbs.append(ref)
            if cbs:
                fn.calls.append(RawCall("transform", (q or short,),
                                        node.lineno, tuple(held),
                                        tuple(cbs)))
            return

        # the ordinary call-edge record
        if isinstance(func, ast.Name):
            fn.calls.append(RawCall("dotted", (func.id,), node.lineno,
                                    tuple(held)))
        elif isinstance(func, ast.Attribute):
            base = func.value
            if isinstance(base, ast.Name) and base.id == "self":
                fn.calls.append(RawCall("self", (func.attr,), node.lineno,
                                        tuple(held)))
            elif isinstance(base, ast.Attribute) and \
                    isinstance(base.value, ast.Name) and \
                    base.value.id == "self":
                fn.calls.append(RawCall("selfattr", (base.attr, func.attr),
                                        node.lineno, tuple(held)))
            elif isinstance(base, ast.Call) and \
                    isinstance(base.func, ast.Name) and \
                    base.func.id == "super":
                fn.calls.append(RawCall("super", (func.attr,), node.lineno,
                                        tuple(held)))
            elif isinstance(base, ast.Name):
                fn.calls.append(RawCall("var", (base.id, func.attr),
                                        node.lineno, tuple(held)))
            else:
                d = _dotted(func)
                if d:
                    fn.calls.append(RawCall("dotted", tuple(d.split(".")),
                                            node.lineno, tuple(held)))

    def _assign_target_of(self, call: ast.Call
                          ) -> Tuple[Optional[str], bool]:
        """(receiver, direct) for ``x = Thread(...)`` — resolved by the
        parent map built lazily per statement scan."""
        parent = getattr(call, "_dlt_parent", None)
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
            t = parent.targets[0]
            d = _dotted(t)
            if d:
                return d, True
        return None, False


def _attach_parents(tree: ast.AST):
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._dlt_parent = node  # type: ignore[attr-defined]


# ------------------------------------------------------------------ cache
_SUMMARY_CACHE: Dict[str, Tuple[str, ModuleSummary]] = {}
_GRAPH_CACHE: Dict[frozenset, "CallGraph"] = {}


def clear_cache():
    _SUMMARY_CACHE.clear()
    _GRAPH_CACHE.clear()


def summarize_source(path: str, src: str) -> ModuleSummary:
    sha = hashlib.sha1(src.encode("utf-8", "replace")).hexdigest()
    module, is_pkg = module_name_for(path)
    lines = src.splitlines()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        inline, filewide = _collect_waivers(lines)
        return ModuleSummary(path=os.path.abspath(path), sha=sha,
                             module=module, is_pkg=is_pkg,
                             inline_waivers=inline, file_waivers=filewide,
                             parse_error=(e.lineno or 0, e.msg or "syntax"))
    _attach_parents(tree)
    return _Summarizer(os.path.abspath(path), module, is_pkg).run(
        tree, sha, lines)


def summarize_file(path: str) -> ModuleSummary:
    apath = os.path.abspath(path)
    with open(apath, encoding="utf-8") as f:
        src = f.read()
    sha = hashlib.sha1(src.encode("utf-8", "replace")).hexdigest()
    cached = _SUMMARY_CACHE.get(apath)
    if cached is not None and cached[0] == sha:
        return cached[1]
    summary = summarize_source(apath, src)
    _SUMMARY_CACHE[apath] = (sha, summary)
    return summary


def discover_files(paths: Iterable[str]) -> List[str]:
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for f in sorted(names):
                    if f.endswith(".py"):
                        files.append(os.path.join(root, f))
        elif p.endswith(".py") and os.path.isfile(p):
            files.append(p)
    return files


# -------------------------------------------------------------- the graph
@dataclasses.dataclass
class Edge:
    callee: str
    lineno: int
    held: Tuple[str, ...]   # resolved lock ids held at the call site


@dataclasses.dataclass
class LockAcq:
    lock: str
    lineno: int
    held: Tuple[str, ...]
    via: str


class CallGraph:
    """Resolved whole-repo call graph over a set of module summaries."""

    def __init__(self, summaries: Sequence[ModuleSummary]):
        self.summaries = list(summaries)
        self.modules: Dict[str, ModuleSummary] = {
            s.module: s for s in summaries}
        self.functions: Dict[str, FunctionFacts] = {}
        self.classes: Dict[str, ClassFacts] = {}
        for s in summaries:
            self.functions.update(s.functions)
            self.classes.update(s.classes)
        self.edges: Dict[str, List[Edge]] = {}
        self.traced_entries: Set[str] = set()
        self.thread_targets: Set[str] = set()
        self.lock_acqs: Dict[str, List[LockAcq]] = {}
        self.io_held: Dict[str, List[Tuple[str, int, Tuple[str, ...]]]] = {}
        self._resolved_bases: Dict[str, Tuple[str, ...]] = {}
        self._acq_closure: Dict[str, Set[str]] = {}
        self._io_closure: Dict[str, Set[str]] = {}
        self._resolve()

    # -- symbol resolution -------------------------------------------------
    def _resolve_qualified(self, q: str, depth: int = 0
                           ) -> Optional[Tuple[str, str]]:
        if not q or depth > 6:
            return None
        if q in self.functions:
            return ("func", q)
        if q in self.classes:
            return ("class", q)
        parts = q.split(".")
        for i in range(len(parts) - 1, 0, -1):
            mod = ".".join(parts[:i])
            summ = self.modules.get(mod)
            if summ is None:
                continue
            rest = parts[i:]
            cand = f"{mod}.{rest[0]}"
            if len(rest) == 1:
                if cand in self.functions:
                    return ("func", cand)
                if cand in self.classes:
                    return ("class", cand)
            elif cand in self.classes and len(rest) == 2:
                m = self.lookup_method(cand, rest[1])
                if m:
                    return ("func", m)
            target = summ.aliases.get(rest[0])
            if target:
                return self._resolve_qualified(
                    ".".join([target] + rest[1:]), depth + 1)
            return None
        return None

    def resolved_bases(self, cls_qname: str) -> Tuple[str, ...]:
        if cls_qname in self._resolved_bases:
            return self._resolved_bases[cls_qname]
        self._resolved_bases[cls_qname] = ()  # cycle guard
        cf = self.classes.get(cls_qname)
        out: List[str] = []
        if cf is not None:
            summ = self.modules.get(cf.module)
            for raw in cf.bases:
                q = self._expand(raw, summ)
                r = self._resolve_qualified(q)
                if r and r[0] == "class":
                    out.append(r[1])
        self._resolved_bases[cls_qname] = tuple(out)
        return self._resolved_bases[cls_qname]

    def lookup_method(self, cls_qname: str, name: str,
                      _depth: int = 0) -> Optional[str]:
        if _depth > 8:
            return None
        q = f"{cls_qname}.{name}"
        if q in self.functions:
            return q
        for b in self.resolved_bases(cls_qname):
            r = self.lookup_method(b, name, _depth + 1)
            if r:
                return r
        return None

    def class_attr(self, cls_qname: str, attr: str, field: str,
                   _depth: int = 0):
        """attr_types / lock_attrs lookup walking the resolved bases."""
        if _depth > 8:
            return None
        cf = self.classes.get(cls_qname)
        if cf is None:
            return None
        store = getattr(cf, field)
        if field == "lock_attrs":
            if attr in store:
                return cls_qname
        elif attr in store:
            return store[attr], cf.module
        for b in self.resolved_bases(cls_qname):
            r = self.class_attr(b, attr, field, _depth + 1)
            if r:
                return r
        return None

    @staticmethod
    def _expand(dotted: str, summ: Optional[ModuleSummary]) -> str:
        if not summ:
            return dotted
        head, _, rest = dotted.partition(".")
        base = summ.aliases.get(head, head)
        return f"{base}.{rest}" if rest else base

    def _resolve_ref(self, kind: str, parts: Tuple[str, ...],
                     fn: FunctionFacts) -> Optional[str]:
        """Resolve a (kind, parts) reference to a function qname."""
        summ = self.modules.get(fn.module)
        if kind == "dotted":
            name = parts[0]
            if len(parts) == 1:
                # scope chain: nested defs, then module scope, then aliases
                for scope in fn.scopes:
                    cand = f"{scope}.{name}"
                    if cand in self.functions:
                        return cand
                    if cand in self.classes:
                        return self.lookup_method(cand, "__init__")
                cand = f"{fn.module}.{name}"
                if cand in self.functions:
                    return cand
                if cand in self.classes:
                    return self.lookup_method(cand, "__init__")
                if name in self.functions:  # already a qname (lambdas)
                    return name
            q = self._expand(".".join(parts), summ)
            r = self._resolve_qualified(q)
            if r is None:
                return None
            if r[0] == "class":
                return self.lookup_method(r[1], "__init__")
            return r[1]
        if kind == "self" and fn.cls:
            return self.lookup_method(fn.cls, parts[0])
        if kind == "super" and fn.cls:
            for b in self.resolved_bases(fn.cls):
                r = self.lookup_method(b, parts[0])
                if r:
                    return r
            return None
        if kind == "selfattr" and fn.cls:
            at = self.class_attr(fn.cls, parts[0], "attr_types")
            if at:
                raw, mod = at
                r = self._resolve_qualified(raw)
                if r and r[0] == "class":
                    return self.lookup_method(r[1], parts[1])
            return None
        if kind == "var":
            raw = fn.var_types.get(parts[0])
            if raw:
                r = self._resolve_qualified(raw)
                if r and r[0] == "class":
                    return self.lookup_method(r[1], parts[1])
                return None  # typed receiver, but not a resolvable class
            # receiver is not a known local instance: try the whole thing
            # as a module/alias dotted path (``stats.standardize(...)``
            # after ``from . import stats``, ``mod.Class(...)``, ...)
            q = self._expand(".".join(parts), summ)
            r = self._resolve_qualified(q)
            if r is None:
                return None
            if r[0] == "class":
                return self.lookup_method(r[1], "__init__")
            return r[1]
        return None

    def _resolve_lock(self, token: str, fn: FunctionFacts) -> Optional[str]:
        """Raw lock token -> stable lock identity, or None if unknown."""
        if token.startswith("self."):
            attr = token[5:]
            if fn.cls:
                owner = self.class_attr(fn.cls, attr, "lock_attrs")
                if owner:
                    return f"{owner}.{attr}"
                if "lock" in attr.lower():
                    return f"{fn.cls}.{attr}"
            return None
        summ = self.modules.get(fn.module)
        head = token.split(".")[0]
        if summ and head in summ.module_locks and "." not in token:
            return f"{fn.module}.{token}"
        q = self._expand(token, summ) if summ else token
        parts = q.split(".")
        if len(parts) >= 2:
            mod, var = ".".join(parts[:-1]), parts[-1]
            m = self.modules.get(mod)
            if m and var in m.module_locks:
                return f"{mod}.{var}"
        return None

    # -- build -------------------------------------------------------------
    def _resolve(self):
        for fn in list(self.functions.values()):
            edges: List[Edge] = []
            held_cache: Dict[Tuple[str, ...], Tuple[str, ...]] = {}

            def rheld(raw: Tuple[str, ...]) -> Tuple[str, ...]:
                if raw not in held_cache:
                    held_cache[raw] = tuple(
                        r for r in (self._resolve_lock(t, fn) for t in raw)
                        if r)
                return held_cache[raw]

            for call in fn.calls:
                if call.kind == "transform":
                    traced = call.parts[0] in TRACING_TRANSFORMS
                    for ckind, cparts in call.callbacks:
                        target = self._resolve_ref(ckind, cparts, fn)
                        if target:
                            if traced:
                                self.traced_entries.add(target)
                            edges.append(Edge(target, call.lineno,
                                              rheld(call.held)))
                    continue
                target = self._resolve_ref(call.kind, call.parts, fn)
                if target and target != fn.qname:
                    edges.append(Edge(target, call.lineno, rheld(call.held)))
            for th in fn.thread_starts:
                if th.target:
                    t = self._resolve_ref(th.target[0], th.target[1], fn)
                    if t:
                        self.thread_targets.add(t)
            self.edges[fn.qname] = edges
            self.lock_acqs[fn.qname] = [
                LockAcq(lk, op.lineno, rheld(op.held), op.via)
                for op in fn.lock_ops
                for lk in [self._resolve_lock(op.token, fn)] if lk]
            self.io_held[fn.qname] = [
                (io.what, io.lineno, rheld(io.held)) for io in fn.io_calls]
            if fn.traced_decorator:
                self.traced_entries.add(fn.qname)

    # -- queries -----------------------------------------------------------
    def entries(self) -> List[str]:
        return sorted(self.traced_entries)

    def reachable_from(self, entry: str
                       ) -> Dict[str, Tuple[str, ...]]:
        """{reached qname: (entry, ..., reached)} chains via BFS."""
        chains: Dict[str, Tuple[str, ...]] = {entry: (entry,)}
        frontier = [entry]
        while frontier:
            nxt: List[str] = []
            for f in frontier:
                for e in self.edges.get(f, ()):
                    if e.callee not in chains:
                        chains[e.callee] = chains[f] + (e.callee,)
                        nxt.append(e.callee)
            frontier = nxt
        return chains

    def acq_closure(self, qname: str) -> Set[str]:
        """All locks ``qname`` may acquire, directly or via callees."""
        if qname in self._acq_closure:
            return self._acq_closure[qname]
        self._acq_closure[qname] = set()  # cycle guard
        out = {a.lock for a in self.lock_acqs.get(qname, ())}
        for e in self.edges.get(qname, ()):
            out |= self.acq_closure(e.callee)
        self._acq_closure[qname] = out
        return out

    def io_closure(self, qname: str) -> Set[str]:
        """Blocking-I/O labels reachable from ``qname`` (incl. its own)."""
        if qname in self._io_closure:
            return self._io_closure[qname]
        self._io_closure[qname] = set()
        out = {w for w, _, _ in self.io_held.get(qname, ())}
        for e in self.edges.get(qname, ()):
            out |= self.io_closure(e.callee)
        self._io_closure[qname] = out
        return out

    def find_path(self, src: str, dst: str,
                  limit: int = 100000) -> Optional[Tuple[str, ...]]:
        """Shortest call chain src -> ... -> dst, or None."""
        if src == dst:
            return (src,)
        chains = {src: (src,)}
        frontier = [src]
        seen = 0
        while frontier and seen < limit:
            nxt: List[str] = []
            for f in frontier:
                for e in self.edges.get(f, ()):
                    if e.callee in chains:
                        continue
                    chains[e.callee] = chains[f] + (e.callee,)
                    if e.callee == dst:
                        return chains[e.callee]
                    nxt.append(e.callee)
                    seen += 1
            frontier = nxt
        return None


def build_graph(paths: Iterable[str]) -> CallGraph:
    files = discover_files(paths)
    summaries = [summarize_file(p) for p in files]
    key = frozenset((s.path, s.sha) for s in summaries)
    g = _GRAPH_CACHE.get(key)
    if g is None:
        g = CallGraph(summaries)
        _GRAPH_CACHE.clear()  # one graph per working set is enough
        _GRAPH_CACHE[key] = g
    return g
