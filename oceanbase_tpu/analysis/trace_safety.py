"""trace-safety checker: host syncs and retrace hazards (rules ``trace.*``).

The static-shape policy (ROADMAP) only pays off while compiled plans are
actually reused, and reuse dies two ways:

- **host syncs** — ``int()/float()/bool()/.item()/np.asarray`` applied
  to a device value blocks the host on the XLA stream (inside a traced
  body it is worse: a ``ConcretizationError`` or a silently baked-in
  constant).  Rule ``trace.host-sync``.
- **retrace hazards** — Python ``if``/``while`` on a tracer-derived
  value (``trace.tracer-branch``) and identity-hashed or mutable objects
  in compile-cache keys (``trace.cache-key``): ``lru_cache`` keyed on an
  object without content ``__hash__``/``__eq__`` mints a fresh XLA
  executable per instance even when nothing changed.

Scope is computed, not declared: traced roots are functions passed to
``jax.jit``/``shard_map`` (or decorated with them); the *device scope*
is their transitive call closure.  The *host half* is tracked by a small
intraprocedural taint: names bound to jit-compiled callables (directly
or via a factory that returns one) mark their call results as device
values, so ``out, ovf = run(x); int(ovf)`` is flagged in the caller even
though the caller itself is never traced.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from oceanbase_tpu.analysis.core import (
    Analyzer,
    Finding,
    dotted_name,
    iter_functions,
)

# call names that trace their function argument
JIT_NAMES = {"jit", "shard_map", "pmap"}
# ... and those that hand it to the one place that does: a ``Program``'s
# body is what exec/plan.py's executable traces (serial and PX plans)
ROOT_NAMES = JIT_NAMES | {"Program"}
# numpy module aliases whose asarray/array force device->host transfer
NP_ALIASES = {"np", "numpy"}
SYNC_BUILTINS = {"int", "float", "bool"}
# an argument mentioning any of these is static/aux metadata, not data
STATIC_MARKERS = {
    "shape", "ndim", "size", "itemsize", "capacity", "sdict", "values",
    "scale", "precision", "dtype", "np_dtype", "kind", "len", "math",
    "iinfo", "finfo", "axis_names", "devices", "device_count", "fields",
    "maxsize", "environ", "time", "monotonic", "perf_counter",
}
# tracer-producing call prefixes (first segment of the dotted name)
TRACER_ROOTS = {"jnp", "lax"}
TRACER_DOTTED_PREFIXES = ("jax.lax.", "jax.ops.", "jax.numpy.", "jnp.",
                          "lax.")


def _module_of(path: str) -> str:
    mod = path[:-3] if path.endswith(".py") else path
    if mod.endswith("/__init__"):
        mod = mod[: -len("/__init__")]
    return mod.replace("/", ".")


@dataclass
class _FuncInfo:
    path: str
    qual: str
    node: ast.AST
    cls: str | None
    calls: list[ast.Call] = field(default_factory=list)


class _Index:
    """Cross-file function/class/import index with best-effort call
    resolution (precise enough for reachability, never raising)."""

    def __init__(self, az: Analyzer):
        self.az = az
        self.funcs: dict[tuple[str, str], _FuncInfo] = {}
        self.by_name: dict[str, dict[str, list[str]]] = {}  # path->name->quals
        self.classes: dict[str, dict[str, ast.ClassDef]] = {}
        self.mod_to_path = {_module_of(p): p for p in az.trees}
        # per-path import maps (module level + function local, merged)
        self.alias: dict[str, dict[str, str]] = {}       # alias -> module
        self.from_imp: dict[str, dict[str, tuple[str, str]]] = {}
        for path, tree in az.trees.items():
            self.classes[path] = {
                n.name: n for n in ast.walk(tree)
                if isinstance(n, ast.ClassDef)}
            al: dict[str, str] = {}
            fi: dict[str, tuple[str, str]] = {}
            for n in ast.walk(tree):
                if isinstance(n, ast.Import):
                    for a in n.names:
                        al[a.asname or a.name.split(".")[0]] = a.name
                elif isinstance(n, ast.ImportFrom) and n.module:
                    for a in n.names:
                        fi[a.asname or a.name] = (n.module, a.name)
            self.alias[path] = al
            self.from_imp[path] = fi
            names: dict[str, list[str]] = {}
            for qual, fnode, cls in iter_functions(tree):
                info = _FuncInfo(path, qual, fnode, cls)
                info.calls = [c for c in ast.walk(fnode)
                              if isinstance(c, ast.Call)]
                self.funcs[(path, qual)] = info
                names.setdefault(qual.split(".")[-1], []).append(qual)
            self.by_name[path] = names

    # -- resolution ------------------------------------------------------
    def resolve_call(self, path: str, call: ast.Call
                     ) -> list[tuple[str, str]]:
        """Call node -> candidate (path, qualname) targets in the file
        set.  Bare names resolve in-module then via from-imports; dotted
        ``mod.fn`` resolves only through known module aliases; ``self.m``
        resolves within the enclosing class's file."""
        f = call.func
        if isinstance(f, ast.Name):
            return self._resolve_name(path, f.id)
        if isinstance(f, ast.Attribute):
            base = f.value
            if isinstance(base, ast.Name):
                if base.id in ("self", "cls"):
                    # any method of this name in the same file (class
                    # attribution is approximate but file-local)
                    return [(path, q)
                            for q in self.by_name[path].get(f.attr, [])
                            if "." in q]
                mod = self.alias[path].get(base.id)
                if mod is None and base.id in self.from_imp[path]:
                    src_mod, orig = self.from_imp[path][base.id]
                    mod = f"{src_mod}.{orig}"
                if mod is not None:
                    tp = self.mod_to_path.get(mod) or self.mod_to_path.get(
                        mod + ".__init__")
                    if tp is not None:
                        return [(tp, q)
                                for q in self.by_name[tp].get(f.attr, [])]
                    return []  # external module: not ours
            # unknown receiver: unresolved (keeps the scope tight)
            return []
        return []

    def _resolve_name(self, path: str, name: str) -> list[tuple[str, str]]:
        out = [(path, q) for q in self.by_name[path].get(name, [])]
        if out:
            return out
        imp = self.from_imp[path].get(name)
        if imp is not None:
            mod, orig = imp
            tp = self.mod_to_path.get(mod) or self.mod_to_path.get(
                mod + ".__init__")
            if tp is not None:
                return [(tp, q) for q in self.by_name[tp].get(orig, [])]
        return []


def _is_jit_call(call: ast.Call, names=JIT_NAMES) -> bool:
    d = dotted_name(call.func)
    return d is not None and d.split(".")[-1] in names


def _has_jit_decorator(fnode) -> bool:
    for dec in getattr(fnode, "decorator_list", []):
        d = dotted_name(dec if not isinstance(dec, ast.Call) else dec.func)
        if d and d.split(".")[-1] in JIT_NAMES:
            return True
        if isinstance(dec, ast.Call):  # functools.partial(jax.jit, ...)
            for a in dec.args:
                ad = dotted_name(a)
                if ad and ad.split(".")[-1] in JIT_NAMES:
                    return True
    return False


def _traced_roots(idx: _Index) -> set[tuple[str, str]]:
    roots: set[tuple[str, str]] = set()
    for (path, qual), info in idx.funcs.items():
        if _has_jit_decorator(info.node):
            roots.add((path, qual))
    # functions passed (positionally) to jit/shard_map call sites
    for (path, _qual), info in idx.funcs.items():
        for call in info.calls:
            if not _is_jit_call(call, ROOT_NAMES):
                continue
            for a in call.args[:1]:  # the traced callable is arg 0
                if isinstance(a, ast.Name):
                    roots.update(idx._resolve_name(path, a.id))
                elif isinstance(a, ast.Call) and _is_jit_call(a):
                    for inner in a.args[:1]:
                        if isinstance(inner, ast.Name):
                            roots.update(
                                idx._resolve_name(path, inner.id))
    # module-level jit calls (outside any def)
    for path, tree in idx.az.trees.items():
        for n in ast.walk(tree):
            if isinstance(n, ast.Call) and _is_jit_call(n):
                for a in n.args[:1]:
                    if isinstance(a, ast.Name):
                        roots.update(idx._resolve_name(path, a.id))
    return roots


def _device_scope(idx: _Index,
                  roots: set[tuple[str, str]]) -> set[tuple[str, str]]:
    """Transitive call closure of the traced roots."""
    scope = set(roots)
    work = list(roots)
    while work:
        key = work.pop()
        info = idx.funcs.get(key)
        if info is None:
            continue
        for call in info.calls:
            for tgt in idx.resolve_call(info.path, call):
                if tgt not in scope:
                    scope.add(tgt)
                    work.append(tgt)
    return scope


# ---------------------------------------------------------------------------
# host-half taint: jit factories and their call results
# ---------------------------------------------------------------------------


def _returns_jit(info: _FuncInfo, idx: _Index) -> bool:
    """Does this function return a jit-compiled callable (directly, via a
    local name, or inside a returned tuple)?"""
    local_jit: set[str] = set()
    for n in ast.walk(info.node):
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call) \
                and _is_jit_call(n.value):
            for t in n.targets:
                if isinstance(t, ast.Name):
                    local_jit.add(t.id)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and n is not info.node and _has_jit_decorator(n):
            local_jit.add(n.name)
    for n in ast.walk(info.node):
        if not isinstance(n, ast.Return) or n.value is None:
            continue
        vals = n.value.elts if isinstance(n.value, ast.Tuple) else [n.value]
        for v in vals:
            if isinstance(v, ast.Call) and _is_jit_call(v):
                return True
            if isinstance(v, ast.Name) and v.id in local_jit:
                return True
    return False


def _refs(node: ast.AST, tainted: set[str]) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in tainted:
            return True
    return False


def _target_names(t: ast.AST) -> list[str]:
    if isinstance(t, ast.Name):
        return [t.id]
    if isinstance(t, (ast.Tuple, ast.List)):
        out: list[str] = []
        for e in t.elts:
            out.extend(_target_names(e))
        return out
    return []


def _host_device_names(info: _FuncInfo, idx: _Index,
                       factories: set[tuple[str, str]]) -> set[str]:
    """Names holding device values in a host function: results of calling
    a jitted callable (bound from ``jax.jit(...)`` or a factory)."""
    jit_callables: set[str] = set()
    device: set[str] = set()
    for _ in range(3):  # tiny fixpoint: assignment chains are short
        for n in ast.walk(info.node):
            if isinstance(n, ast.Assign):
                v, tgts = n.value, n.targets
            elif isinstance(n, ast.AnnAssign) and n.value is not None:
                v, tgts = n.value, [n.target]
            elif isinstance(n, ast.For):
                if _refs(n.iter, device):
                    device.update(_target_names(n.target))
                continue
            elif isinstance(n, (ast.ListComp, ast.SetComp, ast.DictComp,
                                ast.GeneratorExp)):
                for gen in n.generators:
                    if _refs(gen.iter, device):
                        device.update(_target_names(gen.target))
                continue
            else:
                continue
            names = [x for t in tgts for x in _target_names(t)]
            if isinstance(v, ast.Call):
                if _is_jit_call(v):
                    jit_callables.update(names)
                    continue
                resolved = idx.resolve_call(info.path, v)
                if resolved and all(r in factories for r in resolved):
                    jit_callables.update(names)
                    continue
                fn = v.func
                if isinstance(fn, ast.Name) and fn.id in jit_callables:
                    device.update(names)
                    continue
            if _refs(v, device):
                device.update(names)
    return device


# ---------------------------------------------------------------------------
# flagging
# ---------------------------------------------------------------------------


def _mentions_static(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in STATIC_MARKERS:
            return True
        if isinstance(n, ast.Name) and n.id in STATIC_MARKERS:
            return True
    return False


def _int_annotated_params(fnode) -> set[str]:
    """Parameters annotated as plain python scalars are host values."""
    out = set()
    args = fnode.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs):
        ann = a.annotation
        s = ast.unparse(ann) if ann is not None else ""
        if s in ("int", "float", "bool", "str",
                 "int | None", "float | None", "bool | None"):
            out.add(a.arg)
    return out


def _tracer_names(fnode) -> set[str]:
    """Names assigned from jnp./jax.lax./jax.ops. calls in a traced
    function body — Python branching on them is a retrace (or a
    concretization error)."""
    out: set[str] = set()
    for n in _walk_own(fnode):
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
            d = dotted_name(n.value.func) or ""
            if d.split(".")[0] in TRACER_ROOTS or \
                    d.startswith(TRACER_DOTTED_PREFIXES):
                for t in n.targets:
                    out.update(_target_names(t))
    return out


def _is_tracer_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    d = dotted_name(node.func) or ""
    return d.split(".")[0] in TRACER_ROOTS or \
        d.startswith(TRACER_DOTTED_PREFIXES)


def _walk_own(fnode):
    """Walk a function body WITHOUT descending into nested defs/classes
    (those are separate analysis units; descending double-flags)."""
    stack = list(ast.iter_child_nodes(fnode))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _device_evidence(node: ast.AST, tracers: set[str]) -> bool:
    """Does the expression plausibly reference device data — a
    tracer-derived name or a ``.data``/``.mask``/``.valid`` payload
    attribute?  (Static aux metadata like ``.dtype``/``.shape``/
    ``.sdict`` exempts the expression: trace-time host work on python
    scalars is the package's bread and butter, not a sync.)"""
    if _mentions_static(node):
        return False
    if _refs(node, tracers):
        return True
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in ("data", "mask",
                                                       "valid"):
            return True
    return False


def _flag_device_scope(info: _FuncInfo, az: Analyzer,
                       out: list[Finding]) -> None:
    fnode = info.node
    host_params = _int_annotated_params(fnode)
    tracers = _tracer_names(fnode)

    for n in _walk_own(fnode):
        # nested defs are visited as their own _FuncInfo
        if isinstance(n, ast.Call):
            d = dotted_name(n.func)
            if isinstance(n.func, ast.Name) and \
                    n.func.id in SYNC_BUILTINS and n.args:
                a = n.args[0]
                if isinstance(a, ast.Constant) or \
                        (isinstance(a, ast.Name) and a.id in host_params):
                    continue
                if not _device_evidence(a, tracers):
                    continue
                out.append(Finding(
                    "trace.host-sync", info.path, n.lineno, info.qual,
                    f"{n.func.id}({ast.unparse(a)}) in jit-reachable "
                    f"code forces a host sync (or concretizes a tracer)"))
            elif isinstance(n.func, ast.Attribute) and \
                    n.func.attr in ("item", "tolist") and not n.args:
                v = n.func.value
                param_ref = any(
                    isinstance(x, ast.Name) and x.id in {
                        a.arg for a in (fnode.args.posonlyargs
                                        + fnode.args.args
                                        + fnode.args.kwonlyargs)}
                    for x in ast.walk(v))
                if not (_device_evidence(v, tracers) or param_ref):
                    continue
                out.append(Finding(
                    "trace.host-sync", info.path, n.lineno, info.qual,
                    f".{n.func.attr}() on "
                    f"{ast.unparse(v)} in jit-reachable code"))
            elif d is not None and d.split(".")[0] in NP_ALIASES and \
                    d.split(".")[-1] in ("asarray", "array") and n.args:
                a = n.args[0]
                src = ast.unparse(a)
                # the dict-LUT idiom (host work on static aux metadata at
                # trace time) is legitimate; flag only device-data pulls
                if any(m in src for m in (".data", ".mask", ".valid")) \
                        and ".sdict" not in src and ".values" not in src:
                    out.append(Finding(
                        "trace.host-sync", info.path, n.lineno, info.qual,
                        f"{d}({src}) pulls device data to host in "
                        f"jit-reachable code"))
        elif isinstance(n, (ast.If, ast.While)):
            test = n.test
            if _mentions_static(test):
                continue  # dtype/shape branches resolve at trace time
            if _refs(test, tracers) or any(
                    _is_tracer_call(c) for c in ast.walk(test)):
                out.append(Finding(
                    "trace.tracer-branch", info.path, n.lineno, info.qual,
                    f"python branch on tracer-derived value "
                    f"({ast.unparse(test)[:60]}) retraces per outcome"))


def _flag_host_half(info: _FuncInfo, idx: _Index,
                    factories: set[tuple[str, str]],
                    out: list[Finding]) -> None:
    device = _host_device_names(info, idx, factories)
    if not device:
        return
    for n in _walk_own(info.node):
        if not isinstance(n, ast.Call):
            continue
        d = dotted_name(n.func)
        if isinstance(n.func, ast.Name) and n.func.id in SYNC_BUILTINS \
                and n.args and _refs(n.args[0], device):
            out.append(Finding(
                "trace.host-sync", info.path, n.lineno, info.qual,
                f"{n.func.id}({ast.unparse(n.args[0])}) blocks on the "
                f"XLA stream (device value from a jitted call)"))
        elif isinstance(n.func, ast.Attribute) and \
                n.func.attr in ("item", "tolist") and \
                _refs(n.func.value, device):
            out.append(Finding(
                "trace.host-sync", info.path, n.lineno, info.qual,
                f".{n.func.attr}() on {ast.unparse(n.func.value)} "
                f"blocks on the XLA stream"))
        elif d is not None and d.split(".")[0] in NP_ALIASES and \
                d.split(".")[-1] in ("asarray", "array") and n.args and \
                _refs(n.args[0], device):
            out.append(Finding(
                "trace.host-sync", info.path, n.lineno, info.qual,
                f"{d}({ast.unparse(n.args[0])}) blocks on the XLA "
                f"stream (device value from a jitted call)"))


# ---------------------------------------------------------------------------
# cache keys
# ---------------------------------------------------------------------------

_CACHE_DECOS = ("lru_cache", "cache")


def _cached_funcs(idx: _Index) -> set[tuple[str, str]]:
    out = set()
    for key, info in idx.funcs.items():
        for dec in getattr(info.node, "decorator_list", []):
            d = dotted_name(dec if not isinstance(dec, ast.Call)
                            else dec.func)
            if d and d.split(".")[-1] in _CACHE_DECOS:
                out.add(key)
    return out


def _class_hash_eq(cnode: ast.ClassDef) -> tuple[bool, bool]:
    """(has content __hash__, has content __eq__) — frozen dataclasses
    synthesize both."""
    names = {n.name for n in cnode.body
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    frozen = False
    for dec in cnode.decorator_list:
        if isinstance(dec, ast.Call) and \
                (dotted_name(dec.func) or "").endswith("dataclass"):
            for kw in dec.keywords:
                if kw.arg == "frozen" and \
                        isinstance(kw.value, ast.Constant) and kw.value.value:
                    frozen = True
    return ("__hash__" in names or frozen, "__eq__" in names or frozen)


def _flag_cache_keys(idx: _Index, cached: set[tuple[str, str]],
                     out: list[Finding]) -> None:
    all_classes: dict[str, ast.ClassDef] = {}
    for path, cmap in idx.classes.items():
        all_classes.update(cmap)
    for (path, _qual), info in idx.funcs.items():
        for call in info.calls:
            resolved = idx.resolve_call(path, call)
            if not resolved or not any(r in cached for r in resolved):
                continue
            for a in list(call.args) + [kw.value for kw in call.keywords]:
                if isinstance(a, (ast.List, ast.Dict, ast.Set)):
                    out.append(Finding(
                        "trace.cache-key", path, a.lineno, info.qual,
                        f"mutable {type(a).__name__.lower()} literal in a "
                        f"compile-cache key (unhashable or identity-keyed)"))
                elif isinstance(a, ast.Call):
                    d = dotted_name(a.func)
                    if d == "id" or (d or "").endswith(".id"):
                        out.append(Finding(
                            "trace.cache-key", path, a.lineno, info.qual,
                            "id() in a compile-cache key is identity-"
                            "hashed: equal content still retraces"))
                        continue
                    cname = (d or "").split(".")[-1]
                    cnode = all_classes.get(cname)
                    if cnode is not None:
                        has_h, has_e = _class_hash_eq(cnode)
                        if not (has_h and has_e):
                            out.append(Finding(
                                "trace.cache-key", path, a.lineno,
                                info.qual,
                                f"{cname} lacks content __hash__/__eq__ "
                                f"but keys a compile cache: every "
                                f"instance mints a fresh executable"))


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def check_trace_safety(az: Analyzer) -> list[Finding]:
    idx = _Index(az)
    roots = _traced_roots(idx)
    scope = _device_scope(idx, roots)
    factories = {key for key, info in idx.funcs.items()
                 if _returns_jit(info, idx)}
    out: list[Finding] = []
    for key, info in idx.funcs.items():
        if key in scope:
            _flag_device_scope(info, az, out)
        else:
            _flag_host_half(info, idx, factories, out)
    _flag_cache_keys(idx, _cached_funcs(idx), out)
    return out
