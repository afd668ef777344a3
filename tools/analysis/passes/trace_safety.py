"""trace-safety: no host sync, traced branching, or mutable capture under jit.

The static/dynamic config split (DESIGN.md §9) promises per-request parameter
changes without recompilation. That only holds if nothing reachable from a jit
entry point (``jit_search``, kernel bodies, shard_map transports, scan/cond
bodies) forces a trace-time decision on a *traced value*:

* ``float()``/``int()``/``bool()``/``.item()`` on a traced array is a silent
  host sync — a ConcretizationError at best, a device round-trip per call at
  worst;
* Python ``if``/``while`` on a traced value bakes one branch into the program
  (and recompiles when the value class changes);
* mutating a captured dict/list inside a traced function runs at *trace* time,
  not run time — a classic silent-wrong-count bug (the deliberate trace
  counters in ``core/lsp.py`` are exactly this, baselined as such).

Reachability is inter-procedural: entry points are jit/shard_map/pallas/scan-
family call sites plus ``*_ref``-parameter kernel defs, closed over same-module
calls (name-based, as before) AND over cross-module edges the ``ProjectIndex``
resolves — ``jit_search`` in ``core/lsp.py`` reaching ``core/topk.py`` and
``core/merge.py`` marks those callees jit-reachable too, so a host sync three
modules away from the nearest ``@jax.jit`` still flags. Unresolved edges fall
back to the intra-module behavior, so precision never regresses: the project
run is a strict superset of the per-module run. Taint is seeded from jnp/jax
call results, NOT from function parameters — a parameter named ``k`` used as
``int(k)`` on an isinstance-guarded host path is fine; the value classes that
matter here are the ones jnp/jax produced.
"""

from __future__ import annotations

import ast

from tools.analysis.core import (
    SRC_PREFIX,
    AnalysisPass,
    ModuleSource,
    ProjectIndex,
    in_scan_tree,
)

_JIT_WRAPPERS = {
    "jax.jit",
    "jit",
    "jax.pmap",
    "jax.vmap",
    "shard_map",
    "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
    "pl.pallas_call",
    "pallas_call",
    "jax.lax.scan",
    "lax.scan",
    "jax.lax.while_loop",
    "lax.while_loop",
    "jax.lax.cond",
    "lax.cond",
    "jax.lax.fori_loop",
    "lax.fori_loop",
    "jax.lax.switch",
    "lax.switch",
    "jax.checkpoint",
    "jax.remat",
}

# attribute accesses that are static under tracing — a traced name reached only
# through these does not taint the enclosing expression
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size"}

# function forms of the same: jnp.ndim(x) is a Python int however traced x is
_STATIC_CALLS = {
    "jnp.ndim",
    "jnp.shape",
    "jnp.size",
    "jnp.result_type",
    "jax.numpy.ndim",
    "jax.numpy.shape",
    "jax.numpy.size",
    "jax.numpy.result_type",
}

_TRACED_CALL_PREFIXES = ("jnp.", "jax.", "lax.", "pl.", "pltpu.")

# jax-namespace calls that run on the host and return static Python values
_HOST_CALLS = {
    "jax.default_backend",
    "jax.devices",
    "jax.local_devices",
    "jax.device_count",
    "jax.local_device_count",
    "jax.process_index",
    "jax.process_count",
    "jax.eval_shape",
    "jax.ShapeDtypeStruct",
    "jax.named_scope",
}
_HOST_PREFIXES = ("jax.tree_util.", "jax.sharding.", "jax.debug.", "jax.dtypes.")

_SCOPES = (
    SRC_PREFIX + "/core/",
    SRC_PREFIX + "/distributed/",
    SRC_PREFIX + "/kernels/",
)


def _is_jit_wrapper(name: str) -> bool:
    return name in _JIT_WRAPPERS or name.endswith(".pallas_call")


def _decorated_as_jit(fn: ast.AST) -> bool:
    for dec in fn.decorator_list:
        d = AnalysisPass.dotted(dec)
        if _is_jit_wrapper(d):
            return True
        if isinstance(dec, ast.Call):
            d = AnalysisPass.dotted(dec.func)
            if _is_jit_wrapper(d):
                return True
            # functools.partial(jax.jit, ...)
            if d.endswith("partial") and dec.args and _is_jit_wrapper(AnalysisPass.dotted(dec.args[0])):
                return True
    return False


class _FnInfo:
    def __init__(self, node: ast.AST):
        self.node = node
        self.name = node.name
        self.calls: set = set()  # simple names this function calls
        self.entry = False


def _collect_functions(tree: ast.AST) -> dict:
    """name -> _FnInfo for every def (nested included; last def wins a name)."""
    fns: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns[node.name] = _FnInfo(node)
    return fns


def _own_nodes(fn: ast.AST):
    """Walk a function body, NOT descending into nested function defs."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(n))


def _param_names(fn: ast.AST) -> set:
    a = fn.args
    names = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    return names


class TraceSafetyPass(AnalysisPass):
    name = "trace-safety"
    description = (
        "host syncs, Python control flow on traced values, and mutable captures "
        "inside jit-reachable functions defeat the zero-recompile contract"
    )
    project_aware = True

    def applies(self, relpath: str) -> bool:
        if not in_scan_tree(relpath):
            return True  # fixtures / temp copies listed explicitly
        return any(relpath.startswith(s) for s in _SCOPES) or relpath.startswith("benchmarks/")

    def run(self, mod: ModuleSource) -> list:
        fns = _collect_functions(mod.tree)
        self._mark_entries(mod.tree, fns)
        self._close_reachability(fns)
        out = []
        for info in fns.values():
            if info.entry:
                out.extend(self._check_function(mod, info.node))
        return out

    def run_project(self, project: ProjectIndex) -> list:
        """Inter-procedural scan: intra-module seeding and closure exactly as
        ``run``, plus reachability propagated along resolved cross-module call
        edges. Emits the union — every function the intra pass would check is
        still checked, so unresolved edges cost recall, never precision."""
        state: dict = {}  # modname -> (mod, fns, node->info)
        for mn, mod in project.modules.items():
            if not self.applies(mod.relpath):
                continue
            fns = _collect_functions(mod.tree)
            self._mark_entries(mod.tree, fns)
            for info in fns.values():
                for n in _own_nodes(info.node):
                    if isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
                        info.calls.add(n.func.id)
            state[mn] = (mod, fns, {id(i.node): i for i in fns.values()})

        changed = True
        while changed:
            changed = False
            for mn, (mod, fns, _) in state.items():
                for info in list(fns.values()):
                    if not info.entry:
                        continue
                    for callee in info.calls:  # intra-module, name-based
                        tgt = fns.get(callee)
                        if tgt is not None and not tgt.entry:
                            tgt.entry = True
                            changed = True
                    fi = project.fn_by_node.get(id(info.node))
                    if fi is None:
                        continue
                    for key in fi.callees:  # cross-module, resolved
                        if key[0] == mn:
                            continue  # intra edges already closed above
                        other = state.get(key[0])
                        tfi = project.functions.get(key)
                        if other is None or tfi is None:
                            continue
                        tinfo = other[2].get(id(tfi.node))
                        if tinfo is not None and not tinfo.entry:
                            tinfo.entry = True
                            changed = True

        out = []
        for mn, (mod, fns, _) in state.items():
            for info in fns.values():
                if info.entry:
                    out.extend(self._check_function(mod, info.node))
        return out

    # -- reachability ----------------------------------------------------------

    def _mark_entries(self, tree: ast.AST, fns: dict) -> None:
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if _decorated_as_jit(node):
                    fns[node.name].entry = True
                # pallas kernel signature: refs in, refs out
                ref_params = [p for p in node.args.args if p.arg.endswith("_ref")]
                if len(ref_params) >= 2:
                    fns[node.name].entry = True
            elif isinstance(node, ast.Call) and _is_jit_wrapper(self.dotted(node.func)):
                # any function *named* as an argument to a jit-family wrapper
                # (scan/cond bodies, shard_map targets, jitted closures)
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    if isinstance(arg, ast.Name) and arg.id in fns:
                        fns[arg.id].entry = True
                    elif isinstance(arg, ast.Call):
                        # functools.partial(body, ...) passed to the wrapper
                        if self.dotted(arg.func).endswith("partial"):
                            for a in arg.args:
                                if isinstance(a, ast.Name) and a.id in fns:
                                    fns[a.id].entry = True

    def _close_reachability(self, fns: dict) -> None:
        for info in fns.values():
            for n in _own_nodes(info.node):
                if isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
                    info.calls.add(n.func.id)
        changed = True
        while changed:
            changed = False
            for info in fns.values():
                if not info.entry:
                    continue
                for callee in info.calls:
                    if callee in fns and not fns[callee].entry:
                        fns[callee].entry = True
                        changed = True

    # -- per-function checks ---------------------------------------------------

    def _check_function(self, mod: ModuleSource, fn: ast.AST) -> list:
        out = []
        params = _param_names(fn)
        local_targets = set(params)
        # name -> first line at which it holds a traced value. Uses at earlier
        # lines are clean: `int(k)` guarded by isinstance, with k only becoming
        # an array in a later `k = jnp.full(...)`, must not flag.
        tainted: dict = {}

        def refs_tainted(expr: ast.AST, at_line: int) -> bool:
            """True when the expression reads a tainted name or a jnp/jax call
            result, ignoring reads that stay static under tracing (x.shape)."""
            stack = [expr]
            while stack:
                n = stack.pop()
                if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
                    continue  # x.shape / x.dtype: do not descend into x
                if isinstance(n, ast.Call) and self.dotted(n.func) in _STATIC_CALLS:
                    continue  # jnp.ndim(x): static int, do not descend into x
                # strict <: the RHS of the tainting assignment itself is
                # evaluated before the target binds (k = jnp.full(..., int(k)))
                if isinstance(n, ast.Name) and tainted.get(n.id, 10**9) < at_line:
                    return True
                if isinstance(n, ast.Call):
                    d = self.dotted(n.func)
                    if (
                        d.startswith(_TRACED_CALL_PREFIXES)
                        and d not in _HOST_CALLS
                        and not d.startswith(_HOST_PREFIXES)
                    ):
                        return True
                stack.extend(ast.iter_child_nodes(n))
            return False

        def mark(name: str, line: int) -> bool:
            if tainted.get(name, 10**9) > line:
                tainted[name] = line
                return True
            return False

        # iterate to a fixpoint: taint flows through straight-line assigns
        for _ in range(4):
            changed = False
            for n in _own_nodes(fn):
                if isinstance(n, ast.Assign) and refs_tainted(n.value, n.lineno):
                    for t in n.targets:
                        for leaf in ast.walk(t):
                            if isinstance(leaf, ast.Name):
                                changed |= mark(leaf.id, n.lineno)
                elif isinstance(n, ast.AugAssign) and refs_tainted(n.value, n.lineno):
                    if isinstance(n.target, ast.Name):
                        changed |= mark(n.target.id, n.lineno)
                elif isinstance(n, ast.For) and refs_tainted(n.iter, n.lineno):
                    for leaf in ast.walk(n.target):
                        if isinstance(leaf, ast.Name):
                            changed |= mark(leaf.id, n.lineno)
            if not changed:
                break

        for n in _own_nodes(fn):
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                targets = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in targets:
                    if isinstance(t, ast.Name):
                        local_targets.add(t.id)

        for n in _own_nodes(fn):
            # host syncs: float()/int()/bool() or .item() on a traced value
            if isinstance(n, ast.Call):
                d = self.dotted(n.func)
                if d in ("float", "int", "bool") and n.args and refs_tainted(n.args[0], n.lineno):
                    out.append(
                        self.finding(
                            mod,
                            n,
                            "host-sync",
                            f"{d}() on a traced value forces a device sync / "
                            "concretization inside a jitted function",
                        )
                    )
                elif isinstance(n.func, ast.Attribute) and n.func.attr == "item":
                    if refs_tainted(n.func.value, n.lineno):
                        out.append(
                            self.finding(
                                mod,
                                n,
                                "host-sync",
                                ".item() on a traced value forces a device sync "
                                "inside a jitted function",
                            )
                        )
            # Python control flow on traced values. isinstance() tests are
            # exempt: a value's *class* is static under tracing even when its
            # contents are not (the standard array-or-int dispatch idiom).
            elif isinstance(n, (ast.If, ast.While)) and refs_tainted(n.test, n.lineno) and not any(
                isinstance(c, ast.Call) and self.dotted(c.func) == "isinstance"
                for c in ast.walk(n.test)
            ):
                kind = "if" if isinstance(n, ast.If) else "while"
                out.append(
                    self.finding(
                        mod,
                        n,
                        "traced-branch",
                        f"Python `{kind}` on a traced value bakes one branch into "
                        "the trace; use jnp.where / lax.cond / lax.while_loop",
                    )
                )
            # mutation of a captured (free) mutable
            elif isinstance(n, (ast.Assign, ast.AugAssign)):
                targets = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) and isinstance(t.value, ast.Name):
                        if t.value.id not in local_targets:
                            out.append(
                                self.finding(
                                    mod,
                                    n,
                                    "mutable-capture",
                                    f"mutating captured `{t.value.id}` inside a "
                                    "jit-reachable function runs at trace time, "
                                    "not run time",
                                )
                            )
            elif isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                if n.func.attr in ("append", "extend", "update", "add", "setdefault", "pop"):
                    v = n.func.value
                    if isinstance(v, ast.Name) and v.id not in local_targets:
                        out.append(
                            self.finding(
                                mod,
                                n,
                                "mutable-capture",
                                f"`{v.id}.{n.func.attr}(...)` mutates a captured "
                                "object at trace time inside a jit-reachable function",
                            )
                        )
        return out
