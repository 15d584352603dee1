"""tpulint rule set: the JAX/TPU hazards this framework actually hits.

Every rule is a pure-AST check registered with :func:`core.rule`.
Rules are deliberately conservative — a finding should be actionable,
and anything intentional gets a ``# tpulint: disable=<rule>`` pragma.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .core import Finding, FileContext, rule, _axes_from_source


# --------------------------------------------------------------------------
# shared AST helpers
# --------------------------------------------------------------------------

def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a pure Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


_JIT_NAMES = {"jit", "jax.jit", "pjit", "jax.pjit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}


def _jit_call_info(call: ast.Call):
    """(wrapped_fn_expr, jit_kwargs) if ``call`` is jax.jit(...) or
    partial(jax.jit, ...), else None.  wrapped_fn_expr is the first
    positional arg (None for the partial/decorator-factory form)."""
    d = dotted(call.func)
    if d in _JIT_NAMES:
        fn = call.args[0] if call.args else None
        return fn, call.keywords
    if d in _PARTIAL_NAMES and call.args \
            and dotted(call.args[0]) in _JIT_NAMES:
        return None, call.keywords
    return None


def _is_jit_decorator(dec: ast.AST) -> Optional[ast.Call]:
    """The jit Call node when ``dec`` makes the function jit-traced."""
    if dotted(dec) in _JIT_NAMES:
        return ast.Call(func=dec, args=[], keywords=[])
    if isinstance(dec, ast.Call):
        d = dotted(dec.func)
        if d in _JIT_NAMES:
            return dec
        if d in _PARTIAL_NAMES and dec.args \
                and dotted(dec.args[0]) in _JIT_NAMES:
            return dec
    return None


def _const_str_elems(node: ast.AST) -> List[Tuple[str, ast.AST]]:
    """String constants in a literal (plain or tuple/list of them)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [(node.value, node)]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            out.extend(_const_str_elems(e))
        return out
    return []


def _int_elems(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            out.extend(_int_elems(e))
        return out
    return []


# memoized by tree identity: several rules need the same maps for the
# same file in one run.  Single-slot caches: rules for one file run
# back-to-back, and bounding at one entry means a long-lived process
# (pytest session, editor daemon) never accumulates pinned ASTs.
_DEFS_MEMO: List[tuple] = []
_ENC_MEMO: List[tuple] = []


def _function_defs(tree: ast.AST) -> Dict[str, List[ast.FunctionDef]]:
    if _DEFS_MEMO and _DEFS_MEMO[0][0] is tree:
        return _DEFS_MEMO[0][1]
    defs: Dict[str, List[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    _DEFS_MEMO[:] = [(tree, defs)]
    return defs


def _enclosing_map(tree: ast.AST) -> Dict[int, Optional[ast.AST]]:
    """id(node) -> innermost enclosing FunctionDef (None at module
    scope) — lets name lookups respect lexical scoping, so a local
    closure named ``step`` never aliases a method named ``step``."""
    if _ENC_MEMO and _ENC_MEMO[0][0] is tree:
        return _ENC_MEMO[0][1]
    enc: Dict[int, Optional[ast.AST]] = {id(tree): None}

    def walk(node, current):
        for child in ast.iter_child_nodes(node):
            enc[id(child)] = current
            walk(child, child if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef))
                else current)

    walk(tree, None)
    _ENC_MEMO[:] = [(tree, enc)]
    return enc


def _resolve_defs(defs: Dict[str, List[ast.FunctionDef]],
                  enc: Dict[int, Optional[ast.AST]],
                  name: str, at_node: ast.AST) -> List[ast.FunctionDef]:
    """Defs named ``name`` visible from ``at_node``, innermost scope
    first; an inner match shadows all outer ones."""
    cands = defs.get(name, [])
    if len(cands) <= 1:
        return cands
    scope = enc.get(id(at_node))
    while True:
        here = [d for d in cands if enc.get(id(d)) is scope]
        if here:
            return here
        if scope is None:
            return []
        scope = enc.get(id(scope))


# --------------------------------------------------------------------------
# rule: host-sync — device->host synchronization inside traced code
# --------------------------------------------------------------------------

_CALLBACK_SUFFIXES = ("io_callback", "pure_callback", "callback")

# attributes whose access is static at trace time (shape arithmetic is
# fine inside jit — int(np.prod(x.shape)) never touches the device)
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "itemsize", "bits"}
_STATIC_CALLS = {"len", "prod", "np.prod", "math.prod", "ord", "min", "max"}


def _host_callback_fn_names(tree: ast.AST) -> Set[str]:
    """Names of local functions handed to io_callback/pure_callback —
    their bodies run on host, so host syncs there are fine."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            d = dotted(node.func) or ""
            if d.split(".")[-1].endswith(_CALLBACK_SUFFIXES):
                for a in node.args:
                    if isinstance(a, ast.Name):
                        out.add(a.id)
    return out


def _traced_functions(tree: ast.Module) -> List[ast.FunctionDef]:
    """Functions that run under jit in this module: jit-decorated defs,
    local defs passed to jax.jit(f, ...), plus (module-local, by-name)
    everything they call — iterated to a fixpoint."""
    defs = _function_defs(tree)
    enc = _enclosing_map(tree)
    host_fns = _host_callback_fn_names(tree)
    traced: Set[ast.FunctionDef] = set()

    for name, fns in defs.items():
        for fn in fns:
            if any(_is_jit_decorator(d) for d in fn.decorator_list):
                traced.add(fn)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            info = _jit_call_info(node)
            if info and isinstance(info[0], ast.Name):
                traced.update(_resolve_defs(defs, enc, info[0].id, node))

    changed = True
    while changed:
        changed = False
        for fn in list(traced):
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Name):
                    for callee in _resolve_defs(defs, enc,
                                                node.func.id, node):
                        if callee.name not in host_fns \
                                and callee not in traced:
                            traced.add(callee)
                            changed = True
    return [fn for fn in traced if fn.name not in host_fns]


def _is_static_expr(node: ast.AST) -> bool:
    """Conservatively true when an expression is trace-time static
    (pure shape/dtype arithmetic)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _STATIC_ATTRS:
            return True
        if isinstance(sub, ast.Call) \
                and (dotted(sub.func) or "") in _STATIC_CALLS:
            return True
    return False


@rule("host-sync",
      "device->host sync inside jit-traced code (.item(), float()/int() "
      "on array values, np.asarray/np.array on traced values)")
def check_host_sync(ctx: FileContext) -> Iterator[Finding]:
    if "jit" not in ctx.source:       # no traced code, nothing to sync
        return
    for fn in _traced_functions(ctx.tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func) or ""
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                yield Finding("host-sync", ctx.path, node.lineno,
                              node.col_offset,
                              ".item() forces a device->host sync inside "
                              "a jit-traced function")
            elif d in ("float", "int", "bool") and len(node.args) == 1 \
                    and not isinstance(node.args[0], ast.Constant) \
                    and not _is_static_expr(node.args[0]):
                yield Finding("host-sync", ctx.path, node.lineno,
                              node.col_offset,
                              f"{d}() on a traced value breaks the trace "
                              "(ConcretizationTypeError on TPU; host sync "
                              "at best)")
            elif d in ("np.asarray", "np.array", "numpy.asarray",
                       "numpy.array", "onp.asarray", "onp.array") \
                    and node.args \
                    and not _is_static_expr(node.args[0]):
                yield Finding("host-sync", ctx.path, node.lineno,
                              node.col_offset,
                              f"{d}() materializes a traced value on host "
                              "inside jit (use jnp, or move out of the "
                              "traced function)")
            elif d in ("jax.device_get", "device_get"):
                yield Finding("host-sync", ctx.path, node.lineno,
                              node.col_offset,
                              "device_get inside a jit-traced function")


# --------------------------------------------------------------------------
# rule: serving-sync — blocking readbacks inside marked serving-loop code
# --------------------------------------------------------------------------

# marker comment that declares a function part of the serving hot loop
# (documented in docs/TPULINT.md and docs/SERVING.md): every device->host
# materialization inside it lands on the per-token critical path, so all
# token fetches must funnel through the single pragma'd emit point
_SERVING_MARK = "serving-loop"
_SERVING_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray",
                       "numpy.array", "onp.asarray", "onp.array"}


def _serving_marked_lines(ctx: FileContext) -> Set[int]:
    """Line numbers of ``# tpulint: serving-loop`` COMMENT tokens (a
    docstring mentioning the marker must not mark anything)."""
    import re

    pat = re.compile(r"#\s*tpulint:\s*" + _SERVING_MARK + r"\b")
    return {line for line, text in ctx.comments if pat.search(text)}


@rule("serving-sync",
      "blocking device->host readback (np.asarray/float/.item/device_get) "
      "inside a '# tpulint: serving-loop' marked method — route token "
      "fetches through the one pragma'd emit point")
def check_serving_sync(ctx: FileContext) -> Iterator[Finding]:
    marked = _serving_marked_lines(ctx)
    if not marked:
        return
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # the marker sits on the def header (possibly multi-line): any
        # marked line between `def` and the first body statement
        header = range(fn.lineno, fn.body[0].lineno + 1)
        if not any(ln in marked for ln in header):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func) or ""
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                yield Finding("serving-sync", ctx.path, node.lineno,
                              node.col_offset,
                              ".item() blocks the serving loop on a "
                              "device->host sync")
            elif d in _SERVING_SYNC_CALLS and node.args \
                    and not _is_static_expr(node.args[0]):
                yield Finding("serving-sync", ctx.path, node.lineno,
                              node.col_offset,
                              f"{d}() materializes a device value on the "
                              "serving loop's critical path — defer to "
                              "the sanctioned emit point")
            elif d == "float" and len(node.args) == 1 \
                    and not isinstance(node.args[0], ast.Constant) \
                    and not _is_static_expr(node.args[0]):
                yield Finding("serving-sync", ctx.path, node.lineno,
                              node.col_offset,
                              "float() on an array value blocks the "
                              "serving loop until the device catches up")
            elif d in ("jax.device_get", "device_get"):
                yield Finding("serving-sync", ctx.path, node.lineno,
                              node.col_offset,
                              "device_get inside a serving-loop method")


# --------------------------------------------------------------------------
# rule: serving-wait — unbounded blocking waits in serving-loop methods
# --------------------------------------------------------------------------

# kwargs whose presence bounds a blocking primitive
_WAIT_TIMEOUT_KWARGS = {"timeout", "timeout_s", "timeout_ms", "deadline"}
# name fragments that signal the loop carries its own bound (a deadline
# comparison, a step budget, a remaining-time check, a monotonic clock)
_WAIT_BOUND_HINTS = ("deadline", "timeout", "budget", "remaining",
                     "expire", "max_steps", "max_iter", "retries",
                     "attempts", "perf_counter", "monotonic")
# zero-arg attribute calls that block the caller until an external event
# (dict.get(key) / str.join(xs) / Event.wait(t) all take args, so the
# bare no-arg form is the unbounded one)
_WAIT_BLOCKING_ATTRS = {"wait", "get", "join", "acquire", "recv"}


def _mentions_wait_bound(node: ast.AST) -> bool:
    """Any identifier/attribute whose name smells like a deadline or
    budget, or a monotonic-clock call — evidence the code bounds its
    own waiting."""
    for n in ast.walk(node):
        if isinstance(n, ast.Name) \
                and any(h in n.id.lower() for h in _WAIT_BOUND_HINTS):
            return True
        if isinstance(n, ast.Attribute) \
                and any(h in n.attr.lower() for h in _WAIT_BOUND_HINTS):
            return True
    return False


def _blocking_wait_call(node: ast.AST) -> Optional[Tuple[str, bool]]:
    """``(description, unbounded_alone)`` when ``node`` is a call that
    can block the caller on an external event.  ``time.sleep`` is
    bounded by itself (the enclosing polling LOOP is the hazard);
    a no-arg ``.wait()`` / ``.get()`` / ``.join()`` / ``.acquire()`` /
    ``.recv()`` blocks indefinitely on its own."""
    if not isinstance(node, ast.Call):
        return None
    if dotted(node.func) == "time.sleep":
        return "time.sleep()", False
    if isinstance(node.func, ast.Attribute) \
            and node.func.attr in _WAIT_BLOCKING_ATTRS \
            and not node.args \
            and not any(kw.arg in _WAIT_TIMEOUT_KWARGS or kw.arg is None
                        for kw in node.keywords):
        return f".{node.func.attr}()", True
    return None


@rule("serving-wait",
      "unbounded blocking wait inside a '# tpulint: serving-loop' marked "
      "method: a no-timeout .wait()/.get()/.join()/.acquire()/.recv(), "
      "or a polling while-loop (sleep/wait in the body) with no "
      "deadline, step budget, or timeout evidence — a stalled device or "
      "a wedged peer must surface as an error, never a silent hang")
def check_serving_wait(ctx: FileContext) -> Iterator[Finding]:
    marked = _serving_marked_lines(ctx)
    if not marked:
        return
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        header = range(fn.lineno, fn.body[0].lineno + 1)
        if not any(ln in marked for ln in header):
            continue
        # 1) bare unbounded blocking primitives, loop or not
        for node in ast.walk(fn):
            bw = _blocking_wait_call(node)
            if bw is not None and bw[1]:
                yield Finding(
                    "serving-wait", ctx.path, node.lineno,
                    node.col_offset,
                    f"{bw[0]} with no timeout in a serving-loop method "
                    "blocks the loop indefinitely — pass a timeout and "
                    "handle expiry")
        # 2) polling loops with no bound: a while whose body (or test)
        #    blocks, and neither the test nor any break/return/raise
        #    guard references a deadline/budget/clock
        for loop in ast.walk(fn):
            if not isinstance(loop, ast.While):
                continue
            if not any(_blocking_wait_call(n) is not None
                       for n in ast.walk(loop)):
                continue
            if _mentions_wait_bound(loop.test):
                continue
            guarded = any(
                isinstance(n, ast.If) and _mentions_wait_bound(n.test)
                and any(isinstance(x, (ast.Break, ast.Return, ast.Raise))
                        for s in n.body + n.orelse
                        for x in ast.walk(s))
                for n in ast.walk(loop))
            if guarded:
                continue
            yield Finding(
                "serving-wait", ctx.path, loop.lineno, loop.col_offset,
                "polling loop with no deadline in a serving-loop method "
                "— bound it by a perf_counter deadline or a step budget "
                "so a wedged condition raises instead of hanging the "
                "serving loop")


# --------------------------------------------------------------------------
# rule: serving-except — broad excepts must route through the failure
# classifier
# --------------------------------------------------------------------------

@rule("serving-except",
      "except Exception / bare except inside a '# tpulint: serving-loop' "
      "marked method that does not route the exception through the "
      "failure classifier (inference/failures.py classify_failure / "
      "_handle_step_failure) or re-raise — an ad-hoc broad catch on the "
      "serving loop invents a second, unaudited failure policy: the "
      "request-level terminal statuses, bisection quarantine, and "
      "engine-dead escalation all live behind the ONE classifier seam")
def check_serving_except(ctx: FileContext) -> Iterator[Finding]:
    marked = _serving_marked_lines(ctx)
    if not marked or "except" not in ctx.source:
        return
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        header = range(fn.lineno, fn.body[0].lineno + 1)
        if not any(ln in marked for ln in header):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.ExceptHandler):
                continue
            names = _exc_names(node.type)
            bare = node.type is None
            if not (bare or any(n in _BROAD for n in names)):
                continue          # narrow catches pick their own policy
            if _routes_to_classifier(node):
                continue
            if any(isinstance(n, ast.Raise) and n.exc is None
                   for n in ast.walk(node)):
                continue          # a bare re-raise defers the decision
            what = "bare except:" if bare else f"except {'/'.join(names)}"
            yield Finding(
                "serving-except", ctx.path, node.lineno, node.col_offset,
                f"{what} in a serving-loop method swallows failures the "
                "classifier must see — route it through "
                "classify_failure/_handle_step_failure (or pragma with "
                "justification)")


# --------------------------------------------------------------------------
# rule: static-args — recompilation / hashability hazards on jit params
# --------------------------------------------------------------------------

_UNHASHABLE = (ast.Dict, ast.List, ast.Set, ast.DictComp, ast.ListComp,
               ast.SetComp)


def _jit_sites(tree: ast.Module):
    """(call, wrapped FunctionDef or None) for every jit application."""
    defs = _function_defs(tree)
    enc = _enclosing_map(tree)
    sites = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                call = _is_jit_decorator(dec)
                if call is not None:
                    sites.append((call, node))
        elif isinstance(node, ast.Call):
            info = _jit_call_info(node)
            if info is not None:
                fn_expr = info[0]
                fn = None
                if isinstance(fn_expr, ast.Name):
                    cands = _resolve_defs(defs, enc, fn_expr.id, node)
                    fn = cands[0] if len(cands) == 1 else None
                sites.append((node, fn))
    return sites


def _params_of(fn: ast.FunctionDef):
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args]
    defaults: Dict[str, ast.AST] = {}
    pos_with_default = names[len(names) - len(a.defaults):] \
        if a.defaults else []
    for name, d in zip(pos_with_default, a.defaults):
        defaults[name] = d
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        names.append(p.arg)
        if d is not None:
            defaults[p.arg] = d
    return names, defaults


@rule("static-args",
      "jit static_argnums/static_argnames that don't exist, or whose "
      "defaults are unhashable (recompile/TypeError hazards)")
def check_static_args(ctx: FileContext) -> Iterator[Finding]:
    if "jit" not in ctx.source:
        return
    for call, fn in _jit_sites(ctx.tree):
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        line = getattr(call, "lineno", fn.lineno if fn else 0)
        col = getattr(call, "col_offset", 0)
        static_names = [s for s, _ in
                        _const_str_elems(kw.get("static_argnames",
                                                ast.Constant(value=None)))]
        static_nums = _int_elems(kw.get("static_argnums",
                                        ast.Constant(value=None)))
        if fn is None:
            continue
        params, defaults = _params_of(fn)
        for name in static_names:
            if name not in params:
                yield Finding("static-args", ctx.path, line, col,
                              f"static_argnames {name!r} is not a "
                              f"parameter of {fn.name}()")
            elif isinstance(defaults.get(name), _UNHASHABLE):
                yield Finding("static-args", ctx.path, line, col,
                              f"static parameter {name!r} of {fn.name}() "
                              "defaults to an unhashable "
                              "dict/list/set — jit static args must hash "
                              "stably or every call recompiles")
        has_varargs = fn.args.vararg is not None
        n_pos = len(fn.args.posonlyargs + fn.args.args)
        for num in static_nums:
            if num >= n_pos and not has_varargs:
                yield Finding("static-args", ctx.path, line, col,
                              f"static_argnums {num} is out of range for "
                              f"{fn.name}() with {n_pos} positional "
                              "parameters")
            elif 0 <= num < n_pos:
                pname = (fn.args.posonlyargs + fn.args.args)[num].arg
                if isinstance(defaults.get(pname), _UNHASHABLE):
                    yield Finding(
                        "static-args", ctx.path, line, col,
                        f"static parameter {pname!r} of {fn.name}() "
                        "defaults to an unhashable dict/list/set")


# --------------------------------------------------------------------------
# rule: axis-name — collective axis names must exist in the mesh
# --------------------------------------------------------------------------

# final attribute -> index of the axis-name positional argument
_COLLECTIVES = {"psum": 1, "pmean": 1, "pmax": 1, "pmin": 1,
                "psum_scatter": 1, "all_gather": 1, "all_to_all": 1,
                "ppermute": 1, "pshuffle": 1, "pbroadcast": 1,
                "axis_index": 0, "axis_size": 0}
_COLLECTIVE_PREFIXES = {"", "lax", "jax.lax"}


def _local_axis_vocab(ctx: FileContext) -> Set[str]:
    """Axis names declared in THIS file: *_AXIS constants, AXIS_ORDER,
    and Mesh(..., axis_names)/make_mesh constructions (tests build toy
    meshes with their own names)."""
    vocab = _axes_from_source(ctx.source)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = (dotted(node.func) or "").split(".")[-1]
        if d in ("Mesh", "make_mesh", "AbstractMesh"):
            cands = list(node.args[1:2]) + [
                k.value for k in node.keywords
                if k.arg == "axis_names"]
            for c in cands:
                vocab |= {s for s, _ in _const_str_elems(c)}
        elif d == "shard_map":
            for k in node.keywords:
                if k.arg == "axis_names":
                    vocab |= {s for s, _ in _const_str_elems(k.value)}
    return vocab


@rule("axis-name",
      "lax collective axis names cross-checked against the mesh axes "
      "declared in comm/mesh.py")
def check_axis_name(ctx: FileContext) -> Iterator[Finding]:
    if not any(c in ctx.source for c in _COLLECTIVES):
        return
    valid = ctx.mesh_axes | _local_axis_vocab(ctx)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d is None:
            continue
        prefix, _, last = d.rpartition(".")
        if last not in _COLLECTIVES or prefix not in _COLLECTIVE_PREFIXES:
            continue
        idx = _COLLECTIVES[last]
        axis_args = [kw.value for kw in node.keywords
                     if kw.arg == "axis_name"]
        if not axis_args and len(node.args) > idx:
            axis_args = [node.args[idx]]
        for arg in axis_args:
            for name, lit in _const_str_elems(arg):
                if name not in valid:
                    yield Finding(
                        "axis-name", ctx.path, lit.lineno, lit.col_offset,
                        f"{last}() over axis {name!r}, which is not a "
                        f"mesh axis (known: {sorted(valid)})")


# --------------------------------------------------------------------------
# rule: comm-named-scope — comm/ collective helpers must label their stages
# --------------------------------------------------------------------------

# the data-moving collectives (axis_index/axis_size are queries, not comm)
_SCOPED_COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "psum_scatter",
                       "all_gather", "all_to_all", "ppermute", "pshuffle",
                       "pbroadcast"}


def _is_comm_module(path: str) -> bool:
    """Files of the comm package (any path segment ``comm``) or
    modules with ``comm`` as a whole underscore-separated word in the
    stem — how the ``bad_/good_comm_named_scope`` fixture pair opts in
    without sweeping ``common.py``/``recommend.py``-style names."""
    import pathlib
    p = pathlib.PurePath(path)
    return "comm" in p.parts or "comm" in p.stem.split("_")


def _scope_chain_has_named_scope(node: ast.AST, enc) -> bool:
    """Whether any enclosing function of ``node`` contains a
    ``named_scope`` call (``with jax.named_scope(...)`` parses as a
    Call inside the With item, so one walk covers both forms)."""
    fn = enc.get(id(node))
    while fn is not None:
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call):
                d = dotted(sub.func) or ""
                if d.split(".")[-1] == "named_scope":
                    return True
        fn = enc.get(id(fn))
    return False


@rule("comm-named-scope",
      "collective calls in comm/ helpers must run under a "
      "jax.named_scope label — tracemerge's device tracks (and the "
      "T3 overlap measurement bar) are built from these",
      library_only=True)
def check_comm_named_scope(ctx: FileContext) -> Iterator[Finding]:
    if not _is_comm_module(ctx.path):
        return
    if not any(c in ctx.source for c in _SCOPED_COLLECTIVES):
        return
    enc = _enclosing_map(ctx.tree)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d is None:
            continue
        prefix, _, last = d.rpartition(".")
        if last not in _SCOPED_COLLECTIVES \
                or prefix not in _COLLECTIVE_PREFIXES:
            continue
        if not _scope_chain_has_named_scope(node, enc):
            yield Finding(
                "comm-named-scope", ctx.path, node.lineno,
                node.col_offset,
                f"{last}() in a comm/ helper without a jax.named_scope "
                "label anywhere in its enclosing function — unlabeled "
                "collectives render as anonymous device slices in "
                "merged timelines (wrap the stage in "
                "`with jax.named_scope(...)`)")


# --------------------------------------------------------------------------
# rule: silent-except — swallowed exceptions in fallback paths
# --------------------------------------------------------------------------

_BROAD = {"Exception", "BaseException"}
_LOG_ATTRS = {"warning", "error", "exception", "critical", "info",
              "debug", "log", "warn"}

# calls that route the exception through the serving failure
# classifier (inference/failures.py): the EXACT seam names, or any
# method on a receiver chain containing a ``failures`` segment (the
# FailurePolicy object's conventional home — ``self.failures.run``).
# Matched exactly, NOT by substring: a handler that merely counts
# failures (``metrics.count_failures``) or logs one locally
# (``log_failure_locally``) has not routed anything and must still
# answer to serving-except/silent-except
_CLASSIFIER_CALLS = {"classify_failure", "_handle_step_failure",
                     "handle_step_failure"}


def _routes_to_classifier(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Call):
            parts = (dotted(node.func) or "").split(".")
            if parts[-1] in _CLASSIFIER_CALLS \
                    or "failures" in parts[:-1]:
                return True
    return False


def _exc_names(node: Optional[ast.AST]) -> List[str]:
    if node is None:
        return []
    if isinstance(node, ast.Tuple):
        return [n for e in node.elts for n in _exc_names(e)]
    d = dotted(node)
    return [d] if d else []


def _handler_surfaces(handler: ast.ExceptHandler) -> bool:
    """True when the handler re-raises, logs/prints the failure, or
    routes it through the serving failure classifier (which logs and
    acts on every exception it accepts)."""
    if _routes_to_classifier(handler):
        return True
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            d = dotted(node.func) or ""
            last = d.split(".")[-1]
            # attribute calls: logger.warning(...), monitor.log(...)
            if isinstance(node.func, ast.Attribute) and last in _LOG_ATTRS:
                return True
            # bare-name calls: log_dist(...), warn(...) — but NOT
            # math.log()-style names ("log" alone is only a logging
            # call as a method)
            if last in (_LOG_ATTRS - {"log"}) or last == "log_dist" \
                    or last.startswith("log_"):
                return True
            if d in ("print", "warnings.warn", "traceback.print_exc",
                     "pytest.skip", "pytest.fail", "pytest.xfail"):
                return True     # pytest.* raise by design
    return False


@rule("silent-except",
      "bare except / except Exception that falls back without logging "
      "the swallowed error (the silent-disable bug pattern)")
def check_silent_except(ctx: FileContext) -> Iterator[Finding]:
    if "except" not in ctx.source:
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        names = _exc_names(node.type)
        bare = node.type is None
        broad = any(n in _BROAD for n in names)
        if not (bare or broad) or _handler_surfaces(node):
            continue
        what = "bare except:" if bare else f"except {'/'.join(names)}"
        yield Finding(
            "silent-except", ctx.path, node.lineno, node.col_offset,
            f"{what} swallows the error without logging it — trace "
            "failures degrade into silent fallbacks; log the exception "
            "(or pragma if genuinely intentional)")


# --------------------------------------------------------------------------
# rule: print — stray stdout/debugger calls in library code
# --------------------------------------------------------------------------

@rule("print",
      "stray print()/pdb/breakpoint in library code — route through "
      "utils.logging", library_only=True)
def check_print(ctx: FileContext) -> Iterator[Finding]:
    if "print" not in ctx.source and "pdb" not in ctx.source \
            and "breakpoint" not in ctx.source:
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            d = dotted(node.func)
            if d == "print":
                yield Finding("print", ctx.path, node.lineno,
                              node.col_offset,
                              "print() in library code — use "
                              "utils.logging (or pragma for CLI output)")
            elif d in ("pdb.set_trace", "ipdb.set_trace", "breakpoint"):
                yield Finding("print", ctx.path, node.lineno,
                              node.col_offset, f"debugger call {d}()")
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            mods = [a.name for a in node.names]
            if isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            for m in mods:
                if m.split(".")[0] in ("pdb", "ipdb"):
                    yield Finding("print", ctx.path, node.lineno,
                                  node.col_offset,
                                  f"debugger import {m!r}")


# --------------------------------------------------------------------------
# rule: donated-reuse — buffers used after donate_argnums handed them over
# --------------------------------------------------------------------------

def _maximal_refs(scope: ast.AST):
    """(dotted, line, is_store) for every maximal Name/Attribute chain in
    ``scope``, skipping nested function bodies."""
    refs: List[Tuple[str, int, bool]] = []
    skip_children: Set[int] = set()

    def visit(node, in_nested):
        if id(node) in skip_children:
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not scope:
            return
        if isinstance(node, (ast.Attribute, ast.Name)):
            d = dotted(node)
            if d is not None:
                ctx_node = node
                is_store = isinstance(ctx_node.ctx,
                                      (ast.Store, ast.Del))
                refs.append((d, node.lineno, is_store))
                # don't descend into the chain's own parts
                inner = node
                while isinstance(inner, ast.Attribute):
                    skip_children.add(id(inner.value))
                    inner = inner.value
        for child in ast.iter_child_nodes(node):
            visit(child, in_nested)

    visit(scope, False)
    return refs


@rule("donated-reuse",
      "buffer passed at a donate_argnums position and then used again — "
      "donated buffers are invalidated by the call")
def check_donated_reuse(ctx: FileContext) -> Iterator[Finding]:
    if "donate_argnums" not in ctx.source:
        return
    scopes = [ctx.tree] + [n for n in ast.walk(ctx.tree)
                           if isinstance(n, (ast.FunctionDef,
                                             ast.AsyncFunctionDef))]
    for scope in scopes:
        donating: Dict[str, List[int]] = {}
        body_nodes = list(ast.walk(scope))
        for node in body_nodes:
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                info = _jit_call_info(node.value)
                if info is None:
                    continue
                kw = {k.arg: k.value for k in node.value.keywords}
                nums = _int_elems(kw.get("donate_argnums",
                                         ast.Constant(value=None)))
                if nums:
                    donating[node.targets[0].id] = nums
        if not donating:
            continue
        refs = _maximal_refs(scope)
        for node in body_nodes:
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in donating):
                continue
            call_line = node.lineno
            for i in donating[node.func.id]:
                if i >= len(node.args):
                    continue
                expr = dotted(node.args[i])
                if expr is None:
                    continue
                # rebinding must hit the expr exactly; a USE of any
                # longer chain (kv.sum, kv[...]) still reads the buffer
                stores = [ln for d, ln, st in refs
                          if st and d == expr and ln >= call_line]
                loads = [ln for d, ln, st in refs
                         if not st and ln > call_line
                         and (d == expr or d.startswith(expr + "."))]
                for ln in sorted(loads):
                    if any(s <= ln for s in stores):
                        break
                    yield Finding(
                        "donated-reuse", ctx.path, ln, 0,
                        f"{expr!r} was donated to {node.func.id}() "
                        f"(donate_argnums={i}, line {call_line}) and is "
                        "used again here — the buffer is invalid after "
                        "donation")
                    break


# --------------------------------------------------------------------------
# rule: metric-name — registry metric names are an API with a grammar
# --------------------------------------------------------------------------

import re as _re

# every registry metric belongs to one engine family; the grammar keeps
# dashboards/scrapes joinable and makes a typo'd name visibly wrong
_METRIC_NAME_RE = _re.compile(r"^(serving|training)_[a-z0-9_]+$")
_METRIC_PREFIX_RE = _re.compile(r"^(serving|training)_")
# MetricsRegistry registration entry points (telemetry/metrics.py)
_METRIC_REG_ATTRS = {"counter", "gauge", "gauge_fn", "histogram"}
# receiver segments that identify a metrics registry (the conventional
# spellings: ``reg`` / ``registry`` locals, ``self.metrics`` /
# ``engine.metrics`` attributes) — whole-segment matched, like
# telemetry-hotpath's receiver check.  The FleetRegistry re-export
# view (serving/fleet_telemetry.py: ``router.fleet_registry`` / a
# ``freg`` local) is a registration site too — its delegating
# counter/gauge/gauge_fn/histogram land in the fleet exposition
_FLEET_REGISTRY_SEGMENTS = {"fleet_registry", "freg"}
_REGISTRY_SEGMENTS = {"reg", "registry", "metrics"} \
    | _FLEET_REGISTRY_SEGMENTS


def _metric_name_literal(arg: ast.AST):
    """``(full_name, None)`` for a plain string literal, ``(None,
    prefix)`` for an f-string with a leading constant part, ``(None,
    None)`` for anything unverifiable (skipped — conservatism over
    noise)."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value, None
    if isinstance(arg, ast.JoinedStr) and arg.values \
            and isinstance(arg.values[0], ast.Constant) \
            and isinstance(arg.values[0].value, str):
        return None, arg.values[0].value
    return None, None


@rule("metric-name",
      "registry metric names must match ^(serving|training)_[a-z0-9_]+$ "
      "and each name must be registered from exactly one source site — "
      "a typo'd or duplicated registration silently forks a second "
      "series that dashboards never join back up", library_only=True, scope="program")
def check_metric_name(program) -> Iterator[Finding]:
    sites: Dict[str, List[Tuple[str, int]]] = {}
    for mod in program.modules.values():
        ctx = mod.ctx
        if "counter" not in ctx.source and "gauge" not in ctx.source \
                and "histogram" not in ctx.source:
            continue
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METRIC_REG_ATTRS
                    and node.args):
                continue
            recv = dotted(node.func.value) or ""
            segs = set(recv.split("."))
            if not segs & _REGISTRY_SEGMENTS:
                continue          # not a metrics-registry receiver
            is_fleet = bool(segs & _FLEET_REGISTRY_SEGMENTS)
            if is_fleet and isinstance(node.args[0], ast.JoinedStr):
                # fleet re-export label hygiene: per-replica identity
                # is the `replica=` label (from the handle) — an
                # f-string metric NAME forks one series per replica,
                # and dashboards/rollups never join them back up
                yield Finding(
                    "metric-name", ctx.path, node.lineno,
                    node.col_offset,
                    "f-string metric name on a FleetRegistry receiver "
                    "— fleet re-export names are ONE literal per "
                    "series; put the replica in the replica= label "
                    "(from the handle), never the metric name")
                continue
            name, prefix = _metric_name_literal(node.args[0])
            if name is not None:
                if not _METRIC_NAME_RE.match(name):
                    yield Finding(
                        "metric-name", ctx.path, node.lineno,
                        node.col_offset,
                        f"metric name {name!r} does not match "
                        "^(serving|training)_[a-z0-9_]+$ — registry "
                        "names are one grammar per engine family")
                else:
                    sites.setdefault(name, []).append(
                        (ctx.path, node.lineno))
            elif prefix is not None:
                # dynamic name with a constant head: the head must
                # already carry the family prefix (f"serving_{k}_total");
                # a fully dynamic name is unverifiable and skipped
                if not _METRIC_PREFIX_RE.match(prefix):
                    yield Finding(
                        "metric-name", ctx.path, node.lineno,
                        node.col_offset,
                        f"dynamic metric name starts with {prefix!r} — "
                        "the constant head must carry the serving_/"
                        "training_ family prefix so the grammar stays "
                        "checkable")
    for name, locs in sites.items():
        unique = sorted(set(locs))
        if len(unique) <= 1:
            continue
        first = unique[0]
        for path, line in unique[1:]:
            yield Finding(
                "metric-name", path, line, 0,
                f"metric {name!r} is also registered at "
                f"{first[0]}:{first[1]} — one name, one registration "
                "site (get-or-create returns the existing series; a "
                "second literal is how typo'd counters fork)")


# --------------------------------------------------------------------------
# rule: telemetry-hotpath — telemetry must never slow (or break) the
# paths it measures
# --------------------------------------------------------------------------

# receiver segments that identify a telemetry object (engine.tracer /
# engine.metrics and the module-level spellings docs/OBSERVABILITY.md
# prescribes); matched as whole dotted-name segments, so a name like
# `geometrics` never trips it
_TELEMETRY_SEGMENTS = {"tracer", "metrics", "telemetry"}


@rule("telemetry-hotpath",
      "time.time() inside a '# tpulint: serving-loop' marked method "
      "(telemetry clocks are monotonic perf_counter only — wall clocks "
      "step under NTP), or a tracer/metrics call inside a jit-traced "
      "function (host telemetry state referenced during tracing is baked "
      "into the compiled program at best, a tracer error at worst)")
def check_telemetry_hotpath(ctx: FileContext) -> Iterator[Finding]:
    marked = _serving_marked_lines(ctx)
    if marked:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            header = range(fn.lineno, fn.body[0].lineno + 1)
            if not any(ln in marked for ln in header):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) \
                        and dotted(node.func) == "time.time":
                    yield Finding(
                        "telemetry-hotpath", ctx.path, node.lineno,
                        node.col_offset,
                        "time.time() in a serving-loop method — the "
                        "wall clock is non-monotonic (NTP steps corrupt "
                        "span/latency math); use time.perf_counter()")
    if "jit" not in ctx.source:
        return
    for fn in _traced_functions(ctx.tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func) or ""
            if set(d.split(".")) & _TELEMETRY_SEGMENTS:
                yield Finding(
                    "telemetry-hotpath", ctx.path, node.lineno,
                    node.col_offset,
                    f"{d}() inside a jit-traced function — telemetry is "
                    "host-side only; record around the dispatch, never "
                    "inside the trace")


# --------------------------------------------------------------------------
# rule: profiler-capture — profiler sessions on serving paths go through
# the one gated capture-window seam
# --------------------------------------------------------------------------

# jax.profiler session-control entry points: starting/stopping a trace
# (or opening a session-shaped context manager) mid-serving-loop
# bypasses the bounded capture window — its budget, its one-session
# ownership, its clock anchor (without which tracemerge cannot align
# the device events), and its loud absent-profiler degradation
_PROFILER_SESSION_NAMES = {"start_trace", "stop_trace", "start_server",
                           "trace", "TraceAnnotation",
                           "StepTraceAnnotation"}
# the direct-import forms are unambiguous session control even without
# a `profiler` receiver segment
_PROFILER_BARE_NAMES = {"start_trace", "stop_trace"}


@rule("profiler-capture",
      "jax.profiler session control (start_trace/stop_trace/trace/...) "
      "inside a '# tpulint: serving-loop' marked method — deep captures "
      "must route through the gated capture-window seam "
      "(telemetry/profiler.py ProfilerCapture arm/begin/end_step): it "
      "owns the session, the clock anchor tracemerge aligns with, the "
      "cooldown/budget rate limit, and the loud absent-profiler "
      "degradation; a span reaches TraceMe through telemetry/tracer.py "
      "SpanTracer, never a direct TraceAnnotation")
def check_profiler_capture(ctx: FileContext) -> Iterator[Finding]:
    marked = _serving_marked_lines(ctx)
    if not marked or "profiler" not in ctx.source \
            and "start_trace" not in ctx.source:
        return
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        header = range(fn.lineno, fn.body[0].lineno + 1)
        if not any(ln in marked for ln in header):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func) or ""
            segs = d.split(".")
            name = segs[-1]
            via_profiler = "profiler" in segs[:-1] \
                and name in _PROFILER_SESSION_NAMES
            bare = len(segs) == 1 and name in _PROFILER_BARE_NAMES
            if via_profiler or bare:
                yield Finding(
                    "profiler-capture", ctx.path, node.lineno,
                    node.col_offset,
                    f"{d}() in a serving-loop method — profiler "
                    "sessions must route through the gated "
                    "capture-window seam (ProfilerCapture "
                    "arm/begin/end_step), which owns the session, "
                    "budget, and clock anchor")


# --------------------------------------------------------------------------
# rule: async-blocking — no synchronous engine/socket work on the
# event loop (the gateway's concurrency contract)
# --------------------------------------------------------------------------

# known-blocking engine seams: a call to one of these names counts
# only when its receiver chain carries an engine-ish segment (matched
# as whole dotted-name segments, the telemetry-hotpath convention), so
# `watcher.cancel()` (an asyncio.Task) or `queue.put_nowait()` never
# trip it while `self.backend.step()` / `eng.generate()` do
_ASYNC_ENGINE_SEAMS = {"generate", "step", "drain", "put", "flush",
                       "cancel", "query", "snapshot", "load_snapshot",
                       "migrate_out", "health", "health_state",
                       "prometheus_text"}
_ASYNC_ENGINE_RECV = {"backend", "engine", "eng", "router", "fleet",
                      "replica", "rep", "metrics", "fleet_registry"}

# blocking socket/file primitives: flagged on ANY receiver — asyncio
# streams spell these differently (read/drain are coroutines, write is
# buffered), so a bare-socket verb inside a coroutine is always a
# stall on the loop
_ASYNC_SOCKET_OPS = {"recv", "recv_into", "send", "sendall", "sendto",
                     "accept", "connect"}


@rule("async-blocking",
      "synchronous blocking calls (engine step/generate/drain/put, "
      "time.sleep, raw socket ops) directly inside an `async def` — "
      "one blocked coroutine stalls the WHOLE event loop (every open "
      "stream, every health probe); route the call through "
      "asyncio.to_thread / loop.run_in_executor (the gateway's "
      "single-worker engine thread)", library_only=True)
def check_async_blocking(ctx: FileContext) -> Iterator[Finding]:
    if "async def" not in ctx.source:
        return
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, ast.AsyncFunctionDef):
            continue
        # awaited calls are fine by construction; collect them first
        awaited: Set[int] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Await) \
                    and isinstance(node.value, ast.Call):
                awaited.add(id(node.value))

        def walk_async(node) -> Iterator[ast.Call]:
            """Yield Call nodes in the async function's own body —
            nested sync defs and lambdas are deferred thunks (the
            executor pattern hands exactly those off the loop), so
            they are NOT this coroutine's blocking calls; a nested
            AsyncFunctionDef is its own coroutine and gets its own
            visit from the outer ast.walk (descending here would
            report its calls twice, misattributed)."""
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if isinstance(child, ast.Call):
                    yield child
                yield from walk_async(child)

        for call in walk_async(fn):
            if id(call) in awaited:
                continue
            d = dotted(call.func)
            if d is None:
                continue
            segs = d.split(".")
            name = segs[-1]
            recv = set(segs[:-1])
            hit = None
            if name in _ASYNC_ENGINE_SEAMS and recv & _ASYNC_ENGINE_RECV:
                hit = "a blocking engine call"
            elif name == "sleep" and (not recv or "time" in recv):
                # bare `sleep` covers `from time import sleep`; an
                # un-awaited asyncio.sleep(...) is also a bug (a no-op
                # coroutine), caught by the same arm
                hit = "a blocking sleep"
            elif name == "sleep" and "asyncio" in recv:
                hit = "an un-awaited asyncio.sleep (a silent no-op)"
            elif name in _ASYNC_SOCKET_OPS:
                hit = "a blocking socket op"
            if hit is not None:
                yield Finding(
                    "async-blocking", ctx.path, call.lineno,
                    call.col_offset,
                    f"{d}() inside `async def {fn.name}` is {hit} on "
                    "the event loop — every other coroutine (streams, "
                    "health, metrics) stalls behind it; route it "
                    "through asyncio.to_thread / "
                    "loop.run_in_executor(engine_thread, ...)")
