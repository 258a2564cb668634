"""Hot-path device/host discipline rules (the hotlint family).

The eight prior families police *inside-jit* mistakes; these police the
host side of the step loop — the discipline round 5 established by
hand: device->host reads are staged asynchronously
(``copy_to_host_async`` via ``utils.stage_host_async``) and drained at
log boundaries, state threads through donating jits, nothing blocks
between an async dispatch and the device work that could overlap it.
Podracer-style loops live or die on keeping the host out of the device
step; one stray ``.item()`` serializes the whole pipeline.

A **hot loop** is a ``for``/``while`` loop that dispatches a jitted
callable. Jit bindings are resolved lexically and through one layer of
indirection: direct ``jax.jit(f, ...)`` assignments, ``@jit`` /
``@partial(jax.jit, ...)`` decorated defs, plain aliases, ``partial``
wrappers (argument positions shift), and factory calls whose resolved
def (local, or one from-import hop via the project index) returns a jit
expression or a jit-decorated local def. Donation specs ride the same
resolution (reusing rules_sharding's literal ``donate_argnums`` reader):
an **absent** spec is an empty donation set, a **conditional/computed**
spec is unresolvable — and unresolvable silences ``jit-missing-donation``
(house rule: never guess).

The dynamic mirror is :mod:`moolib_tpu.testing.hotwatch`, which counts
actual transfers and compiles over a steady-state window; what these
rules cannot see statically (callables crossing module boundaries as
values, syncs behind opaque attributes) the runtime gate catches.

Suppression grammar (mirrors racelint): ``# hotlint: sync -- <reason>``
on the offending line acknowledges a sync that is the design (a
checkpoint boundary, an action feed to host envs). The reason is
mandatory — a bare ``# hotlint: sync`` suppresses nothing and is itself
flagged by ``hot-bare-suppression``.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .engine import Finding, ModuleContext, Rule, iter_scoped_body
from .engine import terminal_name as _terminal_name
from .rules_bench import is_bench_path
from .rules_jax import _decorator_jit_call, _numpy_aliases
from .rules_sharding import _donate_spec_positions, _kwarg

__all__ = ["RULES"]

_JIT_NAMES = {"jit", "pjit", "pmap"}

_HOT_MARKER_RE = re.compile(r"#\s*hotlint:\s*sync\b")
_HOT_REASON_RE = re.compile(r"#\s*hotlint:\s*sync\b[\s:,(–—-]*([^\s)].*)")

_LOOP_NODES = (ast.For, ast.AsyncFor, ast.While)
_FN_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)

#: Materializing calls: ``float(x)`` builtins and method names forcing a
#: synchronous device->host read. ``block_until_ready`` belongs to
#: sync-in-dispatch-shadow, not here — it syncs without materializing.
_MATERIALIZER_METHODS = {"item", "tolist"}

#: jnp constructors whose loop-invariant construction belongs above the
#: loop (per-step H2D + alloc for a constant).
_JNP_CONSTRUCTORS = {"array", "asarray", "zeros", "ones", "full", "arange",
                     "eye", "linspace"}

#: Method names that dispatch async work besides jit calls: the staged
#: D2H copy and the Accumulator/Group collectives.
_ASYNC_DISPATCH_METHODS = {"copy_to_host_async", "all_reduce",
                           "reduce_gradients"}


def _hot_suppressions(ctx: ModuleContext) -> Dict[int, bool]:
    """line -> has_reason for every ``# hotlint: sync`` marker. Only real
    comments count (``ctx.comments`` is tokenize-derived), so markers in
    lint-test fixture strings neither suppress nor trip the bare rule."""
    out: Dict[int, bool] = {}
    for i, text in ctx.comments:
        if "hotlint" not in text:
            continue
        if _HOT_MARKER_RE.search(text):
            m = _HOT_REASON_RE.search(text)
            out[i] = bool(m and m.group(1).strip())
    return out


def _suppressed(ctx: ModuleContext, node: ast.AST,
                sup: Dict[int, bool]) -> bool:
    return bool(sup.get(getattr(node, "lineno", -1)))


def _jnp_aliases(ctx: ModuleContext) -> Set[str]:
    """Names bound to the jax.numpy module (jnp...)."""
    out: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "jax.numpy" and alias.asname:
                    out.add(alias.asname)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax":
                for alias in node.names:
                    if alias.name == "numpy":
                        out.add(alias.asname or alias.name)
    return out


# -- jit-binding resolution ---------------------------------------------------


def _jit_call_spec(call: ast.Call) -> Optional[Set[int]]:
    """Donation positions declared by a direct jit/pjit/pmap call: a
    literal ``donate_argnums`` gives its set, absence gives the empty set
    (no donation declared), a conditional/computed spec gives None."""
    spec = _kwarg(call, "donate_argnums")
    if spec is None:
        return set()
    return _donate_spec_positions(spec)


def _direct_jit_spec(expr: ast.expr) -> Optional[Tuple[Optional[Set[int]]]]:
    """``(spec,)`` when ``expr`` is a jit/pjit/pmap call (1-tuple so a
    None *spec* is distinguishable from "not a jit expr"); None
    otherwise."""
    if isinstance(expr, ast.Call) and _terminal_name(expr.func) in _JIT_NAMES:
        return (_jit_call_spec(expr),)
    return None


def _factory_jit_spec(fn: ast.AST) -> Optional[Tuple[Optional[Set[int]]]]:
    """Does def ``fn`` return a jitted callable? Checks every ``return``
    in the def (not nested defs) for a jit expression, plus ``return
    <name>`` of a jit-decorated local def. Multiple jit returns with
    disagreeing donation collapse to an unresolvable (None) spec; any
    non-jit return makes the factory not-a-jit-source at all."""
    local_jits: Dict[str, Optional[Set[int]]] = {}
    for node in ast.walk(fn):
        if isinstance(node, _FN_NODES) and node is not fn:
            dec = _decorator_jit_call(node)
            if dec is not None:
                local_jits[node.name] = (
                    set() if dec[1] is None else _jit_call_spec(dec[1])
                )
    specs: List[Optional[Set[int]]] = []
    returns = [n for n in iter_scoped_body(fn.body)
               if isinstance(n, ast.Return)]
    if not returns:
        return None
    for ret in returns:
        v = ret.value
        direct = _direct_jit_spec(v) if v is not None else None
        if direct is not None:
            specs.append(direct[0])
        elif isinstance(v, ast.Name) and v.id in local_jits:
            specs.append(local_jits[v.id])
        else:
            return None  # some path returns a non-jit: not a jit factory
    first = specs[0]
    if all(s == first for s in specs):
        return (first,)
    return (None,)  # jitted on every path, donation disagrees: unresolvable


def _shift_spec(spec: Optional[Set[int]], by: int) -> Optional[Set[int]]:
    """Donation positions after ``partial`` consumed ``by`` leading
    positional args."""
    if spec is None:
        return None
    return {p - by for p in spec if p >= by}


def _all_import_bindings(ctx: ModuleContext) -> Dict[str, Tuple[str, str]]:
    """name -> (dotted module, original name) for every from-import in
    the module INCLUDING function-local (lazy) ones — the examples defer
    their jax/learner imports into ``train()``, and the factory
    resolution must still see them. Last-writer wins on collisions, same
    as the interpreter."""
    out: Dict[str, Tuple[str, str]] = {}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        mod = ctx._absolutize_import(node)
        if mod is None:
            continue
        for alias in node.names:
            if alias.name != "*":
                out[alias.asname or alias.name] = (mod, alias.name)
    return out


def jit_bindings(ctx: ModuleContext) -> Dict[str, Optional[Set[int]]]:
    """name -> donation spec for every name lexically bound to a jitted
    callable anywhere in the module (module level or function-local; the
    map is name-keyed, so rebinding the same name across scopes takes
    last-writer — acceptable for the silence-biased rules built on it).
    Spec semantics follow :func:`_jit_call_spec`: empty set = jitted, no
    donation; None = jitted, donation unresolvable.

    Memoized on the context: all five structural hot rules start from
    this map, and the two-pass tree walk (plus cross-module factory
    resolution) dominates the family's cost — computing it once keeps
    the whole family inside the lint self-runtime budget."""
    cached = getattr(ctx, "_hot_jit_bindings", None)
    if cached is not None:
        return cached
    out: Dict[str, Optional[Set[int]]] = {}

    imports = _all_import_bindings(ctx)

    def factory_spec(call: ast.Call) -> Optional[Tuple[Optional[Set[int]]]]:
        name = call.func.id if isinstance(call.func, ast.Name) else None
        if name is None:
            return None
        resolved = ctx.project.resolve_function(ctx, name)
        if resolved is not None:
            return _factory_jit_spec(resolved[1])
        # Function-local (lazy) imports are invisible to the module
        # symbol table; follow them one hop through the project index.
        bound = imports.get(name)
        if bound is not None:
            target = ctx.project.module(bound[0])
            if target is not None:
                fn = target.top_functions.get(bound[1])
                if fn is not None:
                    return _factory_jit_spec(fn)
            return None
        # Function-local factory defs: look them up lexically.
        for node in ast.walk(ctx.tree):
            if isinstance(node, _FN_NODES) and node.name == name:
                return _factory_jit_spec(node)
        return None

    # Two passes so aliases/partials of names bound later still resolve.
    for _ in range(2):
        for node in ast.walk(ctx.tree):
            if isinstance(node, _FN_NODES):
                dec = _decorator_jit_call(node)
                if dec is not None:
                    out[node.name] = (set() if dec[1] is None
                                      else _jit_call_spec(dec[1]))
                continue
            if not isinstance(node, ast.Assign):
                continue
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
            if not targets:
                continue
            v = node.value
            spec: Optional[Tuple[Optional[Set[int]]]] = None
            direct = _direct_jit_spec(v) if isinstance(v, ast.Call) else None
            if direct is not None:
                spec = direct
            elif isinstance(v, ast.Name) and v.id in out:
                spec = (out[v.id],)
            elif isinstance(v, ast.Call) \
                    and _terminal_name(v.func) == "partial" and v.args:
                inner = v.args[0]
                if isinstance(inner, ast.Name) and inner.id in out:
                    spec = (_shift_spec(out[inner.id], len(v.args) - 1),)
                else:
                    inner_direct = _direct_jit_spec(inner)
                    if inner_direct is not None:
                        spec = (_shift_spec(inner_direct[0],
                                            len(v.args) - 1),)
            elif isinstance(v, ast.Call):
                spec = factory_spec(v)
            if spec is not None:
                for t in targets:
                    out[t] = spec[0]
    ctx._hot_jit_bindings = out
    return out


# -- hot loops + device taint -------------------------------------------------


def _loops(ctx: ModuleContext) -> List[ast.AST]:
    return [n for n in ast.walk(ctx.tree) if isinstance(n, _LOOP_NODES)]


def _loop_jit_calls(loop: ast.AST, jits: Dict[str, object]) -> List[ast.Call]:
    """Jit-bound calls dispatched (lexically) inside the loop body,
    nested defs excluded — they run in their own scope."""
    body = list(loop.body) + list(getattr(loop, "orelse", []))
    return [
        n for n in iter_scoped_body(body)
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
        and n.func.id in jits
    ]


def _assigned_names(target: ast.expr) -> List[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in target.elts:
            out.extend(_assigned_names(e))
        return out
    return []


def _names_in(expr: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}


def _taint_from(value: ast.expr, tainted: Set[str],
                jits: Dict[str, object]) -> bool:
    """Does assigning from ``value`` propagate device taint? Jit-call
    results seed it; plain aliases, subscripts, and attribute loads of a
    tainted name carry it. Arbitrary calls do NOT (their result may be
    anything — silence over guessing)."""
    if isinstance(value, ast.Call):
        return isinstance(value.func, ast.Name) and value.func.id in jits
    if isinstance(value, (ast.Name, ast.Subscript, ast.Attribute)):
        base = value
        while isinstance(base, (ast.Subscript, ast.Attribute)):
            base = base.value
        return isinstance(base, ast.Name) and base.id in tainted
    if isinstance(value, (ast.Tuple, ast.List)):
        return any(_taint_from(e, tainted, jits) for e in value.elts)
    return False


def _device_taint(scope_body: List[ast.stmt],
                  jits: Dict[str, object]) -> Set[str]:
    """Names carrying jit-result values anywhere in the scope (eager:
    order-insensitive, because loop bodies re-run — a name tainted at the
    bottom is tainted at the top of the next iteration). Two passes reach
    the alias fixpoint for the chains that occur in practice."""
    tainted: Set[str] = set()
    for _ in range(2):
        for node in iter_scoped_body(scope_body):
            if isinstance(node, ast.Assign):
                if _taint_from(node.value, tainted, jits):
                    for t in node.targets:
                        tainted.update(_assigned_names(t))
    return tainted


def _log_boundary(stack: List[ast.AST]) -> bool:
    """Is the innermost enclosing ``if`` a log/drain boundary? The house
    drain pattern gates host reads on a log-cadence test (``now -
    last_log >= log_interval``) — any name mentioning ``log`` or
    ``drain`` in the test exempts the read."""
    for anc in reversed(stack):
        if isinstance(anc, ast.If):
            for n in ast.walk(anc.test):
                name = None
                if isinstance(n, ast.Name):
                    name = n.id
                elif isinstance(n, ast.Attribute):
                    name = n.attr
                if name and ("log" in name.lower()
                             or "drain" in name.lower()):
                    return True
    return False


def _walk_with_ifstack(stmts: List[ast.stmt]):
    """Yield (node, enclosing-if stack) for every node under ``stmts``
    without crossing nested defs — the log-boundary exemption needs the
    ``if`` ancestry that a flat walk loses."""
    def go(node: ast.AST, stack: List[ast.AST]):
        yield node, stack
        if isinstance(node, _FN_NODES + (ast.ClassDef, ast.Lambda)):
            return
        pushed = stack + [node] if isinstance(node, ast.If) else stack
        for child in ast.iter_child_nodes(node):
            yield from go(child, pushed)

    for s in stmts:
        yield from go(s, [])


# -- rules --------------------------------------------------------------------


class HostTransferInStepLoop(Rule):
    family = "hot"
    name = "host-transfer-in-steploop"
    description = (
        "a jit-result value is synchronously materialized (float()/"
        ".item()/.tolist()/np.asarray()/jax.device_get()/f-string "
        "interpolation) inside a loop that also dispatches a jitted "
        "step: every iteration stalls the device pipeline on a blocking "
        "D2H read. Stage with copy_to_host_async (utils.stage_host_async) "
        "and drain at a log boundary, or acknowledge a designed sync "
        "with `# hotlint: sync -- <reason>`."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        jits = jit_bindings(ctx)
        if not jits:
            return
        sup = _hot_suppressions(ctx)
        np_aliases = _numpy_aliases(ctx)
        seen: Set[int] = set()
        for loop in _loops(ctx):
            if not _loop_jit_calls(loop, jits):
                continue
            body = list(loop.body) + list(getattr(loop, "orelse", []))
            tainted = _device_taint(body, jits)
            if not tainted:
                continue
            for node, ifstack in _walk_with_ifstack(body):
                if id(node) in seen:
                    continue
                msg = self._materializes(node, tainted, np_aliases)
                if msg is None:
                    continue
                if _log_boundary(ifstack) or _suppressed(ctx, node, sup):
                    continue
                seen.add(id(node))
                yield self.finding(ctx, node, msg)

    @staticmethod
    def _materializes(node: ast.AST, tainted: Set[str],
                      np_aliases: Set[str]) -> Optional[str]:
        if isinstance(node, ast.FormattedValue):
            if _names_in(node.value) & tainted:
                return ("f-string interpolation of a jit-result value "
                        "forces a blocking D2H read each iteration; "
                        "stage it and format at the log boundary")
            return None
        if not isinstance(node, ast.Call):
            return None
        f = node.func
        if isinstance(f, ast.Name) and f.id == "float" and node.args \
                and _names_in(node.args[0]) & tainted:
            return ("float() on a jit-result value blocks the step loop "
                    "on a D2H read; stage via copy_to_host_async and "
                    "drain at a log boundary")
        if isinstance(f, ast.Attribute) and f.attr in _MATERIALIZER_METHODS \
                and _names_in(f.value) & tainted:
            return (f"`.{f.attr}()` on a jit-result value blocks the "
                    "step loop on a D2H read; stage via "
                    "copy_to_host_async and drain at a log boundary")
        if isinstance(f, ast.Attribute) and f.attr in ("asarray", "array") \
                and isinstance(f.value, ast.Name) \
                and f.value.id in np_aliases and node.args \
                and _names_in(node.args[0]) & tainted:
            return (f"{f.value.id}.{f.attr}() on a jit-result value "
                    "synchronously materializes it every iteration; "
                    "stage via copy_to_host_async and drain at a log "
                    "boundary")
        if isinstance(f, ast.Attribute) and f.attr == "device_get" \
                and node.args and _names_in(node.args[0]) & tainted:
            return ("jax.device_get() in the step loop blocks on a full "
                    "D2H read; stage via copy_to_host_async and drain "
                    "at a log boundary")
        if isinstance(f, ast.Attribute) and f.attr == "format" \
                and any(_names_in(a) & tainted for a in node.args):
            return ("str.format() of a jit-result value forces a "
                    "blocking D2H read each iteration; stage it and "
                    "format at the log boundary")
        return None


class JitMissingDonation(Rule):
    family = "hot"
    name = "jit-missing-donation"
    description = (
        "a loop rebinds a jitted call's result onto its own argument "
        "(`state = train_step(state, batch)` threading) but the jit "
        "declares no donate_argnums for that position: XLA keeps both "
        "generations of the buffers live — double HBM for the threaded "
        "state plus a copy. Donate the threaded position (conditional "
        "donation specs are trusted and stay silent)."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        jits = jit_bindings(ctx)
        if not jits:
            return
        sup = _hot_suppressions(ctx)
        for loop in _loops(ctx):
            body = list(loop.body) + list(getattr(loop, "orelse", []))
            for node in iter_scoped_body(body):
                if not isinstance(node, ast.Assign) \
                        or not isinstance(node.value, ast.Call):
                    continue
                call = node.value
                if not isinstance(call.func, ast.Name) \
                        or call.func.id not in jits:
                    continue
                spec = jits[call.func.id]
                if spec is None:
                    continue  # conditional/computed donation: trust it
                targets: Set[str] = set()
                for t in node.targets:
                    targets.update(_assigned_names(t))
                for pos, arg in enumerate(call.args):
                    if isinstance(arg, ast.Name) and arg.id in targets \
                            and pos not in spec \
                            and not _suppressed(ctx, node, sup):
                        yield self.finding(
                            ctx, node,
                            f"{arg.id!r} threads through jitted "
                            f"{call.func.id!r} (position {pos}) without "
                            "donation: declare donate_argnums=("
                            f"{pos},) so XLA reuses the buffers instead "
                            "of holding both generations",
                        )
                        break  # one finding per threading call site


class SyncInDispatchShadow(Rule):
    family = "hot"
    name = "sync-in-dispatch-shadow"
    description = (
        "a blocking sync (.block_until_ready()/jax.block_until_ready()) "
        "sits lexically between an async dispatch (jit call, "
        "copy_to_host_async, Accumulator/Group collective) and later "
        "jitted device work in the same function: the sync serializes "
        "work that could overlap — dispatch everything first, then "
        "sync. Deliberate timing barriers in bench-scoped files are "
        "exempt."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        if is_bench_path(ctx.relpath):
            return  # timing protocols sync between dispatches by design
        jits = jit_bindings(ctx)
        if not jits:
            return
        sup = _hot_suppressions(ctx)
        bodies: List[List[ast.stmt]] = [ctx.tree.body]
        for node in ast.walk(ctx.tree):
            if isinstance(node, _FN_NODES):
                bodies.append(node.body)
        for body in bodies:
            dispatch_lines: List[int] = []
            device_lines: List[int] = []
            syncs: List[Tuple[ast.AST, List[ast.AST]]] = []
            for node, ifstack in _walk_with_ifstack(body):
                if not isinstance(node, ast.Call):
                    continue
                line = getattr(node, "lineno", 0)
                f = node.func
                if isinstance(f, ast.Name) and f.id in jits:
                    dispatch_lines.append(line)
                    device_lines.append(line)
                elif isinstance(f, ast.Name) and f.id == "stage_host_async":
                    dispatch_lines.append(line)
                elif isinstance(f, ast.Attribute) \
                        and f.attr in _ASYNC_DISPATCH_METHODS:
                    dispatch_lines.append(line)
                elif isinstance(f, ast.Attribute) \
                        and f.attr == "block_until_ready":
                    syncs.append((node, ifstack))
            for node, ifstack in syncs:
                line = getattr(node, "lineno", 0)
                if not any(d < line for d in dispatch_lines):
                    continue
                if not any(w > line for w in device_lines):
                    continue  # final sync before leaving: legitimate
                if _log_boundary(ifstack) or _suppressed(ctx, node, sup):
                    continue
                yield self.finding(
                    ctx, node,
                    "block_until_ready() between an async dispatch and "
                    "later jitted work serializes the overlap; move the "
                    "sync after the last dispatch (or drop it and let "
                    "data dependence order the work)",
                )


class DeviceAllocInStepLoop(Rule):
    family = "hot"
    name = "device-alloc-in-steploop"
    description = (
        "a jnp constant constructor (jnp.zeros/ones/full/arange/array...) "
        "with loop-invariant arguments runs inside a hot loop: every "
        "iteration pays an H2D transfer plus a device allocation for a "
        "value that never changes. Hoist it above the loop."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        jits = jit_bindings(ctx)
        jnp_aliases = _jnp_aliases(ctx)
        if not jits or not jnp_aliases:
            return
        sup = _hot_suppressions(ctx)
        for loop in _loops(ctx):
            if not _loop_jit_calls(loop, jits):
                continue
            body = list(loop.body) + list(getattr(loop, "orelse", []))
            stored: Set[str] = set(_assigned_names(getattr(
                loop, "target", ast.Tuple(elts=[]))))
            for node in iter_scoped_body(body):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        stored.update(_assigned_names(t))
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    stored.update(_assigned_names(node.target))
                elif isinstance(node, (ast.For, ast.AsyncFor)):
                    stored.update(_assigned_names(node.target))
                elif isinstance(node, ast.comprehension):
                    stored.update(_assigned_names(node.target))
                elif isinstance(node, ast.NamedExpr):
                    stored.update(_assigned_names(node.target))
                elif isinstance(node, ast.withitem) \
                        and node.optional_vars is not None:
                    stored.update(_assigned_names(node.optional_vars))
            for node in iter_scoped_body(body):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if not (isinstance(f, ast.Attribute)
                        and f.attr in _JNP_CONSTRUCTORS
                        and isinstance(f.value, ast.Name)
                        and f.value.id in jnp_aliases):
                    continue
                operands = list(node.args) \
                    + [kw.value for kw in node.keywords]
                if not operands:
                    continue  # jnp.array() alone: malformed, not ours
                invariant = all(
                    not any(isinstance(n, ast.Call)
                            for n in ast.walk(op))
                    and not (_names_in(op) & stored)
                    for op in operands
                )
                if invariant and not _suppressed(ctx, node, sup):
                    yield self.finding(
                        ctx, node,
                        f"{f.value.id}.{f.attr}() with loop-invariant "
                        "arguments allocates (and transfers) the same "
                        "constant every iteration; hoist it above the "
                        "loop",
                    )


class PythonLoopOverDeviceArray(Rule):
    family = "hot"
    name = "python-loop-over-device-array"
    description = (
        "Python-level for-iteration (or per-element indexing by the loop "
        "variable) over a jit-result array: each element access is a "
        "separate device read and the loop body runs un-fused on the "
        "host. Use vmap/scan/fori_loop (or materialize once, outside "
        "the hot path)."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        jits = jit_bindings(ctx)
        if not jits:
            return
        sup = _hot_suppressions(ctx)
        np_aliases = _numpy_aliases(ctx)
        bodies: List[List[ast.stmt]] = [ctx.tree.body]
        for node in ast.walk(ctx.tree):
            if isinstance(node, _FN_NODES):
                bodies.append(node.body)
        for body in bodies:
            tainted = _device_taint(body, jits)
            if not tainted:
                continue
            # A name rebound through an np materializer is host-resident
            # from there on; eager taint cannot order the two, so such
            # names are ambiguous — drop them (silence over guessing).
            for node in iter_scoped_body(body):
                if isinstance(node, ast.Assign) \
                        and isinstance(node.value, ast.Call):
                    f = node.value.func
                    if isinstance(f, ast.Attribute) \
                            and f.attr in ("asarray", "array") \
                            and isinstance(f.value, ast.Name) \
                            and f.value.id in np_aliases:
                        for t in node.targets:
                            tainted.difference_update(_assigned_names(t))
            if not tainted:
                continue
            for node in iter_scoped_body(body):
                if isinstance(node, (ast.For, ast.AsyncFor)) \
                        and isinstance(node.iter, ast.Name) \
                        and node.iter.id in tainted \
                        and not _suppressed(ctx, node, sup):
                    yield self.finding(
                        ctx, node,
                        f"Python for-loop iterates jit-result array "
                        f"{node.iter.id!r} element by element; vmap/"
                        "scan/fori_loop keeps it on device (or "
                        "materialize once with device_get outside the "
                        "hot path)",
                    )
                elif isinstance(node, (ast.For, ast.AsyncFor)):
                    loop_vars = set(_assigned_names(node.target))
                    if not loop_vars:
                        continue
                    for sub in iter_scoped_body(list(node.body)):
                        if isinstance(sub, ast.Subscript) \
                                and isinstance(sub.value, ast.Name) \
                                and sub.value.id in tainted \
                                and isinstance(sub.slice, ast.Name) \
                                and sub.slice.id in loop_vars \
                                and not _suppressed(ctx, sub, sup):
                            yield self.finding(
                                ctx, sub,
                                f"per-element indexing of jit-result "
                                f"array {sub.value.id!r} by the loop "
                                "variable reads the device once per "
                                "element; vmap/scan/fori_loop (or one "
                                "bulk device_get) replaces the loop",
                            )
                            break  # one finding per loop


class HotBareSuppression(Rule):
    family = "hot"
    name = "hot-bare-suppression"
    description = (
        "`# hotlint: sync` without a reason: the marker exists to record "
        "WHY a sync is the design (checkpoint boundary, host env feed). "
        "Write `# hotlint: sync -- <reason>`; a bare marker suppresses "
        "nothing."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for line, has_reason in sorted(_hot_suppressions(ctx).items()):
            if not has_reason:
                yield Finding(
                    path=ctx.relpath, line=line, col=0, rule=self.name,
                    message="bare `# hotlint: sync` marker: add the "
                            "reason (`# hotlint: sync -- <why this sync "
                            "is the design>`) or remove it",
                    snippet=ctx.line(line).strip(),
                )


RULES = [HostTransferInStepLoop, JitMissingDonation, SyncInDispatchShadow,
         DeviceAllocInStepLoop, PythonLoopOverDeviceArray,
         HotBareSuppression]
