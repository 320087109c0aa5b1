"""JAX/shard_map-aware rules — the bug classes that actually cost rounds.

Each rule is deliberately conservative: it flags only what it can resolve
statically (string-literal axis names, module-level constants, in-module
function bodies) and stays silent on anything dynamic, because a static
gate that cries wolf gets ``# noqa``'d into uselessness.

  collective-axis      — a collective (lax.psum/pmean/ppermute/all_gather/
                         axis_index/...) called with a literal axis name the
                         module never binds in any shard_map/Mesh/
                         PartitionSpec. The wrong-axis-reaches-a-collective
                         bug: "dp" typo'd where the mesh says "sp".
  unreduced-contraction — a shard_map whose in_specs shard an axis its
                         out_specs drop, with a dot/conv in the body and NO
                         collective over that axis anywhere on the body's
                         call graph: the per-shard partial products escape
                         unsummed.
  host-sync-in-hot-loop — .item()/np.asarray/jax.device_get/
                         block_until_ready inside for/while bodies of the
                         measurement surfaces (harness.py, training.py,
                         run.py, resilience/supervisor.py);
                         float(...) too when the loop is a timed region
                         (its body calls time.monotonic/perf_counter/time).
                         Each one is a device round-trip inside the loop
                         being timed. EXEMPT: anything inside a function
                         decorated ``@off_timed_path``
                         (resilience.sentinel) — sentinel/digest screening
                         is a host round trip BY DESIGN and contractually
                         runs between timed regions, not inside them; the
                         decorator is the statically-checkable form of that
                         contract (same review bar as a # noqa).
  span-write-in-timed-region — span/metric persistence (tracer.emit /
                         with ...span(...) / histogram.observe /
                         counter.inc / a tracer-owned journal append)
                         inside a TIMED loop in the hot-loop scope (now
                         including observability/): spans persist with an
                         fsync'd journal append — measure first, persist
                         from an @off_timed_path completion helper
                         (Tracer.emit takes explicit bounds for exactly
                         this). Same exemption mechanics as
                         host-sync-in-hot-loop.
  blocking-socket-call-in-timed-region — recv/accept/connect/sendall/
                         getresponse/urlopen inside a TIMED loop in the
                         hot-loop scope (now including the serving front
                         end + traffic/SLO layers): a network wait inside
                         the region being measured corrupts the number
                         and stalls the loop behind a peer's TCP window.
                         Transport belongs on its own thread behind the
                         admission queue. Same @off_timed_path exemption;
                         a deliberate latency-measuring client loop
                         carries a reviewed # noqa.
  key-reuse            — the same PRNG key expression consumed by two
                         jax.random draws with no intervening split/fold_in
                         rebinding (same scope), or a loop-invariant key
                         drawn from inside a loop.
  jit-in-loop          — jax.jit/shard_map/pmap constructed inside a
                         for/while body: a fresh callable (and retrace) per
                         iteration.
  check-vma-disabled   — a literal ``check_vma=False``: the shard_map
                         varying-manual-axes checker silently off for the
                         whole body (ops.vma exists so kernels can keep it
                         ON; a deliberate disable documents itself with
                         ``# noqa: check-vma-disabled <reason>``).
  stale-device-set     — a Mesh/make_mesh/mesh_for call inside a function
                         (the grow-back paths hold the same discipline:
                         ElasticPool.heal admits a rejoining device only
                         after a FRESH jax.devices() re-query shows it)
                         consuming a MODULE-cached ``jax.devices()`` /
                         ``jax.local_devices()`` list. By the time a
                         rebuild/retry path runs, the device set may have
                         shrunk — the cached list still names the lost
                         chip, so every "recovered" mesh routes
                         collectives through a dead device. Re-query at
                         build time (``parallel.elastic.ElasticPool``
                         owns this discipline). Module-scope mesh builds
                         (executed at import, list is fresh) are exempt.
  implicit-upcast      — a dot/conv contraction primitive in a hot-path
                         module (ops/, models/, parallel/, precision/)
                         fed a bf16/int8-cast operand with no explicit
                         ``preferred_element_type``: the accumulation
                         dtype is then whatever XLA infers, which differs
                         across backends and silently changes numerics —
                         the precision subsystem's contract
                         (docs/PRECISION.md) is that mixed-precision
                         contractions STATE their accumulation width.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import List, Optional, Set

from .engine import FileContext, Rule, register
from .findings import Finding
from .index import ModuleIndex, _terminal_attr

# ---------------------------------------------------------------------------
# collective-axis


_COLLECTIVES_AXIS_ARG1 = {
    "psum", "pmean", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "psum_scatter", "pcast",
}
_COLLECTIVES_AXIS_ARG0 = {"axis_index", "axis_size"}
_LAX_ROOTS = {"lax"}


def _is_lax_call(func: ast.expr) -> bool:
    """True for lax.X / jax.lax.X (not arbitrary obj.psum methods)."""
    if not isinstance(func, ast.Attribute):
        return False
    v = func.value
    if isinstance(v, ast.Name):
        return v.id in _LAX_ROOTS
    if isinstance(v, ast.Attribute) and v.attr == "lax":
        return isinstance(v.value, ast.Name) and v.value.id == "jax"
    return False


def _axis_arg(node: ast.Call) -> Optional[ast.expr]:
    name = _terminal_attr(node.func)
    for kw in node.keywords:
        if kw.arg == "axis_name":
            return kw.value
    if name in _COLLECTIVES_AXIS_ARG0:
        return node.args[0] if node.args else None
    if len(node.args) >= 2:
        return node.args[1]
    return None


@register
class CollectiveAxisRule(Rule):
    code = "collective-axis"

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        bound = ctx.mod.axis_names
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not _is_lax_call(node.func):
                continue
            name = _terminal_attr(node.func)
            if name not in _COLLECTIVES_AXIS_ARG1 | _COLLECTIVES_AXIS_ARG0:
                continue
            arg = _axis_arg(node)
            if arg is None:
                continue
            axes = ctx.mod.resolve_strs(arg)
            if axes is None:
                continue  # dynamic axis expression — can't judge statically
            for ax in axes:
                if ax not in bound:
                    out.append(
                        self.finding(
                            ctx, node.lineno,
                            f"lax.{name}(..., {ax!r}): axis {ax!r} is never "
                            "bound by a shard_map/Mesh/PartitionSpec in this "
                            "module — a wrong axis name raises (or worse, "
                            "silently no-ops under a different mesh) only "
                            "at trace time on the device",
                            span=(node.lineno, getattr(node, "end_lineno", node.lineno)),
                        )
                    )
        return out


# ---------------------------------------------------------------------------
# unreduced-contraction


_CONTRACTION_CALLS = {
    "dot", "dot_general", "matmul", "einsum", "tensordot",
    "conv_general_dilated", "conv", "conv2d",
}
# Collectives that move/combine data over the axis; any of them over the
# dropped axis means the body author thought about that axis — we only flag
# the "no collective at all" case.
_REDUCING_CALLS = {
    "psum", "pmean", "psum_scatter", "all_gather", "all_to_all", "ppermute",
    "pcast",
}


def _spec_axes(mod: ModuleIndex, node: ast.expr) -> Optional[Set[str]]:
    """All axis names in a spec pytree; None if anything is unresolvable
    (a spec held in a variable, a computed P(...) entry, ...)."""
    axes: Set[str] = set()

    def entry(a: ast.expr) -> bool:  # one P(...) argument (axis position)
        if isinstance(a, ast.Constant):
            if isinstance(a.value, str):
                axes.add(a.value)
                return True
            return a.value is None
        if isinstance(a, ast.Name):
            val = mod.str_consts.get(a.id)
            if val is None:
                return False
            axes.add(val)
            return True
        if isinstance(a, (ast.Tuple, ast.List)):
            return all(entry(e) for e in a.elts)
        return False

    def tree(n: ast.expr) -> bool:  # the spec pytree structure
        if isinstance(n, (ast.Tuple, ast.List)):
            return all(tree(e) for e in n.elts)
        if isinstance(n, ast.Dict):
            return all(tree(v) for v in n.values if v is not None)
        if isinstance(n, ast.Call) and _terminal_attr(n.func) in (
            "P",
            "PartitionSpec",
        ):
            return all(entry(a) for a in n.args)
        return entry(n)  # bare string/None leaf spec

    return axes if tree(node) else None


def _body_calls(mod: ModuleIndex, fn_node: ast.AST, seen: Set[str]) -> Set[str]:
    """Terminal callee names reachable from fn_node through in-module defs."""
    names: Set[str] = set()
    for sub in ast.walk(fn_node):
        if isinstance(sub, ast.Call):
            callee = _terminal_attr(sub.func)
            if callee:
                names.add(callee)
                info = mod.functions.get(callee)
                if info is not None and callee not in seen:
                    seen.add(callee)
                    names |= _body_calls(mod, info.node, seen)
        elif isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.MatMult):
            names.add("matmul")
    return names


@register
class UnreducedContractionRule(Rule):
    code = "unreduced-contraction"

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if _terminal_attr(node.func) != "shard_map":
                continue
            kws = {kw.arg: kw.value for kw in node.keywords}
            in_specs, out_specs = kws.get("in_specs"), kws.get("out_specs")
            if in_specs is None or out_specs is None or not node.args:
                continue
            in_axes = _spec_axes(ctx.mod, in_specs)
            out_axes = _spec_axes(ctx.mod, out_specs)
            if in_axes is None or out_axes is None:
                continue  # dynamic specs — can't judge
            dropped = in_axes - out_axes
            if not dropped:
                continue
            body = node.args[0]
            if isinstance(body, ast.Name):
                info = ctx.mod.functions.get(body.id)
                if info is None:
                    continue
                body_node: ast.AST = info.node
            elif isinstance(body, ast.Lambda):
                body_node = body
            else:
                continue
            called = _body_calls(ctx.mod, body_node, {getattr(body, "id", "")})
            if not called & _CONTRACTION_CALLS:
                continue
            if called & _REDUCING_CALLS:
                continue  # some collective on the path — assume it handles it
            axes = ", ".join(sorted(dropped))
            out.append(
                self.finding(
                    ctx, node.lineno,
                    f"shard_map in_specs shard axis {axes!r} but out_specs "
                    "drop it, the body contracts (dot/conv/matmul) and "
                    "contains no collective — per-shard partial products "
                    "escape without a psum",
                    span=(node.lineno, getattr(node, "end_lineno", node.lineno)),
                )
            )
        return out


# ---------------------------------------------------------------------------
# host-sync-in-hot-loop


# The measurement surfaces plus the serving subsystem's dispatch/load
# loops: a host sync per dispatched batch is a latency tax on every
# request, so serving/{server,loadgen,batcher,queue}.py live under the
# same rule (journal writes and result slicing are exempted via the same
# @off_timed_path contract the supervisor's screening uses). The
# observability subsystem lives here too — an instrumentation layer that
# syncs inside the loops it instruments would corrupt every number it
# reports. Directory scope, so it covers trace/metrics/stages/export AND
# the ISSUE 12 replay module (the replay pacing loop re-drives a
# recorded arrival schedule on the wall clock, where a stray sync or
# span write would shear the very schedule being reproduced) AND the
# ISSUE 13 roofline/specs modules the moment they exist — the roofline
# join runs between timed regions by construction, and the specs
# module's live memory snapshots feed an @off_timed_path telemetry
# helper on the dispatch loop.
_HOT_LOOP_FILES = {
    "harness.py", "training.py", "run.py", "supervisor.py",
    "server.py", "loadgen.py", "batcher.py", "queue.py",
    # The network serving front end + traffic/SLO layers (ISSUE 11): the
    # transport sits directly on the request path, so a host sync or a
    # blocking socket call inside a timed region there is a per-request
    # latency tax.
    "frontend.py", "traffic.py", "slo.py",
    # The fleet router tier (ISSUE 16): every northbound request crosses
    # the router's handler and redirect loop, and the probe loop's
    # latency IS the detection time — the same no-stray-waits discipline
    # as the front end, plus the fleet launcher whose READY scan gates
    # drill bring-up.
    "router.py", "fleet.py",
    # The fused-block megakernels (ISSUE 17): the whole point is one
    # HBM round trip per block, so a stray host sync in the wrapper
    # would sit directly inside every timed fused pass.
    "megakernel.py",
    # The Autopilot controller (ISSUE 18): evaluated from the dispatch
    # loop's observation cadence every tick, so an undeclared sync in
    # evaluate() would tax every batch. Actuation (gate screen, rewarm)
    # is host-blocking by design and rides the @off_timed_path contract.
    "controller.py",
    # The fleet control plane (ISSUE 20): evaluated from the router's
    # probe sweep, whose latency IS the fleet's detection time — a
    # stray sync there delays every backend's scrape. Journaling rides
    # @off_timed_path like the router's own record writers.
    "fleet_controller.py",
}
_HOT_LOOP_DIRS = {"observability"}


def _in_hot_loop_scope(path: Path) -> bool:
    return path.name in _HOT_LOOP_FILES or bool(
        _HOT_LOOP_DIRS & set(path.parts[:-1])
    )
_TIME_CALLS = {"monotonic", "perf_counter", "time", "process_time"}
_OFF_TIMED_PATH_DECORATOR = "off_timed_path"


def _off_timed_path_spans(tree: ast.AST):
    """Line spans of functions decorated ``@off_timed_path`` — the
    statically-visible 'never called inside a timed region' contract
    (resilience.sentinel.off_timed_path). Sync findings inside them are
    exempt: screening/oracle checks are host round trips by design."""
    spans = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if _terminal_attr(target) == _OFF_TIMED_PATH_DECORATOR:
                spans.append((node.lineno, getattr(node, "end_lineno", node.lineno)))
                break
    return spans


def _loop_is_timed(loop: ast.AST) -> bool:
    for sub in ast.walk(loop):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr in _TIME_CALLS
            and isinstance(sub.func.value, ast.Name)
            and sub.func.value.id == "time"
        ):
            return True
    return False


def _iter_loop_body(loop: ast.AST):
    """Nodes in a loop body, NOT descending into nested function defs
    (a def in a loop body doesn't execute per iteration)."""
    stack = list(loop.body)
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


@register
class HostSyncInHotLoopRule(Rule):
    code = "host-sync-in-hot-loop"

    def applies(self, path: Path) -> bool:
        return _in_hot_loop_scope(path)

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        exempt = _off_timed_path_spans(ctx.tree)
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            timed = _loop_is_timed(loop)
            for node in _iter_loop_body(loop):
                what = self._sync_kind(node, timed)
                if what is None:
                    continue
                if any(a <= node.lineno <= b for a, b in exempt):
                    continue  # @off_timed_path: screening by contract
                out.append(
                    self.finding(
                        ctx, node.lineno,
                        f"{what} inside a {'timed ' if timed else ''}"
                        "for/while body is a host<->device sync per "
                        "iteration — hoist it out of the loop or batch "
                        "the transfer (deliberate sites: "
                        "# noqa: host-sync-in-hot-loop, or mark the whole "
                        "function @off_timed_path when it contractually "
                        "runs between timed regions)",
                        span=(node.lineno, getattr(node, "end_lineno", node.lineno)),
                    )
                )
        return out

    @staticmethod
    def _sync_kind(node: ast.AST, timed: bool) -> Optional[str]:
        if not isinstance(node, ast.Call):
            return None
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr == "item" and not node.args:
                return ".item()"
            if f.attr == "block_until_ready":
                return "block_until_ready"
            if (
                f.attr in ("device_get", "block_until_ready")
                and isinstance(f.value, ast.Name)
                and f.value.id == "jax"
            ):
                return f"jax.{f.attr}"
            if (
                f.attr == "asarray"
                and isinstance(f.value, ast.Name)
                and f.value.id in ("np", "numpy", "onp")
            ):
                return "np.asarray"
        elif isinstance(f, ast.Name) and f.id == "float" and timed and node.args:
            # float() is only a sync when applied to a device value; outside
            # a timed loop the FP rate (str/row parsing) swamps the signal.
            return "float(...)"
        return None


# ---------------------------------------------------------------------------
# span-write-in-timed-region


# Persistence calls of the observability layer: span emission, metric
# observation, tracer/metric journal appends. Each one is an fsync (span
# journal) or a lock acquisition (registry) — file-system latency inside
# the region being measured corrupts the measurement it serves.
_SPAN_WRITE_ATTRS = {"emit", "observe", "inc", "span"}
_TRACERISH = ("tracer", "metric", "registry", "span")


def _receiver_name(func: ast.expr) -> str:
    """Terminal variable name a method call dispatches on: ``tracer`` for
    ``tracer.emit``, ``journal`` for ``self.journal.append``."""
    v = func.value if isinstance(func, ast.Attribute) else None
    while isinstance(v, ast.Attribute):
        if isinstance(v.value, ast.Name) and v.value.id == "self":
            return v.attr
        v = v.value
    return v.id if isinstance(v, ast.Name) else ""


@register
class SpanWriteInTimedRegionRule(Rule):
    """Span/metric persistence inside a TIMED region (a for/while whose
    body reads the clock): ``tracer.emit``/``.span``, ``histogram.
    observe``, ``counter.inc``, or a journal ``append`` on a tracer-owned
    journal. The observability contract is measure-first, persist-after —
    the serving dispatch loop emits its spans from the ``@off_timed_path``
    completion helper, and anything else must too (or carry a reviewed
    ``# noqa: span-write-in-timed-region``)."""

    code = "span-write-in-timed-region"

    def applies(self, path: Path) -> bool:
        return _in_hot_loop_scope(path)

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        exempt = _off_timed_path_spans(ctx.tree)
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            if not _loop_is_timed(loop):
                continue
            for node in _iter_loop_body(loop):
                what = self._write_kind(node)
                if what is None:
                    continue
                if any(a <= node.lineno <= b for a, b in exempt):
                    continue  # @off_timed_path: persistence by contract
                out.append(
                    self.finding(
                        ctx, node.lineno,
                        f"{what} inside a timed region — spans/metrics "
                        "persist with an fsync'd journal append or a lock; "
                        "measure first and persist from an @off_timed_path "
                        "completion helper (Tracer.emit takes explicit "
                        "bounds for exactly this), or # noqa: "
                        "span-write-in-timed-region with a reason",
                        span=(node.lineno, getattr(node, "end_lineno", node.lineno)),
                    )
                )
        return out

    @staticmethod
    def _write_kind(node: ast.AST):
        # with tracer.span(...)/with span(...): the context-manager form.
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                c = item.context_expr
                if isinstance(c, ast.Call):
                    f = c.func
                    if isinstance(f, ast.Name) and f.id in ("span", "obs_span"):
                        return f"{f.id}(...)"
                    if isinstance(f, ast.Attribute) and f.attr == "span":
                        return f"{_receiver_name(f) or '<expr>'}.span(...)"
            return None
        if not isinstance(node, ast.Call) or not isinstance(node.func, ast.Attribute):
            return None
        attr = node.func.attr
        recv = _receiver_name(node.func)
        if attr in ("emit", "observe", "inc"):
            return f"{recv or '<expr>'}.{attr}(...)"
        if attr == "append" and any(t in recv.lower() for t in _TRACERISH):
            return f"{recv}.append(...)"
        return None


# ---------------------------------------------------------------------------
# blocking-socket-call-in-timed-region


# Socket primitives that block on the network. The attribute names are
# distinctive enough to resolve statically without type inference
# (``recv``/``accept``/``sendall``/``getresponse``/``urlopen``); generic
# names (``read``, ``send``, ``request``) stay out — a rule that flags
# queue ``request`` handling cries wolf and gets noqa'd into uselessness.
_SOCKET_BLOCKING_ATTRS = {
    "recv", "recv_into", "recvfrom", "recvmsg", "accept", "connect",
    "sendall", "getresponse", "urlopen",
}


@register
class BlockingSocketInTimedRegionRule(Rule):
    """A blocking socket call inside a TIMED region (a for/while whose
    body reads the clock) in the hot-loop scope: network waits inside the
    region being measured corrupt the measurement AND stall the dispatch
    loop behind a peer's TCP window. The serving front end keeps sockets
    on transport threads — the dispatch loop never touches one — and the
    client fleet's latency loop *deliberately* measures around its socket
    (a reviewed ``# noqa: blocking-socket-call-in-timed-region``). The
    ``@off_timed_path`` exemption applies, same mechanics as
    host-sync-in-hot-loop."""

    code = "blocking-socket-call-in-timed-region"

    def applies(self, path: Path) -> bool:
        return _in_hot_loop_scope(path)

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        exempt = _off_timed_path_spans(ctx.tree)
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            if not _loop_is_timed(loop):
                continue
            for node in _iter_loop_body(loop):
                what = self._socket_kind(node)
                if what is None:
                    continue
                if any(a <= node.lineno <= b for a, b in exempt):
                    continue  # @off_timed_path: transport by contract
                out.append(
                    self.finding(
                        ctx, node.lineno,
                        f"{what} inside a timed region blocks on the "
                        "network while the clock runs — move transport to "
                        "its own thread, hand work through the admission "
                        "queue, or mark the enclosing function "
                        "@off_timed_path when it contractually runs "
                        "between timed regions (a latency-measuring "
                        "client loop carries a reviewed # noqa: "
                        "blocking-socket-call-in-timed-region)",
                        span=(node.lineno, getattr(node, "end_lineno", node.lineno)),
                    )
                )
        return out

    @staticmethod
    def _socket_kind(node: ast.AST) -> Optional[str]:
        if not isinstance(node, ast.Call):
            return None
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _SOCKET_BLOCKING_ATTRS:
            return f"{_receiver_name(f) or '<expr>'}.{f.attr}(...)"
        if isinstance(f, ast.Name) and f.id == "urlopen":
            return "urlopen(...)"
        return None


# ---------------------------------------------------------------------------
# key-reuse


_KEY_CONSUMERS = {
    "normal", "uniform", "randint", "bernoulli", "categorical", "permutation",
    "truncated_normal", "gumbel", "choice", "exponential", "laplace", "bits",
    "shuffle", "poisson", "beta", "gamma", "dirichlet", "rademacher",
}
_KEY_DERIVERS = {"split", "fold_in", "clone"}


def _is_jax_random_call(func: ast.expr) -> tuple:
    """(kind, name) where kind is 'consume'/'derive'/None for
    jax.random.X / random.X / jrandom.X calls."""
    if not isinstance(func, ast.Attribute):
        return (None, "")
    name = func.attr
    v = func.value
    is_random_mod = (
        (isinstance(v, ast.Name) and v.id in ("random", "jrandom", "jr"))
        or (
            isinstance(v, ast.Attribute)
            and v.attr == "random"
            and isinstance(v.value, ast.Name)
            and v.value.id == "jax"
        )
    )
    if not is_random_mod:
        return (None, "")
    if name in _KEY_CONSUMERS:
        return ("consume", name)
    if name in _KEY_DERIVERS:
        return ("derive", name)
    return (None, "")


def _root_name(node: ast.expr) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Call)):
        node = node.func if isinstance(node, ast.Call) else node.value
    return node.id if isinstance(node, ast.Name) else None


def _assigned_names(node: ast.AST) -> Set[str]:
    names: Set[str] = set()
    targets = []
    if isinstance(node, ast.Assign):
        targets = node.targets
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.NamedExpr)):
        targets = [node.target]
    elif isinstance(node, ast.For):
        targets = [node.target]
    for t in targets:
        for sub in ast.walk(t):
            if isinstance(sub, ast.Name):
                names.add(sub.id)
    return names


class _ScopeKeyTracker(ast.NodeVisitor):
    """Linear sweep of ONE function scope (nested defs get their own)."""

    def __init__(self, rule: Rule, ctx: FileContext, scope: ast.AST):
        self.rule = rule
        self.ctx = ctx
        self.scope = scope
        self.findings: List[Finding] = []
        self.consumed: dict = {}  # key text -> first lineno
        self.loop_stack: List[ast.AST] = []

    def _visit_scope_body(self) -> None:
        body = self.scope.body if hasattr(self.scope, "body") else []
        for stmt in body if isinstance(body, list) else [body]:
            self.visit(stmt)

    def visit_FunctionDef(self, node) -> None:  # don't descend: own scope
        pass

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def _handle_rebind(self, node: ast.AST) -> None:
        for name in _assigned_names(node):
            for text in [t for t, r in self._roots.items() if r == name]:
                self.consumed.pop(text, None)

    @property
    def _roots(self) -> dict:
        return getattr(self, "_roots_map", {})

    def _remember_root(self, text: str, root: Optional[str]) -> None:
        if not hasattr(self, "_roots_map"):
            self._roots_map = {}
        self._roots_map[text] = root

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)  # RHS consumption first
        self._handle_rebind(node)

    visit_AugAssign = visit_Assign
    visit_AnnAssign = visit_Assign

    def visit_For(self, node: ast.For) -> None:
        self.visit(node.iter)
        self._handle_rebind(node)  # loop target rebinds each iteration
        self.loop_stack.append(node)
        for stmt in node.body + node.orelse:
            self.visit(stmt)
        self.loop_stack.pop()

    def visit_While(self, node: ast.While) -> None:
        self.visit(node.test)
        self.loop_stack.append(node)
        for stmt in node.body + node.orelse:
            self.visit(stmt)
        self.loop_stack.pop()

    def visit_If(self, node: ast.If) -> None:
        # Branches are mutually exclusive: consuming the same key in the
        # `if` body and the `else` body is NOT reuse. Run each branch from
        # the pre-branch state, then merge (either branch may have consumed
        # a key as far as code after the If is concerned).
        self.visit(node.test)
        before = dict(self.consumed)
        for stmt in node.body:
            self.visit(stmt)
        after_body = self.consumed
        self.consumed = dict(before)
        for stmt in node.orelse:
            self.visit(stmt)
        merged = dict(after_body)
        merged.update(self.consumed)
        self.consumed = merged

    def visit_Call(self, node: ast.Call) -> None:
        kind, name = _is_jax_random_call(node.func)
        if kind == "consume" and node.args:
            key = node.args[0]
            try:
                text = ast.unparse(key)
            except Exception:
                text = ""
            root = _root_name(key)
            if text:
                self._remember_root(text, root)
                prev = self.consumed.get(text)
                if prev is not None:
                    self.findings.append(self._reuse(node, name, text, prev))
                else:
                    self.consumed[text] = node.lineno
                    if self.loop_stack and root is not None:
                        loop = self.loop_stack[-1]
                        rebound = any(
                            root in _assigned_names(sub)
                            for sub in ast.walk(loop)
                        )
                        if not rebound:
                            self.findings.append(
                                self._reuse(node, name, text, node.lineno, loop=True)
                            )
        self.generic_visit(node)

    def _reuse(self, node, fn_name, text, prev, loop=False) -> Finding:
        where = (
            "consumed inside a loop that never splits it"
            if loop
            else f"already consumed at line {prev}"
        )
        return self.rule.finding(
            self.ctx, node.lineno,
            f"PRNG key {text!r} {where}: jax.random.{fn_name} with a reused "
            "key repeats the same randomness (jax.random.split first; "
            "deliberate reuse: # noqa: key-reuse)",
            span=(node.lineno, getattr(node, "end_lineno", node.lineno)),
        )


@register
class KeyReuseRule(Rule):
    code = "key-reuse"

    def applies(self, path: Path) -> bool:
        # Tests reuse fixed keys deliberately (determinism), and so may
        # fixture builders.
        return "tests" not in path.parts

    def check(self, ctx: FileContext) -> List[Finding]:
        out: List[Finding] = []
        scopes: List[ast.AST] = [ctx.tree]
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append(node)
        for scope in scopes:
            tracker = _ScopeKeyTracker(self, ctx, scope)
            tracker._visit_scope_body()
            out.extend(tracker.findings)
        return out


# ---------------------------------------------------------------------------
# jit-in-loop


_TRACED_BUILDERS = {"jit", "shard_map", "pmap", "xmap", "pallas_call"}


@register
class JitInLoopRule(Rule):
    code = "jit-in-loop"

    def applies(self, path: Path) -> bool:
        # Tests retrace per parametrized case by design; the churn there
        # costs test time, not TPU time.
        return "tests" not in path.parts

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in _iter_loop_body(loop):
                if not isinstance(node, ast.Call):
                    continue
                name = _terminal_attr(node.func)
                if name not in _TRACED_BUILDERS:
                    continue
                # Bare-name calls must actually refer to the jax builder
                # (an imported name), not a local helper called `jit`.
                if isinstance(node.func, ast.Name) and name not in ctx.mod.imports:
                    continue
                if isinstance(node.func, ast.Attribute):
                    root = _root_name(node.func)
                    if root not in ("jax", "jit", "shard_map", "pjit", "pl"):
                        continue
                out.append(
                    self.finding(
                        ctx, node.lineno,
                        f"{name}(...) constructed inside a for/while body "
                        "builds a fresh traced callable every iteration "
                        "(full retrace + compile churn) — hoist the "
                        "construction out of the loop (deliberate sites: "
                        "# noqa: jit-in-loop)",
                        span=(node.lineno, getattr(node, "end_lineno", node.lineno)),
                    )
                )
        return out


# ---------------------------------------------------------------------------
# implicit-upcast


# Low-precision dtype names as they appear in astype targets (jnp.bfloat16,
# "bfloat16", np.int8, ...). fp8 spellings included for forward-compat.
_LOW_PRECISION_DTYPES = {
    "bfloat16", "bf16", "float16", "fp16", "int8", "int4",
    "float8_e4m3fn", "float8_e5m2",
}
# Contraction PRIMITIVES whose accumulation dtype preferred_element_type
# pins. Deliberately excludes repo wrappers (conv2d_pallas & co) — those
# state their accumulation internally.
_UPCAST_CONTRACTIONS = {
    "dot", "dot_general", "matmul", "einsum", "tensordot",
    "conv_general_dilated",
}
_UPCAST_ROOTS = {"jnp", "lax", "jax", "np", "numpy"}
_HOT_PATH_DIRS = {"ops", "models", "parallel", "precision"}


def _low_cast_dtype(node: ast.expr) -> Optional[str]:
    """'bfloat16' when node is ``<expr>.astype(<low-precision dtype>)``."""
    if (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "astype"
        and node.args
    ):
        a = node.args[0]
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            return a.value if a.value in _LOW_PRECISION_DTYPES else None
        name = _terminal_attr(a)
        if name in _LOW_PRECISION_DTYPES:
            return name
    return None


@register
class ImplicitUpcastRule(Rule):
    code = "implicit-upcast"

    def applies(self, path: Path) -> bool:
        return bool(_HOT_PATH_DIRS & set(path.parts[:-1]))

    def check(self, ctx: FileContext) -> List[Finding]:
        out: List[Finding] = []
        scopes: List[ast.AST] = [ctx.tree]
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append(node)
        for scope in scopes:
            out.extend(self._check_scope(ctx, scope))
        return out

    @staticmethod
    def _scope_nodes(scope: ast.AST):
        """Nodes of ONE scope's body, not descending into nested function
        defs/lambdas (those are their own scopes with their own casts)."""
        stack = list(scope.body if isinstance(scope.body, list) else [scope.body])
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                stack.extend(ast.iter_child_nodes(node))

    def _check_scope(self, ctx: FileContext, scope: ast.AST) -> List[Finding]:
        # Names bound to a low-precision cast anywhere in THIS scope (flow-
        # insensitive but cast-anchored: only operands traceable to an
        # explicit .astype(bf16/int8/...) are judged — plain arrays whose
        # dtype we cannot know statically stay silent).
        casts: dict = {}
        for sub in self._scope_nodes(scope):
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                dt = _low_cast_dtype(sub.value)
                t = sub.targets[0]
                if dt and isinstance(t, ast.Name):
                    casts[t.id] = dt
        findings: List[Finding] = []
        for sub in self._scope_nodes(scope):
            if not isinstance(sub, ast.Call):
                continue
            name = _terminal_attr(sub.func)
            if name not in _UPCAST_CONTRACTIONS:
                continue
            if isinstance(sub.func, ast.Attribute):
                if _root_name(sub.func) not in _UPCAST_ROOTS:
                    continue
            elif name not in ctx.mod.imports:
                continue  # a bare local helper named `dot` etc.
            if any(kw.arg == "preferred_element_type" for kw in sub.keywords):
                continue
            low = set()
            for arg in sub.args:
                dt = _low_cast_dtype(arg)
                if dt is None and isinstance(arg, ast.Name):
                    dt = casts.get(arg.id)
                if dt:
                    low.add(dt)
            if not low:
                continue
            findings.append(
                self.finding(
                    ctx, sub.lineno,
                    f"{name}(...) contracts over "
                    f"{'/'.join(sorted(low))}-cast operands without an "
                    "explicit preferred_element_type — the accumulation "
                    "dtype is whatever XLA infers (backend-dependent "
                    "numerics); state it "
                    "(preferred_element_type=jnp.float32) or document "
                    "the inference with # noqa: implicit-upcast",
                    span=(sub.lineno, getattr(sub, "end_lineno", sub.lineno)),
                )
            )
        return findings


# ---------------------------------------------------------------------------
# stale-device-set


_DEVICE_QUERIES = {"devices", "local_devices"}
_MESH_BUILDERS = {"Mesh", "make_mesh", "mesh_for"}


def _is_device_query(node: ast.expr) -> bool:
    """``jax.devices()``/``jax.local_devices()``, optionally wrapped in a
    list()/tuple()/sorted() materializer."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Name) and f.id in ("list", "tuple", "sorted") and node.args:
        return _is_device_query(node.args[0])
    return (
        isinstance(f, ast.Attribute)
        and f.attr in _DEVICE_QUERIES
        and _root_name(f) == "jax"
    )


@register
class StaleDeviceSetRule(Rule):
    code = "stale-device-set"

    def check(self, ctx: FileContext) -> List[Finding]:
        # Module-scope names bound to a device query at import time — the
        # cache whose staleness the rule is about. Anything queried inside
        # the consuming function is by definition fresh and never flagged.
        cached: Set[str] = set()
        for stmt in ctx.tree.body:
            if isinstance(stmt, ast.Assign) and _is_device_query(stmt.value):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        cached.add(t.id)
            elif (
                isinstance(stmt, ast.AnnAssign)
                and stmt.value is not None
                and isinstance(stmt.target, ast.Name)
                and _is_device_query(stmt.value)
            ):
                # Annotated spelling of the same cache:
                # ``DEVICES: List[jax.Device] = jax.devices()``.
                cached.add(stmt.target.id)
        if not cached:
            return []
        fn_spans = [
            (f.lineno, getattr(f, "end_lineno", f.lineno))
            for f in ast.walk(ctx.tree)
            if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if _terminal_attr(node.func) not in _MESH_BUILDERS:
                continue
            # Module-scope builds run at import with the list still fresh;
            # only deferred (in-function — i.e. rebuild/retry-path) builds
            # can consume a stale cache.
            if not any(a <= node.lineno <= b for a, b in fn_spans):
                continue
            used = sorted(
                name
                for name in cached
                if any(
                    isinstance(sub, ast.Name) and sub.id == name
                    for arg in list(node.args) + [kw.value for kw in node.keywords]
                    for sub in ast.walk(arg)
                )
            )
            if used:
                out.append(
                    self.finding(
                        ctx, node.lineno,
                        f"mesh built from {'/'.join(used)!r}, a module-cached "
                        "jax.devices() list, inside a function: by "
                        "rebuild/retry time the device set may have shrunk "
                        "and the mesh would still name the lost device — "
                        "re-query jax.devices() at build time (or route "
                        "through parallel.elastic.ElasticPool.alive()); "
                        "deliberate pins: # noqa: stale-device-set",
                        span=(node.lineno, getattr(node, "end_lineno", node.lineno)),
                    )
                )
        return out


# ---------------------------------------------------------------------------
# check-vma-disabled


@register
class CheckVmaDisabledRule(Rule):
    code = "check-vma-disabled"

    def check(self, ctx: FileContext) -> List[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                if (
                    kw.arg == "check_vma"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value is False
                ):
                    out.append(
                        self.finding(
                            ctx, kw.value.lineno,
                            "check_vma=False disables the shard_map "
                            "varying-axes checker for the whole body; use "
                            "ops.vma.kernel_check_vma()/vma-tagged kernel "
                            "out_shapes instead, or document the disable "
                            "with # noqa: check-vma-disabled <reason>",
                            span=(node.lineno, getattr(node, "end_lineno", node.lineno)),
                        )
                    )
        return out
