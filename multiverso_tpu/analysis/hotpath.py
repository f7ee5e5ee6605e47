"""JAX hot-path rules: host syncs, retraces, donation, host/device mixups.

"Exploring the limits of Concurrency in ML Training on Google TPUs"
(PAPERS.md) measures host-side stalls and retraces dominating TPU step
time; every rule here statically rejects one mechanism of that tax:

* ``implicit-host-sync``   — ``float()``/``.item()``/``np.asarray()`` on a
  traced value blocks dispatch until the device flushes;
* ``block-until-ready-in-loop`` — a sync inside a host loop serializes
  the pipelined dispatch window the async engines exist to keep full;
* ``retrace-hazard``       — constructing a jit/shard_map/pallas_call
  inside a loop recompiles (and re-caches) per iteration;
* ``missing-donation``     — an update step jitted without donation holds
  two copies of every table in HBM and forces a copy per step;
* ``host-jnp-in-loop``     — jnp scalar/array constructors on host
  control paths create a device round trip where numpy was meant;
* ``span-in-traced-fn``    — telemetry ``span()``/``observe()`` inside a
  traced body fires at TRACE time, not run time: the metric silently
  stops measuring after the first compilation.
"""

from __future__ import annotations

import ast
from typing import Iterator, Set

from multiverso_tpu.analysis import astutil
from multiverso_tpu.analysis.core import (FileContext, Finding, Rule,
                                          register)

_JIT_NAMES = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}
_TRANSFORM_IN_LOOP = _JIT_NAMES | {
    "jax.shard_map", "jax.experimental.shard_map.shard_map",
    "jax.experimental.pallas.pallas_call",
    "jax.vmap", "jax.grad", "jax.value_and_grad",
}
_NP_SYNC_CALLS = {"numpy.asarray", "numpy.array"}
_CAST_BUILTINS = {"float", "int", "bool", "complex"}
_SYNC_METHODS = {"item", "tolist", "__array__"}
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "aval", "sharding"}

# Scalar boxing / constant allocation per iteration is pure waste on a
# host path; asarray/array are NOT here — per-batch uploads in a host
# training loop are the intended device boundary.
_JNP_HOST_CONSTRUCTORS = {
    "float32", "float64", "float16", "bfloat16", "int8", "int16", "int32",
    "int64", "uint32", "uint64", "zeros", "ones", "full", "arange",
}


_STATIC_HOST_FUNCS = {"len", "abs", "min", "max", "round", "int",
                      "float", "bool", "sum", "sorted", "tuple", "list"}
_STATIC_HOST_MODULES = ("numpy.", "math.", "builtins.")


def _is_static_expr(node: ast.expr, aliases, depth: int = 0) -> bool:
    """Conservatively true when the expression is COMPOSED ENTIRELY of
    trace-time-static atoms: literals, shape/dtype attribute chains,
    len(), pure host math (numpy/math) over static operands, or a local
    name every one of whose assignments in the enclosing function is
    itself static (one step of dataflow — catches ``scale =
    1/np.sqrt(q.shape[-1]); float(scale)``).  Casting those to a Python
    scalar inside a traced function is fine and idiomatic; an expression
    merely CONTAINING a static atom (``x.sum() / x.shape[0]``) is not."""
    def static(sub: ast.expr) -> bool:
        return _is_static_expr(sub, aliases, depth)

    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute):
        return node.attr in _STATIC_ATTRS
    if isinstance(node, ast.Subscript):
        return static(node.value)       # x.shape[0]
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(static(e) for e in node.elts)
    if isinstance(node, ast.BinOp):
        return static(node.left) and static(node.right)
    if isinstance(node, ast.UnaryOp):
        return static(node.operand)
    if isinstance(node, ast.Compare):
        return static(node.left) and all(static(c)
                                         for c in node.comparators)
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id == "len":
            return True                 # len(traced) is a static int
        resolved = astutil.resolve_name(fn, aliases) or ""
        pure_host = (
            (isinstance(fn, ast.Name) and fn.id in _STATIC_HOST_FUNCS)
            or resolved.startswith(_STATIC_HOST_MODULES))
        return pure_host and node.args and \
            all(static(a) for a in node.args)
    if isinstance(node, ast.Name) and depth < 2:
        fn = astutil.enclosing_function(node)
        assigns = []
        while fn is not None:
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Assign) and \
                        astutil.enclosing_function(sub) is fn and \
                        any(isinstance(t, ast.Name) and t.id == node.id
                            for t in sub.targets):
                    assigns.append(sub.value)
            fn = astutil.enclosing_function(fn)
        if assigns and all(_is_static_expr(v, aliases, depth + 1)
                           for v in assigns):
            return True
    return False


@register
class ImplicitHostSync(Rule):
    id = "implicit-host-sync"
    severity = "error"
    rationale = (
        "float()/int()/bool()/np.asarray()/.item() on a traced value "
        "inside a jitted/shard_mapped/lax-loop body either raises a "
        "TracerError at trace time or — on values captured from outside "
        "the trace — silently blocks the host on the device queue. "
        "Pull scalars out with jnp ops, or sync once outside the step.")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ctx.walk():
            if not isinstance(node, ast.Call):
                continue
            if not astutil.is_traced_context(node, ctx.traced):
                continue
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id in _CAST_BUILTINS \
                    and fn.id not in ctx.aliases:
                if len(node.args) == 1 and \
                        not _is_static_expr(node.args[0], ctx.aliases):
                    yield self.finding(
                        ctx, node,
                        f"builtin {fn.id}() on a (potentially traced) "
                        "value inside a traced function forces a "
                        "device->host sync or a TracerError")
                continue
            if isinstance(fn, ast.Attribute) and fn.attr in _SYNC_METHODS \
                    and not node.args:
                yield self.finding(
                    ctx, node,
                    f".{fn.attr}() inside a traced function pulls the "
                    "value to the host")
                continue
            name = astutil.resolve_name(fn, ctx.aliases)
            if name in _NP_SYNC_CALLS and node.args and \
                    not all(_is_static_expr(a, ctx.aliases)
                            for a in node.args):
                yield self.finding(
                    ctx, node,
                    f"{name}() materializes its operand on the host; "
                    "use jnp inside traced code")


@register
class BlockUntilReadyInLoop(Rule):
    id = "block-until-ready-in-loop"
    severity = "warning"
    rationale = (
        "A per-iteration block_until_ready() in a host loop caps "
        "throughput at one dispatch per round trip — exactly the stall "
        "the depth-N dispatch queue (W2V pipelined_host) exists to hide. "
        "Sync once per block, or bound the in-flight window instead.")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if ctx.role == "script":
            # bench/CLI scripts sync deliberately: timing loops measure
            # through block_until_ready by design.
            return
        for node in ctx.walk():
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            is_sync = (isinstance(fn, ast.Attribute) and
                       fn.attr == "block_until_ready") or \
                astutil.resolve_name(fn, ctx.aliases) == \
                "jax.block_until_ready"
            if not is_sync:
                continue
            if astutil.is_traced_context(node, ctx.traced):
                continue
            if astutil.in_host_loop(node) is not None:
                yield self.finding(
                    ctx, node,
                    "block_until_ready() inside a host loop serializes "
                    "dispatch; hoist the sync or bound in-flight depth")


@register
class RetraceHazard(Rule):
    id = "retrace-hazard"
    severity = "error"
    rationale = (
        "jax.jit/shard_map/pallas_call construction inside a loop builds "
        "a fresh transform (and usually a fresh closure) every "
        "iteration: each call retraces, recompiles, and grows the jit "
        "cache without bound. Build the transform once outside the loop "
        "and close over nothing that changes per iteration.")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ctx.walk():
            if not isinstance(node, ast.Call):
                continue
            name = astutil.resolve_name(node.func, ctx.aliases)
            hit = name in _TRANSFORM_IN_LOOP or (
                name == "functools.partial" and node.args and
                astutil.resolve_name(node.args[0].func
                                     if isinstance(node.args[0], ast.Call)
                                     else node.args[0],
                                     ctx.aliases) in _TRANSFORM_IN_LOOP)
            if not hit:
                continue
            loop = astutil.in_host_loop(node)
            if loop is not None:
                yield self.finding(
                    ctx, node,
                    f"{name}(...) constructed inside a "
                    f"{'for' if isinstance(loop, ast.For) else 'while'} "
                    "loop retraces/recompiles every iteration — hoist "
                    "the transform out of the loop")


@register
class MissingDonation(Rule):
    id = "missing-donation"
    severity = "warning"
    rationale = (
        "An update/step function jitted without donate_argnums keeps the "
        "old table buffers alive across the call: 2x HBM for every "
        "table plus a copy per step. The fused steps donate all four "
        "word2vec tables; new step jits must do the same.")

    _STEP_RE = ("step", "update")

    def _looks_like_step(self, arg: ast.expr) -> bool:
        name = None
        if isinstance(arg, ast.Name):
            name = arg.id
        elif isinstance(arg, ast.Attribute):
            name = arg.attr
        elif isinstance(arg, ast.Call):
            # jit(make_step(...)) — builder names carry the signal too
            return self._looks_like_step(arg.func)
        if name is None:
            return False
        low = name.lower()
        return any(tok in low for tok in self._STEP_RE)

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ctx.walk():
            if not isinstance(node, ast.Call):
                continue
            name = astutil.resolve_name(node.func, ctx.aliases)
            if name not in _JIT_NAMES or not node.args:
                continue
            kwargs = {k.arg for k in node.keywords}
            if {"donate_argnums", "donate_argnames"} & kwargs:
                continue
            if self._looks_like_step(node.args[0]):
                yield self.finding(
                    ctx, node,
                    "jit of an update/step function without "
                    "donate_argnums: table buffers are copied instead "
                    "of reused (2x HBM + a copy per step)")


@register
class HostJnpInLoop(Rule):
    id = "host-jnp-in-loop"
    severity = "warning"
    rationale = (
        "jnp scalar/array constructors on a host control path allocate "
        "on-device and round-trip per loop iteration; host bookkeeping "
        "(counters, accumulators, staging) should be numpy/Python until "
        "the single upload at dispatch.")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if ctx.role == "script":
            return
        for node in ctx.walk():
            if not isinstance(node, ast.Call):
                continue
            name = astutil.resolve_name(node.func, ctx.aliases)
            if not name or not name.startswith("jax.numpy."):
                continue
            if name.rsplit(".", 1)[1] not in _JNP_HOST_CONSTRUCTORS:
                continue
            if astutil.is_traced_context(node, ctx.traced):
                continue
            if astutil.in_host_loop(node) is not None:
                yield self.finding(
                    ctx, node,
                    f"{name}() inside a host loop allocates on-device "
                    "per iteration — keep host-side state in numpy and "
                    "upload once")


# Telemetry call targets whose execution inside a traced body is a silent
# no-op after the first compilation (they run at TRACE time only).
_TELEMETRY_SPAN_FNS = {
    "multiverso_tpu.telemetry.span",
    "multiverso_tpu.telemetry.spans.span",
    "multiverso_tpu.telemetry.emit_span",
    "multiverso_tpu.telemetry.spans.emit_span",
}
_TELEMETRY_METRIC_FACTORIES = {
    "multiverso_tpu.telemetry.histogram",
    "multiverso_tpu.telemetry.metrics.histogram",
    "multiverso_tpu.telemetry.counter",
    "multiverso_tpu.telemetry.metrics.counter",
    "multiverso_tpu.telemetry.gauge",
    "multiverso_tpu.telemetry.metrics.gauge",
}
_METRIC_METHODS = {"observe", "inc", "set"}


@register
class SpanInTracedFn(Rule):
    id = "span-in-traced-fn"
    severity = "error"
    rationale = (
        "telemetry span()/emit_span() and histogram observe() (counter "
        "inc(), gauge set()) calls lexically inside a jit/shard_map-"
        "traced function body execute at TRACE time only: after the "
        "first compilation the metric never updates again — a silent "
        "observability no-op that reads as 'this path is never slow'. "
        "Time the traced call from the HOST side (wrap the call site, "
        "not the body), or use jax.profiler annotations for device "
        "regions.")

    def _metric_receivers(self, ctx: FileContext) -> Set[str]:
        """Names assigned from a telemetry metric factory anywhere in
        the file (module attrs and locals alike): ``h = histogram(..)``
        then ``h.observe(..)`` inside a traced body still fires."""
        names: Set[str] = set()
        for node in ctx.walk():
            if not isinstance(node, ast.Assign) or \
                    not isinstance(node.value, ast.Call):
                continue
            if astutil.resolve_name(node.value.func, ctx.aliases) \
                    not in _TELEMETRY_METRIC_FACTORIES:
                continue
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                elif isinstance(t, ast.Attribute):
                    names.add(t.attr)
        return names

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        receivers = self._metric_receivers(ctx)
        for node in ctx.walk():
            if not isinstance(node, ast.Call):
                continue
            if not astutil.is_traced_context(node, ctx.traced):
                continue
            name = astutil.resolve_name(node.func, ctx.aliases)
            if name in _TELEMETRY_SPAN_FNS:
                yield self.finding(
                    ctx, node,
                    f"{name.rsplit('.', 1)[1]}() inside a traced "
                    "function body fires at trace time, not run time — "
                    "the span records exactly once, at compilation")
                continue
            fn = node.func
            if not isinstance(fn, ast.Attribute) or \
                    fn.attr not in _METRIC_METHODS:
                continue
            recv = fn.value
            direct = isinstance(recv, ast.Call) and \
                astutil.resolve_name(recv.func, ctx.aliases) \
                in _TELEMETRY_METRIC_FACTORIES
            named = (isinstance(recv, ast.Name) and recv.id in receivers) \
                or (isinstance(recv, ast.Attribute)
                    and recv.attr in receivers)
            if direct or named:
                yield self.finding(
                    ctx, node,
                    f".{fn.attr}() on a telemetry metric inside a "
                    "traced function body fires at trace time, not run "
                    "time — the metric stops updating after the first "
                    "compilation")
