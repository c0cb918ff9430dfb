"""Compiled span execution engine: route a DP partition to real kernels.

Takes a :class:`~repro.core.partition.PartitionResult` (or a raw boundary
list) and executes the net span-by-span on a batch of images. Engines live
in the deployment registry (``repro.occam.registry``); this module
registers the four built-in ones at import:

* ``pallas`` — the generated N-layer fused-span kernel
  (``repro.kernels.fused_span``): conv/pool spans, any per-layer k /
  stride / same-padding, residual edges (in-span adds, sources crossing
  in from DRAM, spills of partition-crossing sources), multi-row output
  tiles (``out_rows``), batch in the leading grid dimension so filters
  stay VMEM-resident across images (paper Eqn. 6).
* ``scan`` — the jitted row-streaming twin
  (``repro.models.cnn._span_scan_jit``): same schedule and row math as
  the kernel, as a plain ``lax.fori_loop`` (forced-backend / A-B
  reference).
* ``oracle`` — layer-by-layer execution for oversized single layers (the
  DP's lower-bound spans, which by definition exceed on-chip capacity) or
  spans whose schedule fails validation.
* ``interpreted`` — the Python RowRing loop (the executable
  specification); never auto-selected, available as a forced backend.

``plan_routes`` asks ``registry.route_span`` per span — adding a backend
elsewhere (a real-TPU kernel, a continuous-stream body) is a
``register_engine`` call, not an edit here.

Off-chip traffic is accounted per span boundary exactly as
``repro.models.cnn.occam_forward`` does (model == machine: totals equal
``predicted_transfers`` x batch), regardless of which engine ran the span.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import closure
from repro.core.graph import NetSpec
from repro.core.partition import PartitionResult
from repro.kernels.fused_span import ops as span_ops
from repro.models import cnn
from repro.occam import registry

ROUTE_PALLAS = "pallas"
ROUTE_SCAN = "scan"
ROUTE_ORACLE = "oracle"
ROUTE_INTERPRETED = "interpreted"


@dataclasses.dataclass(frozen=True)
class SpanRoute:
    start: int
    end: int
    route: str
    reason: str


def _boundaries_of(partition: PartitionResult | Sequence[int],
                   net: NetSpec) -> list[int]:
    if isinstance(partition, PartitionResult):
        return list(partition.boundaries)
    return list(partition)


def plan_routes(net: NetSpec,
                partition: PartitionResult | Sequence[int], *,
                backend: str = registry.AUTO, out_rows: int = 1,
                dtype: str | None = None) -> tuple[SpanRoute, ...]:
    """Decide per-span engine. Pure function of the net + partition.

    ``backend``: ``"auto"`` (priority dispatch over the registry) or a
    registered engine name to force every span onto it (BackendError if
    any span is ineligible).
    ``out_rows``: requested output tile height (rows per step), clamped
    per span to its output height (a deep net's tail maps are short);
    engines whose schedule cannot retain the closure at that height
    reject.
    ``dtype``: activation dtype name, when known at planning time.
    """
    boundaries = _boundaries_of(partition, net)
    cuts = [0] + boundaries + [net.n_layers]
    fits = {(sp.start, sp.end): sp.fits for sp in partition.spans} \
        if isinstance(partition, PartitionResult) else {}
    routes = []
    for a, b in zip(cuts, cuts[1:]):
        t = max(1, min(out_rows, net.map_shape(b)[0]))
        ctx = registry.RouteContext(fits=fits.get((a, b), True),
                                    out_rows=t, dtype=dtype)
        name, reason = registry.route_span(net, a, b, ctx, backend=backend)
        routes.append(SpanRoute(a, b, name, reason))
    return tuple(routes)


def execute_partition(params: list[dict], xs: jax.Array, net: NetSpec,
                      partition: PartitionResult | Sequence[int], *,
                      counter: cnn.TrafficCounter | None = None,
                      interpret: bool | None = None,
                      routes: tuple[SpanRoute, ...] | None = None,
                      out_rows: int = 1, policy=None) -> jax.Array:
    """Execute ``net`` on ``xs`` ((B, H, W, C) or (H, W, C)) span-by-span.

    ``counter`` accumulates off-chip element transfers (x batch), matching
    ``cnn.predicted_transfers(net, boundaries) * batch``; under a policy
    the byte twins scale by the boundary width.
    ``out_rows``: output tile height per step (Eqn. 6 amortization).
    ``policy``: an ``occam.quant.DtypePolicy`` — every map that crosses a
    span boundary (input, span outputs, spills, residual sources) makes
    the round trip through the policy's boundary dtype before the next
    span reads it, and weights through the weight dtype, so the
    single-device result is bit-identical to a pipeline placement doing
    real quantized transport. Dequant happens at span entry: span bodies
    compute in ``policy.compute`` (a float dtype), which is why int8
    boundaries still route onto the float-only engines.
    """
    if interpret is None:
        interpret = span_ops.default_interpret()
    squeeze = xs.ndim == 3
    if squeeze:
        xs = xs[None]
    batch = xs.shape[0]
    if policy is not None and policy.is_default:
        policy = None
    if policy is None:
        boundary = lambda arr: arr  # noqa: E731
        bpe = 4.0
    else:
        from repro.occam.quant import casting

        params = casting.quantize_params(params, policy)
        boundary = functools.partial(casting.fake_quant,
                                     dtype=policy.boundary,
                                     scale=policy.scale)
        bpe = policy.boundary_bytes
    boundaries = _boundaries_of(partition, net)
    routes = routes or plan_routes(
        net, partition, out_rows=out_rows,
        dtype=policy.compute if policy is not None else str(xs.dtype))
    crossing = [(s, t) for (s, t) in net.residual_edges
                if any(s < p < t for p in boundaries)]
    spill_sources = {s for (s, _t) in crossing}
    stored: dict[int, jax.Array] = {0: boundary(xs)}
    for route in routes:
        a, b = route.start, route.end
        cnn.count_span_reads(counter, net, a, b, batch, bytes_per_elem=bpe)
        spill = tuple(sorted(m for m in spill_sources if a < m < b))
        engine = registry.get_engine(route.route)
        t = max(1, min(out_rows, net.map_shape(b)[0]))  # per-span clamp
        out, spilled = engine.run(params, net, a, b, stored, spill,
                                  interpret=interpret, out_rows=t)
        cnn.count_span_writes(counter, net, b, spilled, batch,
                              bytes_per_elem=bpe)
        stored[b] = boundary(out)
        stored.update({m: boundary(v) for m, v in spilled.items()})
    y = stored[net.n_layers]
    return y[0] if squeeze else y


# --------------------------------------------------------------------------
# Built-in engines: eligibility checks
# --------------------------------------------------------------------------

def _oversized(net: NetSpec, a: int, b: int,
               ctx: registry.RouteContext) -> bool:
    """The DP's lower-bound case: a single layer that exceeds capacity."""
    return not ctx.fits and b - a == 1


# Activation dtypes the generated kernel's row math supports (conv_row
# accumulates in float32; integer activations would silently change ReLU
# and pooling semantics). Declared on the EngineSpec so ``route_span``
# gates on it before ``accepts`` runs — int8 *boundaries* still route
# here because a DtypePolicy's ``compute`` dtype (what the span body
# sees after dequant-at-entry) is always a float.
_PALLAS_DTYPES = ("float32", "bfloat16", "float16")


def _tile_shape_reason(net: NetSpec, a: int, b: int,
                       out_rows: int) -> str | None:
    """Named tile-shape disqualifier for SPAN(a, b) at ``out_rows``, or
    None when the requested tile height is representable."""
    if out_rows < 1:
        return f"tile shape: out_rows={out_rows} (must be >= 1)"
    out_h = net.map_shape(b)[0]
    if out_rows > out_h:
        return (f"tile shape: out_rows={out_rows} exceeds span output "
                f"height {out_h}")
    return None


def _pallas_accepts(net: NetSpec, a: int, b: int,
                    ctx: registry.RouteContext) -> tuple[bool, str]:
    """Kernel eligibility. Rejections name the specific disqualifier —
    the BackendError a forced ``backend="pallas"`` raises carries it."""
    if _oversized(net, a, b, ctx):
        return False, "oversized single layer (lower bound)"
    bad_tile = _tile_shape_reason(net, a, b, ctx.out_rows)
    if bad_tile:
        return False, bad_tile
    # Residual edges are first-class now: in-span targets add from the
    # closure rings (or DRAM operands for sources crossing in), interior
    # sources of partition-crossing edges stream out as spills. The
    # schedule build proves every residual source is still ring-resident
    # when its target row needs it — a proof failure names the edge.
    touched = [(s, t) for (s, t) in net.residual_edges
               if a < t <= b or a < s < b]
    try:
        closure.span_schedule(net, a, b, out_rows=ctx.out_rows)
    except (AssertionError, RuntimeError) as e:
        kind = f"residual edges {touched}: " if touched else ""
        return False, (f"schedule rejected at out_rows={ctx.out_rows}: "
                       f"{kind}{e}")
    if touched:
        return True, f"fused span kernel (residual edges {touched})"
    return True, "fused span kernel"


def _scan_accepts(net: NetSpec, a: int, b: int,
                  ctx: registry.RouteContext) -> tuple[bool, str]:
    if _oversized(net, a, b, ctx):
        return False, "oversized single layer (lower bound)"
    bad_tile = _tile_shape_reason(net, a, b, ctx.out_rows)
    if bad_tile:
        return False, bad_tile
    touched = [(s, t) for (s, t) in net.residual_edges
               if a < t <= b or a < s < b]
    try:
        closure.span_schedule(net, a, b, out_rows=ctx.out_rows)
    except (AssertionError, RuntimeError) as e:
        return False, f"schedule rejected at out_rows={ctx.out_rows}: {e}"
    if touched:
        return True, f"residual edges {touched}"
    return True, "jitted row-streaming scan"


def _always_accepts(reason: str):
    def accepts(net: NetSpec, a: int, b: int,
                ctx: registry.RouteContext) -> tuple[bool, str]:
        if _oversized(net, a, b, ctx):
            return True, "oversized single layer (lower bound)"
        return True, reason
    return accepts


# --------------------------------------------------------------------------
# Built-in engines: span runners
# --------------------------------------------------------------------------

def _span_src_keys(net: NetSpec, a: int, b: int) -> tuple[int, ...]:
    """DRAM-resident residual sources crossing into SPAN(a, b)."""
    return tuple(sorted({s for (s, t) in net.residual_edges
                         if s < a < t <= b}))


def _run_pallas(params, net: NetSpec, a: int, b: int, stored, spill, *,
                interpret: bool, out_rows: int = 1):
    """The fused kernel on one span: residual sources crossing in ride as
    DRAM operands, partition-crossing interior sources spill as extra
    kernel outputs, ``out_rows`` output row-planes per grid step."""
    src_keys = _span_src_keys(net, a, b)
    out = span_ops.span_forward(stored[a], params[a:b], net, a, b,
                                interpret=interpret, out_rows=out_rows,
                                srcs={s: stored[s] for s in src_keys},
                                spill=spill)
    if spill:
        return out  # already (ys, {map -> spilled})
    return out, {}


def _run_scan(params, net: NetSpec, a: int, b: int, stored, spill, *,
              interpret: bool, out_rows: int = 1):
    """Batched jitted row-streaming of one span (vmap over images)."""
    src_keys = _span_src_keys(net, a, b)
    schedule = closure.span_schedule(net, a, b, spill=spill,
                                     out_rows=out_rows)
    fn = functools.partial(cnn._span_scan_jit, net=net, a=a, b=b,
                           schedule=schedule, spill=spill,
                           src_keys=src_keys)
    out, spills = jax.vmap(fn, in_axes=(None, 0, 0))(
        tuple(params[a:b]), stored[a],
        tuple(stored[s] for s in src_keys))
    return out, dict(zip(spill, spills))


def _run_oracle(params, net: NetSpec, a: int, b: int, stored, spill, *,
                interpret: bool, out_rows: int = 1):
    """Layer-by-layer batched execution of one span (+ residual adds)."""
    maps = {a: stored[a]}
    y = stored[a]
    for m in range(a + 1, b + 1):
        layer = net.layers[m - 1]
        if layer.kind == "conv":
            f = lambda im: cnn._conv_window(  # noqa: E731
                cnn._pad_rows_zero(im, layer), params[m - 1]["w"],
                params[m - 1]["b"], layer)
        else:
            f = lambda im: cnn._pool_window(  # noqa: E731
                cnn._pad_rows_neg(im, layer), layer)
        y = jax.vmap(f)(y)
        for (s, t) in net.residual_edges:
            if t != m:
                continue
            src = stored[s] if s < a else maps[s]
            y = y + jax.vmap(
                lambda sm, shape=y.shape[1:]: cnn._project_shortcut(
                    sm, *shape))(src)
        maps[m] = y
    return y, {m: maps[m] for m in spill}


def _run_interpreted(params, net: NetSpec, a: int, b: int, stored, spill, *,
                     interpret: bool, out_rows: int = 1):
    """The Python RowRing loop (executable specification), per image.

    ``out_rows`` is accepted for signature parity and ignored: the oracle
    and the RowRing specification execute whole maps / single rows, so
    tile height changes nothing about their results or their costs."""
    outs, spills = [], {m: [] for m in spill}
    for i in range(stored[a].shape[0]):
        sto_i = {k: v[i] for k, v in stored.items()}
        out, sp = cnn._stream_span(params, net, a, b, sto_i, set(spill))
        outs.append(out)
        for m in spill:
            spills[m].append(sp[m])
    return jnp.stack(outs), {m: jnp.stack(v) for m, v in spills.items()}


# --------------------------------------------------------------------------
# SPMD pipeline stage bodies (shard_map-traceable span cores)
# --------------------------------------------------------------------------

def _pallas_spmd_body(net: NetSpec, a: int, b: int, spill, src_keys, *,
                      out_rows: int = 1):
    """Stage-body builder for the pallas engine: the fused span kernel as
    a shard_map-traceable pipeline stage core.

    Interpret mode is decided once at build time exactly as
    ``execute_partition`` decides it (pure-Python kernel evaluation off
    TPU — it traces fine under shard_map; the compiled kernel on real
    TPUs). The schedule is built (and ring-retention validated) here, at
    pipeline build time, and baked into the jit cache key."""
    interpret = span_ops.default_interpret()

    def body(span_params, x, srcs):
        out, spilled = span_ops.span_pallas_call(
            x, list(span_params), net, a, b, interpret=interpret,
            out_rows=out_rows, srcs=dict(zip(src_keys, srcs)), spill=spill)
        return out, spilled

    return body


def _scan_spmd_body(net: NetSpec, a: int, b: int, spill, src_keys, *,
                    out_rows: int = 1):
    """Stage-body builder for the scan engine: the same row-streaming math
    as ``_run_scan``, with the static span schedule precomputed once at
    pipeline build time."""
    schedule = closure.span_schedule(net, a, b, spill=spill,
                                     out_rows=out_rows)
    fn = functools.partial(cnn._span_scan_jit, net=net, a=a, b=b,
                           schedule=schedule, spill=spill,
                           src_keys=src_keys)

    def body(span_params, x, srcs):
        out, spills = jax.vmap(fn, in_axes=(None, 0, 0))(
            tuple(span_params), x, srcs)
        return out, dict(zip(spill, spills))

    return body


def _oracle_spmd_body(net: NetSpec, a: int, b: int, spill, src_keys, *,
                      out_rows: int = 1):
    """Stage-body builder for the oracle engine (lower-bound spans)."""
    def body(span_params, x, srcs):
        stored = {a: x, **dict(zip(src_keys, srcs))}
        full = [{}] * a + list(span_params)
        return _run_oracle(full, net, a, b, stored, spill, interpret=False)

    return body


# Auto-dispatch order: kernel > compiled scan > oracle. The interpreted
# specification never wins auto (the oracle accepts everything first) but
# is a valid forced backend. spmd_capable marks the engines whose bodies
# trace under shard_map: pallas/scan/oracle all register a make_spmd_body
# (the pallas body runs the fused kernel — interpret-mode off TPU, the
# compiled kernel on real TPUs — so kernel-routed spans drive pipeline
# stages directly, no scan substitution); only the interpreted Python
# loop cannot trace and stays off pipelines.
registry.register_engine(
    ROUTE_PALLAS, priority=10, accepts=_pallas_accepts, run=_run_pallas,
    spmd_capable=True, make_spmd_body=_pallas_spmd_body,
    dtypes=_PALLAS_DTYPES,
    description="generated N-layer fused-span Pallas kernel")
registry.register_engine(
    ROUTE_SCAN, priority=20, accepts=_scan_accepts, run=_run_scan,
    spmd_capable=True, make_spmd_body=_scan_spmd_body,
    dtypes=_PALLAS_DTYPES,
    description="jitted row-streaming scan (residual-capable)")
registry.register_engine(
    ROUTE_ORACLE, priority=30, accepts=_always_accepts(
        "layer-by-layer fallback"), run=_run_oracle,
    spmd_capable=True, make_spmd_body=_oracle_spmd_body,
    description="layer-by-layer oracle (lower-bound spans)")
registry.register_engine(
    ROUTE_INTERPRETED, priority=100, accepts=_always_accepts(
        "interpreted RowRing specification"), run=_run_interpreted,
    description="Python RowRing loop (executable specification)")


