"""Occam N-layer fused-span Pallas kernel generator: a DP-chosen span of
conv/pool layers streamed row-by-row with the dependence closure in VMEM.

This is the paper's contribution C1+C2 as a *generated* TPU kernel — given
any span ``(a, b)`` of a :class:`~repro.core.graph.NetSpec` (conv and
maxpool, any per-layer k / stride >= 1 / same-padding, residual edges
included) it emits one ``pallas_call``:

* Necessary condition (C1): the tile is one full input **row-plane block**
  (``in_rows`` x W x C_in) per grid step — the BlockSpec shape. Nothing
  narrower enters VMEM; nothing is ever re-read from HBM (contrast Layer
  Fusion's square tiles, which re-fetch/recompute halos).
* Sufficient condition (C2): one circular row buffer per map
  ``L_a .. L_{b-1}``, sized by ``closure.span_row_counts`` — the exact
  dependence closure — lives in VMEM scratch, persisting across the
  *sequential* TPU grid. Software-managed VMEM makes the closure an
  allocation, not a cache-hit hope (the paper's GPU pain).
* Cross-image filter reuse (Eqn. 6): the grid's **leading dimension is the
  batch**; filters are whole-array VMEM blocks with a constant index map,
  so they are fetched once and stay chip-resident across all images.
* Multi-row tiles (Eqn. 6 amortization): ``out_rows`` output row-planes per
  step — the output BlockSpec is an ``out_rows``-row block and the ring
  advance/arrival widen to match, amortizing ring shifts and weight
  re-touch across the tile height (the paper's Table II ``TileDim``).
* Residual spans: in-span residual sources are read back from the closure
  rings (``span_schedule`` proves they are still resident); sources
  crossing into the span from an earlier partition arrive as extra DRAM
  operands; interior sources of partition-crossing edges stream out as
  extra kernel outputs (``spill``).

Scheduling: the per-step work (which rows of which interior maps become
computable as input blocks arrive) is precomputed by
``closure.span_schedule`` — demand-driven and replay-validated against ring
retention — then shipped to the kernel as scalar-prefetch tables
(``PrefetchScalarGridSpec``). The kernel body is a static nest over maps
and slots; each slot reads its scheduled row index from SMEM and is
``pl.when``-guarded. The input/output BlockSpec index maps also read the
schedule, streaming exactly one input block in and one ``out_rows``-row
output block out per step.

Space-to-depth input (``span_input_rewrite``): where a span opens with a
stride-s conv whose input has few channels (s*s*C_in <= 128, one lane
tile) and only that conv reads the input map, ``span_pallas_call`` folds
each s x s pixel block of the input into channels and runs the kernel on
the equivalent stride-1 conv with a ceil(k/s)-wide filter, zero-padded
and regrouped to match (AlexNet's 11x11 stride-4 stem over 3 channels
becomes a 3x3 conv over 48). The fold runs as XLA ops ahead of the
kernel, so the strided stem needs no column-phase selection matmuls, its
taps read K = s*s*C_in instead of C_in, and the input ring no longer
pads 3 channels to 128 lanes. Strided convs inside a span read maps
the kernel itself produces row by row, so they keep the column phases.

Strided pools pick their columns once: ``rowops.pool_row`` takes the
window max at every column first (contiguous maxima on the vector
units), then one one-hot selection per output row. ``COLUMN_SELECTS``
records how many selections each lowered span makes per image.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro.core import closure
from repro.core.graph import LayerSpec, NetSpec, chain

from .rowops import (NEG_INF, column_selects, conv_row, pool_row,
                     project_row, ring_window)


# (net name, a, b, interpret) of every span kernel this process has
# lowered, appended at trace time: evidence of which mode really ran.
LOWERINGS: list[tuple[str, int, int, bool]] = []

# (net name, a, b, stride, folded channels) of every span lowered through
# the space-to-depth input rewrite, appended at trace time.
INPUT_REWRITES: list[tuple[str, int, int, int, int]] = []

# (net name, a, b, selection matmuls per image) of every span kernel
# lowered, appended at trace time: ``rowops.column_selects`` of the span
# the kernel runs, under the published net and span.
COLUMN_SELECTS: list[tuple[str, int, int, int]] = []

# Left over the kernel's own buffers for the values its body keeps live
# (windows, column phases, accumulators): the compiler's default scoped
# VMEM budget, which every kernel got before it asked for a limit.
_VMEM_HEADROOM_BYTES = 16 * 1024 * 1024


def _tiled_bytes(shape: tuple[int, ...], itemsize: int) -> int:
    """VMEM bytes of a buffer under the TPU's tiled layout: the two minor
    dims pad to (sublanes, 128 lanes) tiles, 8 sublanes of 32 bits. A
    3-channel row therefore costs as much as a 128-channel one."""
    sub = 8 * max(1, 4 // itemsize)
    *lead, rows, cols = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    return (math.prod(lead) * (-(-rows // sub) * sub)
            * (-(-cols // 128) * 128) * itemsize)


def _span_kernel(sched_ref, outrow_ref, inrow_ref, x_ref, *refs,
                 net: NetSpec, a: int, b: int,
                 schedule: closure.SpanSchedule, n_src: int, n_wb: int,
                 src_keys: tuple[int, ...], spill: tuple[int, ...]):
    del outrow_ref  # consumed by the output BlockSpec index map
    src_refs = refs[:n_src]
    wb_refs = refs[n_src:n_src + n_wb]
    out_ref = refs[n_src + n_wb]
    spill_refs = refs[n_src + n_wb + 1:n_src + n_wb + 1 + len(spill)]
    rings = refs[n_src + n_wb + 1 + len(spill):]
    caps, h = schedule.ring_caps, schedule.heights
    in_rows, out_rows = schedule.in_rows, schedule.out_rows
    n_maps = len(h)
    i = pl.program_id(1)

    # --- arrival: the step's input block joins the closure ring -----------
    # inrow_ref holds the last-arrived block per step; a step is a fresh
    # arrival iff its entry exceeds the previous step's (monotone table).
    blk = inrow_ref[i]
    fresh = jnp.logical_or(i == 0, blk > inrow_ref[jnp.maximum(i - 1, 0)])
    for ii in range(in_rows):
        g = blk * in_rows + ii

        @pl.when(jnp.logical_and(fresh, g < h[0]))
        def _store_input(g=g, ii=ii):
            rings[0][(g % caps[0]).astype(jnp.int32)] = x_ref[0, ii]

    # --- scheduled production: maps a+1 .. b in dependency order ----------
    # A map's slots run as one loop over its slot-table columns, so the
    # body is emitted once per map rather than once per slot.
    slot = 0
    wb_idx = 0
    for off in range(1, n_maps):
        m = a + off
        layer = net.layers[m - 1]
        w_m, c_m = net.map_shape(m)[1], net.map_shape(m)[2]
        if layer.kind == "conv":
            w_ref, b_ref = wb_refs[wb_idx], wb_refs[wb_idx + 1]
            wb_idx += 2
        else:
            w_ref = b_ref = None

        def produce(j, carry, slot0=slot, off=off, m=m, layer=layer,
                    w_m=w_m, c_m=c_m, w_ref=w_ref, b_ref=b_ref):
            r = sched_ref[i, slot0 + j]

            @pl.when(r >= 0)
            def _produce():
                pad_val = 0.0 if layer.kind == "conv" else NEG_INF
                win = ring_window(rings[off - 1], r, layer.k, layer.stride,
                                  layer.padding, h[off - 1], caps[off - 1],
                                  pad_val)
                if layer.kind == "conv":
                    row = conv_row(win, w_ref[...], b_ref[...], layer.stride,
                                   layer.padding, layer.out_w)
                else:
                    row = pool_row(win, layer.k, layer.stride, layer.padding,
                                   layer.out_w)
                # residual edges terminating at map m: sources are either
                # still ring-resident (schedule-proven) or DRAM operands
                for (s, tt) in net.residual_edges:
                    if tt != m:
                        continue
                    h_s = net.map_shape(s)[0]
                    sh = max(h_s // h[off], 1)
                    src_abs = jnp.minimum(r * sh, h_s - 1)
                    if s < a:
                        src_row = src_refs[src_keys.index(s)][0, src_abs]
                    else:
                        src_row = rings[s - a][
                            (src_abs % caps[s - a]).astype(jnp.int32)]
                    row = row + project_row(src_row.astype(jnp.float32),
                                            w_m, c_m)
                if off < n_maps - 1:
                    rings[off][(r % caps[off]).astype(jnp.int32)] = \
                        row.astype(rings[off].dtype)
                else:
                    out_ref[0, (r % out_rows).astype(jnp.int32)] = \
                        row.astype(out_ref.dtype)
                if m in spill:
                    sref = spill_refs[spill.index(m)]
                    sref[0, r] = row.astype(sref.dtype)

            return carry

        n_slots = schedule.slots[off - 1]
        if n_slots == 1:
            produce(0, 0)
        else:
            lax.fori_loop(0, n_slots, produce, 0)
        slot += n_slots


@functools.partial(jax.jit,
                   static_argnames=("net", "a", "b", "schedule", "spill",
                                    "src_keys", "interpret", "rewrite"))
def _span_pallas(xs: jax.Array, wb: tuple[jax.Array, ...],
                 srcs: tuple[jax.Array, ...], *, net: NetSpec,
                 a: int, b: int, schedule: closure.SpanSchedule,
                 spill: tuple[int, ...], src_keys: tuple[int, ...],
                 interpret: bool,
                 rewrite: tuple[str, int, int, int, int] | None = None):
    """``rewrite``: the ``INPUT_REWRITES`` entry when ``net`` is the
    space-to-depth form of a published span; records and the kernel's
    name then speak of the published net and span."""
    from jax.experimental.pallas import tpu as pltpu

    name, pa, pb = rewrite[:3] if rewrite else (net.name, a, b)
    LOWERINGS.append((name, pa, pb, interpret))
    COLUMN_SELECTS.append((name, pa, pb, column_selects(net, a, b)))
    if rewrite:
        INPUT_REWRITES.append(rewrite)
    batch = xs.shape[0]
    n_maps = b - a + 1
    h_b, w_b, c_b = net.map_shape(b)
    in_rows, out_rows = schedule.in_rows, schedule.out_rows
    sched_tab = jnp.asarray(np.asarray(schedule.slot_table(), np.int32))
    outrow_tab = jnp.asarray(np.asarray(schedule.out_row_table(), np.int32))
    inrow_tab = jnp.asarray(np.asarray(schedule.in_row_table(), np.int32))

    # pad the input to whole arrival blocks so every step's block load is
    # in-bounds (padding rows are never stored: the g < h[0] guard)
    h_a = net.map_shape(a)[0]
    n_blocks = -(-h_a // in_rows)
    if n_blocks * in_rows != h_a:
        xs = jnp.pad(xs, ((0, 0), (0, n_blocks * in_rows - h_a),
                          (0, 0), (0, 0)))

    in_specs = [
        # one full input row-plane block per step — the C1 tile shape
        pl.BlockSpec((1, in_rows) + net.map_shape(a)[1:],
                     lambda n, i, s, o, ir: (n, ir[i], 0, 0)),
    ]
    # DRAM-resident residual sources crossing into the span: whole maps,
    # one per image (constant over the step dimension)
    for s in src_keys:
        in_specs.append(pl.BlockSpec(
            (1,) + net.map_shape(s),
            lambda n, i, ss, o, ir: (n, 0, 0, 0)))
    # chip-resident filters: whole arrays, constant index map -> fetched
    # once, shared across the whole batch grid dimension (Eqn. 6). One
    # buffer each: a block that is never re-fetched needs no second one.
    for arr in wb:
        in_specs.append(pl.BlockSpec(
            arr.shape, lambda n, i, s, o, ir, nd=arr.ndim: (0,) * nd,
            pipeline_mode=pl.Buffered(1)))

    out_specs = [
        # out_rows-row output block per producing step (Eqn. 6 tile)
        pl.BlockSpec((1, out_rows, w_b, c_b),
                     lambda n, i, s, o, ir: (n, o[i], 0, 0)),
    ]
    out_shapes = [jax.ShapeDtypeStruct((batch, h_b, w_b, c_b), xs.dtype)]
    for m in spill:
        # spilled interior maps stream out whole (revisited block per
        # image; every row is written before the image's steps finish)
        out_specs.append(pl.BlockSpec(
            (1,) + net.map_shape(m),
            lambda n, i, s, o, ir: (n, 0, 0, 0)))
        out_shapes.append(
            jax.ShapeDtypeStruct((batch,) + net.map_shape(m), xs.dtype))

    ring_shapes = [(schedule.ring_caps[off],) + net.map_shape(a + off)[1:]
                   for off in range(n_maps - 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch, schedule.n_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM(shape, xs.dtype) for shape in ring_shapes],
    )
    itemsize = jnp.dtype(xs.dtype).itemsize
    streamed = [spec.block_shape for spec in in_specs[:1 + len(srcs)]]
    streamed += [spec.block_shape for spec in out_specs]
    vmem_bytes = (
        2 * sum(_tiled_bytes(shape, itemsize) for shape in streamed)
        + sum(_tiled_bytes(arr.shape, jnp.dtype(arr.dtype).itemsize)
              for arr in wb)
        + sum(_tiled_bytes(shape, itemsize) for shape in ring_shapes))
    kernel = functools.partial(_span_kernel, net=net, a=a, b=b,
                               schedule=schedule, n_src=len(srcs),
                               n_wb=len(wb), src_keys=src_keys, spill=spill)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes + _VMEM_HEADROOM_BYTES),
        interpret=interpret,
        # the name its calls carry in a profile; every span kernel's
        # contains "_span_pallas", which the benchmark matches on
        name=f"_span_pallas_{pa}_{pb}",
    )(sched_tab, outrow_tab, inrow_tab, xs, *srcs, *wb)
    return outs[0], tuple(outs[1:])


def span_input_rewrite(net: NetSpec, a: int, b: int
                       ) -> tuple[NetSpec, int, int, int]:
    """``(net', a', b', s)``: the net and span the kernel runs for SPAN(a, b)
    of ``net``, and the stride ``s`` its input is folded by.

    The rewrite applies where the span's first layer is a conv of stride
    s > 1 with s*s*C_in <= 128 (the folded channels fit one lane tile, so
    K costs no extra MXU pass), and no layer but that conv reads map ``a``
    (it is no residual source; a span spills only interior maps) and no
    residual edge enters the span from before it. ``net'`` is then the span alone, map ``a`` becoming map 0:
    its first layer a ceil(k/s)-wide stride-1 conv over the
    (out + ceil(k/s) - 1)-square, s*s*C_in-channel space-to-depth input,
    the other layers and in-span residual edges as published. Elsewhere
    it returns ``(net, a, b, 1)``.
    """
    first = net.layers[a]
    s = first.stride
    if (first.kind != "conv" or s < 2 or s * s * first.in_ch > 128
            or any(src == a or src < a < t <= b
                   for src, t in net.residual_edges)):
        return net, a, b, 1
    kq = -(-first.k // s)
    specs = [("conv", kq, 1, 0, first.out_ch)] + [
        (layer.kind, layer.k, layer.stride, layer.padding, layer.out_ch)
        for layer in net.layers[a + 1:b]]
    edges = tuple((src - a, t - a) for src, t in net.residual_edges
                  if a <= src and t <= b)
    folded = chain(f"{net.name}.s2d", specs,
                   in_h=first.out_h + kq - 1, in_w=first.out_w + kq - 1,
                   in_ch=s * s * first.in_ch, residual_edges=edges)
    return folded, 0, b - a, s


def _space_to_depth(xs: jax.Array, layer: LayerSpec, s: int) -> jax.Array:
    """(B, H, W, C) -> (B, H', W', s*s*C): the conv's zero padding, then
    pad or crop at the bottom and right to whole s-blocks of the rows and
    columns the stride-1 form reads (a crop drops only rows and columns
    the published conv never reads), each s x s block folded into
    channels in (row phase, column phase, channel) order.

    The fold is a stride-s conv with a one-hot filter at ``HIGHEST``
    precision, exact as ``rowops.select_cols`` is: XLA lays a 3-channel
    map out for the TPU's convolution unit in fewer passes than it does
    a reshape and transpose (0.75 against 1.00 ms a round of AlexNet's
    whole-body span on a v5e)."""
    kq = -(-layer.k // s)
    hp, wp = (layer.out_h + kq - 1) * s, (layer.out_w + kq - 1) * s
    p = layer.padding
    xs = jnp.pad(xs, ((0, 0), (p, max(0, hp - layer.in_h - p)),
                      (p, max(0, wp - layer.in_w - p)), (0, 0)))
    c = xs.shape[-1]
    onehot = jnp.eye(s * s * c, dtype=xs.dtype).reshape(s, s, c, s * s * c)
    return lax.conv_general_dilated(
        xs[:, :hp, :wp], onehot, (s, s), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _space_to_depth_filter(w: jax.Array, s: int) -> jax.Array:
    """(k, k, C_in, C_out) -> (k', k', s*s*C_in, C_out), k' = ceil(k/s):
    zero taps pad k to k'*s, and tap (q*s + p) of each axis moves to tap
    q, phase p, in ``_space_to_depth``'s channel order."""
    k, _, cin, cout = w.shape
    kq = -(-k // s)
    w = jnp.pad(w, ((0, kq * s - k), (0, kq * s - k), (0, 0), (0, 0)))
    w = w.reshape(kq, s, kq, s, cin, cout).transpose(0, 2, 1, 3, 4, 5)
    return w.reshape(kq, kq, s * s * cin, cout)


def span_pallas_call(xs: jax.Array, layer_params: list[dict], net: NetSpec,
                     a: int, b: int, *, interpret: bool = False,
                     out_rows: int = 1,
                     srcs: dict[int, jax.Array] | None = None,
                     spill: tuple[int, ...] = ()) -> tuple[jax.Array, dict]:
    """Run SPAN(a, b) of ``net`` on a batch of images under one fused kernel.

    xs: (B, H, W, C) — feature map L_a for B images.
    layer_params: params aligned with ``net.layers[a:b]`` ({"w", "b"} per
    conv, {} per pool).
    out_rows: output row-planes per grid step (tile height t, Eqn. 6).
    srcs: {map index -> (B, h, w, c)} DRAM-resident sources of residual
    edges crossing into the span (required when such edges exist).
    spill: interior maps to materialize as extra outputs (sources of
    partition-crossing residual edges).

    Returns ``(L_b maps, {spilled map index -> array})``.

    Where ``span_input_rewrite`` applies, the input and the first filter
    are folded space-to-depth here, in the caller's trace, and the kernel
    runs the rewritten span.

    The schedule is rebuilt (cheaply) on every call so ring retention is
    re-validated against the *current* ``closure.span_row_counts``; the jit
    cache is keyed on the schedule itself.
    """
    spill = tuple(sorted(set(spill)))
    src_keys = tuple(sorted({s for (s, t) in net.residual_edges
                             if s < a < t <= b}))
    missing = [s for s in src_keys if s not in (srcs or {})]
    if missing:
        raise ValueError(
            f"span ({a}, {b}) needs DRAM residual sources {missing}; "
            "pass them via srcs=")
    wb: list[jax.Array] = []
    for off, layer in enumerate(net.layers[a:b]):
        if layer.kind == "conv":
            wb.append(layer_params[off]["w"])
            wb.append(layer_params[off]["b"])
    knet, ka, kb, fold = span_input_rewrite(net, a, b)
    rewrite = None
    if fold > 1:
        xs = _space_to_depth(xs, net.layers[a], fold)
        wb[0] = _space_to_depth_filter(wb[0], fold)
        rewrite = (net.name, a, b, fold, knet.map_shape(0)[2])
    kspill = tuple(m - a + ka for m in spill)
    schedule = closure.span_schedule(knet, ka, kb, spill=kspill,
                                     out_rows=out_rows)
    out, spills = _span_pallas(
        xs, tuple(wb), tuple((srcs or {})[s] for s in src_keys),
        net=knet, a=ka, b=kb, schedule=schedule, spill=kspill,
        src_keys=src_keys, interpret=interpret, rewrite=rewrite)
    return out, dict(zip(spill, spills))


def span_kernel_vmem_elems(net: NetSpec, a: int, b: int,
                           out_rows: int = 1) -> tuple[int, int]:
    """(ring_scratch_elems, weight_elems) the generated kernel keeps in VMEM.

    ring_scratch_elems == |DC(a, b)| at the given tile height and their sum
    == span_footprint_elems — the property tests pin this identity (scratch
    bytes = footprint x dtype size, minus the weights held as VMEM inputs
    rather than scratch). Both are of the span the kernel runs: for a span
    that takes the space-to-depth input, the closure and filters of the
    rewritten span (``span_input_rewrite``).
    """
    knet, ka, kb, _ = span_input_rewrite(net, a, b)
    schedule = closure.span_schedule(knet, ka, kb, out_rows=out_rows)
    return schedule.scratch_elems(), knet.span_weight_elems(ka, kb)
