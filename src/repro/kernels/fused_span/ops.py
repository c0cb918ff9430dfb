"""Public ops: Occam fused-span execution with validation + backend dispatch.

``span_forward`` is the general entry point: any conv/pool span of a
NetSpec — per-layer k, stride >= 1, same-padding, batch > 1, residual
edges, multi-row output tiles — lowered to a single generated Pallas
kernel (see kernel.py). ``fused_span`` keeps the original two-conv
signature and now simply builds the equivalent 2-layer NetSpec and runs it
through the same generator, so the legacy path exercises the general
machinery.

Residual edges crossing *into* the span need their DRAM-resident source
maps passed via ``srcs``; interior sources of partition-crossing edges are
materialized by listing them in ``spill``. The dispatcher in
``repro.runtime.span_engine`` wires both automatically per DP partition.
"""
from __future__ import annotations

import jax

from repro.core.graph import NetSpec, chain

from .kernel import span_kernel_vmem_elems, span_pallas_call
from .ref import fused_span_ref


def default_interpret() -> bool:
    """Interpret mode everywhere but a TPU backend: on the CPU the kernel
    body runs as Python for correctness tests; on a chip it compiles."""
    return jax.default_backend() != "tpu"


def span_forward(xs: jax.Array, layer_params: list[dict], net: NetSpec,
                 a: int, b: int, interpret: bool | None = None,
                 out_rows: int = 1,
                 srcs: dict[int, jax.Array] | None = None,
                 spill: tuple[int, ...] = ()):
    """Execute SPAN(a, b) of ``net`` as one fused Pallas kernel.

    xs: (B, H, W, C) batch (or (H, W, C), auto-promoted) of L_a planes.
    ``interpret`` defaults to True off-TPU (pure-Python execution of the
    kernel body for correctness validation on CPU).
    ``out_rows``: output row-planes per grid step (tile height t, Eqn. 6).
    ``srcs``: DRAM-resident sources of residual edges crossing into the
    span ({map index -> (B, h, w, c) or (h, w, c) matching xs}).
    ``spill``: interior maps to materialize for downstream spans.

    Returns feature map L_b — or ``(L_b, {map -> array})`` when ``spill``
    is non-empty.
    """
    if not (0 <= a < b <= net.n_layers):
        raise ValueError(f"bad span ({a}, {b})")
    squeeze = xs.ndim == 3
    if squeeze:
        xs = xs[None]
        srcs = {s: v[None] for s, v in (srcs or {}).items()}
    if xs.shape[1:] != net.map_shape(a):
        raise ValueError(f"input {xs.shape[1:]} != map L_{a} "
                         f"{net.map_shape(a)}")
    if len(layer_params) != b - a:
        raise ValueError("layer_params must align with net.layers[a:b]")
    for off, layer in enumerate(net.layers[a:b]):
        if layer.kind == "conv":
            w = layer_params[off]["w"]
            if w.shape != (layer.k, layer.k, layer.in_ch, layer.out_ch):
                raise ValueError(f"layer {a + off} weight shape {w.shape}")
    if interpret is None:
        interpret = default_interpret()
    ys, spilled = span_pallas_call(xs, layer_params, net, a, b,
                                   interpret=interpret, out_rows=out_rows,
                                   srcs=srcs, spill=spill)
    if squeeze:
        ys = ys[0]
        spilled = {m: v[0] for m, v in spilled.items()}
    return (ys, spilled) if spill else ys


def fused_span(x: jax.Array, w1: jax.Array, b1: jax.Array,
               w2: jax.Array, b2: jax.Array,
               interpret: bool | None = None) -> jax.Array:
    """Two stacked same-padded stride-1 conv+ReLU layers, fused so the
    intermediate map never leaves VMEM (Occam dependence closure).

    x: (H, W, Cin); w1: (k, k, Cin, Cmid); w2: (k, k, Cmid, Cout).
    Legacy 2-conv signature, now lowered via the N-layer span generator.
    """
    k = w1.shape[0]
    if w1.shape[0] != w1.shape[1] or w2.shape[0] != w2.shape[1]:
        raise ValueError("square filters only")
    if w2.shape[0] != k:
        raise ValueError("both layers must share k")
    if k % 2 != 1:
        raise ValueError("odd k only (same padding)")
    if x.ndim != 3 or x.shape[-1] != w1.shape[2] or w1.shape[3] != w2.shape[2]:
        raise ValueError(f"shape mismatch: {x.shape} {w1.shape} {w2.shape}")
    h, w, _ = x.shape
    net = chain("fused_span", [("conv", k, 1, k // 2, int(w1.shape[3])),
                               ("conv", k, 1, k // 2, int(w2.shape[3]))],
                in_h=h, in_w=w, in_ch=int(x.shape[-1]))
    return span_forward(x, [{"w": w1, "b": b1}, {"w": w2, "b": b2}],
                        net, 0, 2, interpret=interpret)


__all__ = ["default_interpret", "fused_span", "fused_span_ref",
           "span_forward", "span_kernel_vmem_elems"]
