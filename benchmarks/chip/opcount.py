"""Operations and bytes of one span-kernel call, counted from shapes.

A span (a, b) maps feature map a to map b through layers a..b-1. Its
useful work is the conv layers' multiply-accumulates, two operations
each. The column-phase selection matmuls the kernel adds for strided
taps are not useful work and are not counted. Its bytes are what one
call must move to and from HBM: per image its input map, its output
map, the interior maps it spills and the residual sources it reads;
once per call its weights and biases.
"""
from __future__ import annotations

import netref


def span_flops(net: dict, a: int, b: int, batch: int) -> float:
    return 2.0 * netref.conv_macs(net, a, b) * batch


def span_bytes(net: dict, a: int, b: int, batch: int,
               spill: tuple[int, ...] = (), itemsize: int = 4) -> float:
    def elems(m):
        h, w, c = netref.map_shape(net, m)
        return h * w * c

    per_image = elems(a) + elems(b) + sum(elems(m) for m in spill)
    per_image += sum(elems(s) for s, t in net.get("residual_edges", ())
                     if s < a < t <= b)
    lays = netref.layers(net)[a:b]
    weights = sum(l.k * l.k * l.in_ch * l.out_ch + l.out_ch
                  for l in lays if l.kind == "conv")
    return float(itemsize * (per_image * batch + weights))


def least_time_s(flops: float, nbytes: float, peaks: dict
                 ) -> tuple[float, str]:
    """The roofline's least time for one call, and which bound sets it."""
    t_compute = flops / peaks["flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
