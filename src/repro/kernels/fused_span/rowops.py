"""Row-plane compute shared by the Pallas span kernel and the jitted scan
streaming path (repro.models.cnn).

Everything here operates on plain jnp values, so the same code runs inside a
Pallas kernel body (on values read from VMEM refs) and inside a traced
``lax.fori_loop`` (on values gathered from ring arrays). Keeping one
implementation is what makes the kernel-vs-scan equality tests meaningful:
both engines share the row math and differ only in how rows are stored.

Convs are executed as k*k MXU matmuls (W_out, C_in) @ (C_in, C_out) over
horizontally shifted/strided row windows, accumulating in fp32
(channels-minor layout; the MXU-friendly form of the paper's row-streamed
convolution). Pools are k*k running maxima with -inf padding.

Horizontal strides never slice a value with a step: Mosaic (the TPU
kernel compiler) lowers neither ``x[a:b:s]`` nor a strided ``lax.slice``.
A strided layer instead splits each window row into its ``stride`` column
phases with one-hot selection matmuls (``column_phases``), after which
every tap reads a contiguous slice of one phase.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def select_cols(row, start: int, stride: int, n: int):
    """Columns ``start, start + stride, ...`` (``n`` of them) of a (W, C)
    row as a one-hot (n, W) @ (W, C) matmul. ``precision=HIGHEST`` keeps
    it exact in fp32: each output is one input times 1.0 plus zeros."""
    shape = (n, row.shape[0])
    sel = (lax.broadcasted_iota(jnp.int32, shape, 1)
           == start + stride * lax.broadcasted_iota(jnp.int32, shape, 0))
    out = jnp.dot(sel.astype(jnp.float32), row.astype(jnp.float32),
                  precision=lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    return out.astype(row.dtype)


def column_phases(row, stride: int, k: int):
    """Split a (W, C) row into the column phases a k-wide tap reads:
    phase p holds columns p, p + stride, ... so column dx + stride * i is
    row i of phase dx % stride, offset by dx // stride."""
    w = row.shape[0]
    return [select_cols(row, p, stride, -(-(w - p) // stride))
            for p in range(min(stride, k))]


def _tap_cols(window, dy: int, dx: int, stride: int, out_w: int, phases):
    """Columns dx, dx + stride, ... (out_w of them) of window row dy."""
    if stride == 1:
        return window[dy, dx:dx + out_w, :]
    q = dx // stride
    return phases[dy][dx % stride][q:q + out_w, :]


def ring_window(ring, r, k: int, stride: int, padding: int, h_prev: int,
                cap: int, pad_val: float):
    """Gather the k input rows feeding output row ``r`` from a circular
    buffer of the most recent ``cap`` rows of a (h_prev, W, C) map.

    ``ring`` may be a Pallas ref or a jnp array — both support dynamic
    first-axis indexing. Rows outside [0, h_prev) are synthesized padding
    (zero for conv, -inf for pool), exactly like the oracle's edge handling.
    Returns (k, W, C).
    """
    rows = []
    for dy in range(k):
        rr = r * stride - padding + dy
        valid = jnp.logical_and(rr >= 0, rr < h_prev)
        safe = (jnp.where(valid, rr, 0) % cap).astype(jnp.int32)
        data = ring[safe]
        rows.append(jnp.where(valid, data, jnp.full_like(data, pad_val)))
    return jnp.stack(rows)


def conv_row(window, w, b, stride: int, padding: int, out_w: int):
    """One conv+ReLU output row from a (k, W_in, C_in) window.

    window carries the exact vertical halo (already padding-synthesized);
    horizontal same-padding is applied here. w: (k, k, C_in, C_out).
    Returns (out_w, C_out) in fp32.
    """
    k = w.shape[0]
    if padding:
        window = jnp.pad(window, ((0, 0), (padding, padding), (0, 0)))
    acc = jnp.zeros((out_w, w.shape[-1]), jnp.float32)
    phases = [column_phases(window[dy], stride, k) for dy in range(k)] \
        if stride > 1 else None
    for dy in range(k):
        for dx in range(k):
            cols = _tap_cols(window, dy, dx, stride, out_w, phases)
            acc += jnp.dot(cols.astype(jnp.float32),
                           w[dy, dx].astype(jnp.float32),
                           preferred_element_type=jnp.float32)
    return jax.nn.relu(acc + b.astype(jnp.float32))


def pool_row(window, k: int, stride: int, padding: int, out_w: int):
    """One max-pool output row from a (k, W_in, C) window (vertical halo
    included, out-of-range rows already -inf). Returns (out_w, C)."""
    if padding:
        window = jnp.pad(window, ((0, 0), (padding, padding), (0, 0)),
                         constant_values=NEG_INF)
    phases = [column_phases(window[dy], stride, k) for dy in range(k)] \
        if stride > 1 else None
    acc = jnp.full((out_w, window.shape[-1]), NEG_INF, window.dtype)
    for dy in range(k):
        for dx in range(k):
            acc = jnp.maximum(
                acc, _tap_cols(window, dy, dx, stride, out_w, phases))
    return acc


def project_row(src_row, w_t: int, c_t: int):
    """Parameter-free 'option A' residual shortcut for one row-plane:
    strided horizontal subsample + channel pad/trim. src_row: (W_s, C_s)."""
    w_s, c_s = src_row.shape
    sw = max(w_s // w_t, 1)
    y = select_cols(src_row, 0, sw, w_t) if sw > 1 else src_row[:w_t, :]
    if c_t > c_s:
        y = jnp.pad(y, ((0, 0), (0, c_t - c_s)))
    elif c_t < c_s:
        y = y[:, :c_t]
    return y
