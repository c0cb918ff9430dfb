"""Row math of the span kernel and the scan engine (``rowops``).

A max-pool row reduces first and picks its strided columns once; that is
bitwise the older form, which split every window row into column phases
and maxed k*k strided taps. ``column_selects`` counts the one-hot
selection matmuls a span makes per image, and ``kernel.COLUMN_SELECTS``
records that count for each span kernel lowered."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_span import kernel, rowops
from repro.kernels.fused_span.rowops import NEG_INF
from repro.models import zoo


def phase_split_pool_row(window, k, stride, padding, out_w):
    """The pool row as the kernel computed it before: every window row
    split into its column phases, then a running max over the k*k
    taps, each a contiguous slice of one phase."""
    if padding:
        window = jnp.pad(window, ((0, 0), (padding, padding), (0, 0)),
                         constant_values=NEG_INF)
    phases = [rowops.column_phases(window[dy], stride, k)
              for dy in range(k)] if stride > 1 else None
    acc = jnp.full((out_w, window.shape[-1]), NEG_INF, window.dtype)
    for dy in range(k):
        for dx in range(k):
            if stride == 1:
                tap = window[dy, dx:dx + out_w, :]
            else:
                q = dx // stride
                tap = phases[dy][dx % stride][q:q + out_w, :]
            acc = jnp.maximum(acc, tap)
    return acc


POOL_CASES = list(itertools.product(
    (2, 3),          # k
    (1, 2, 3),       # stride
    (0, 1),          # padding
    (15, 16),        # input width, odd and even
    (3, 64),         # channels
))


@pytest.mark.parametrize("k,stride,padding,width,ch", POOL_CASES)
def test_pool_row_is_bitwise_the_phase_split_form(k, stride, padding,
                                                  width, ch):
    """Reduce-then-pick equals the phase-split pool bit for bit, on a
    window of signed values, one whose top row is -inf padding, and one
    that is padding throughout."""
    out_w = (width + 2 * padding - k) // stride + 1
    x = jax.random.normal(jax.random.PRNGKey(k * 100 + stride * 10 + width),
                          (k, width, ch))
    windows = [x, x.at[0].set(NEG_INF), jnp.full_like(x, NEG_INF)]
    for win in windows:
        got = rowops.pool_row(win, k, stride, padding, out_w)
        want = phase_split_pool_row(win, k, stride, padding, out_w)
        assert got.shape == (out_w, ch) and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


VGG_ROUTES = [(0, 6, 168), (6, 11, 28), (11, 13, 0), (13, 14, 0),
              (14, 16, 14), (16, 17, 0), (17, 18, 0), (18, 19, 0),
              (19, 21, 7)]
SELECT_CASES = ([("vggnet",) + r for r in VGG_ROUTES]
                + [("alexnet", 0, 8, 46)])


@pytest.mark.parametrize("name,a,b,selects", SELECT_CASES)
def test_column_selects_of_the_benchmark_spans(name, a, b, selects):
    """One selection per output row of a strided pool: VGG-19's 2x2
    stride-2 pools in the spans vgg19.sat runs, and AlexNet's three 3x3
    stride-2 pools in its whole body, whose stem takes the space-to-depth
    input and so selects nothing (27 + 13 + 6 rows)."""
    net = zoo.get_network(name)
    knet, ka, kb, _ = kernel.span_input_rewrite(net, a, b)
    assert rowops.column_selects(knet, ka, kb) == selects


def test_column_selects_counts_strided_convs_and_shortcuts():
    """A strided conv still splits each of its k window rows into
    min(stride, k) column phases; a shortcut that subsamples its source
    picks once per row. ResNet-18's stride-2 block (6, 8): conv 6 -> 7
    is 3x3 stride 2 over 28 output rows, the edge (6, 8) halves the
    width of its 28 rows."""
    net = zoo.get_network("resnet18")
    conv = net.layers[6]
    assert (conv.kind, conv.k, conv.stride, conv.out_h) == ("conv", 3, 2, 28)
    assert (6, 8) in net.residual_edges
    assert rowops.column_selects(net, 6, 8) == 28 * 3 * 2 + 28


@pytest.mark.parametrize("name,a,b,selects", [
    ("vggnet", 0, 6, 168), ("vggnet", 14, 16, 14), ("vggnet", 16, 17, 0),
    ("alexnet", 0, 8, 46)])
def test_lowering_records_column_selects(name, a, b, selects):
    """An interpret-mode lowering of the span's step at full size appends
    the count under the published net and span."""
    net = zoo.get_network(name)
    xs = jax.ShapeDtypeStruct((1,) + net.map_shape(a), jnp.float32)
    params = [{"w": jax.ShapeDtypeStruct((layer.k, layer.k, layer.in_ch,
                                          layer.out_ch), jnp.float32),
               "b": jax.ShapeDtypeStruct((layer.out_ch,), jnp.float32)}
              if layer.kind == "conv" else {} for layer in net.layers[a:b]]

    def step(xs, params):
        return kernel.span_pallas_call(xs, params, net, a, b,
                                       interpret=True)[0]

    jax.jit(step).lower(xs, params)
    assert (name, a, b, selects) in kernel.COLUMN_SELECTS
