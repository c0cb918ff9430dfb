"""Compiled span engine tests: the generated N-layer Pallas kernel and the
jitted scan executor agree with the layer-by-layer oracle across kernel
sizes, strides, conv/pool mixes and batch; the kernel's VMEM scratch is
exactly the dependence closure; and the dispatcher routes a PartitionResult
correctly while preserving model==machine traffic accounting. Spans that
open with a strided conv over few channels run on a space-to-depth input;
the same equalities hold there, and the rewrite engages only where the
rule says."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import closure
from repro.core.graph import chain
from repro.core.partition import partition_cnn
from repro.kernels.fused_span import kernel
from repro.kernels.fused_span.kernel import (span_input_rewrite,
                                             span_kernel_vmem_elems)
from repro.kernels.fused_span.ops import span_forward
from repro.models import cnn, zoo
from repro.runtime import span_engine

C, P = "conv", "pool"

SPAN_CASES = [
    # (name, specs, hw, in_ch)
    ("k1-s1", [(C, 1, 1, 0, 4), (C, 1, 1, 0, 8)], 8, 3),
    ("k3-s1-deep", [(C, 3, 1, 1, 4), (C, 3, 1, 1, 8), (C, 3, 1, 1, 4)], 8, 3),
    pytest.param("k5-s1", [(C, 5, 1, 2, 4), (C, 5, 1, 2, 4)], 10, 2,
                 marks=pytest.mark.slow),
    ("k3-s2", [(C, 3, 2, 1, 4), (C, 3, 1, 1, 8)], 10, 3),
    pytest.param("mixed-k", [(C, 5, 1, 2, 4), (C, 1, 1, 0, 8),
                             (C, 3, 2, 1, 8)], 10, 3,
                 marks=pytest.mark.slow),
    ("conv-pool-s2", [(C, 3, 1, 1, 4), (P, 2, 2, 0, 0), (C, 3, 2, 1, 8)], 12, 3),
    ("pool-k3-s2-pad", [(C, 3, 1, 1, 4), (P, 3, 2, 1, 0)], 9, 3),
    pytest.param("vgg-block", [(C, 3, 1, 1, 8), (C, 3, 1, 1, 8),
                               (P, 2, 2, 0, 0), (C, 3, 1, 1, 16)], 8, 3,
                 marks=pytest.mark.slow),
    # VGG's 2x2 stride-2 unpadded pools, over an even and an odd width
    ("vgg-pools", [(C, 3, 1, 1, 4), (P, 2, 2, 0, 0), (C, 3, 1, 1, 8),
                   (P, 2, 2, 0, 0)], 14, 3),
]


def make_case(specs, hw, ch, batch=2, seed=0):
    net = chain("t", specs, in_h=hw, in_w=hw, in_ch=ch)
    params = cnn.init_params(jax.random.PRNGKey(seed), net)
    xs = jax.random.normal(jax.random.PRNGKey(seed + 1), (batch, hw, hw, ch))
    ref = jax.vmap(lambda im: cnn.reference_forward(params, im, net))(xs)
    return net, params, xs, ref


def assert_close(got, ref, **kw):
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4, **kw)


@pytest.mark.parametrize("name,specs,hw,ch", SPAN_CASES)
def test_pallas_kernel_matches_oracle(name, specs, hw, ch):
    """Generated kernel == oracle across k in {1,3,5}, stride in {1,2},
    conv+pool mixes, batch > 1 (interpret mode)."""
    net, params, xs, ref = make_case(specs, hw, ch, batch=2)
    got = span_forward(xs, params, net, 0, net.n_layers, interpret=True)
    assert_close(got, ref, err_msg=name)


@pytest.mark.parametrize("name,specs,hw,ch", SPAN_CASES)
def test_scan_matches_oracle(name, specs, hw, ch):
    """Jitted scan streaming == oracle on the same case grid."""
    net, params, xs, ref = make_case(specs, hw, ch, batch=2)
    got = jnp.stack([cnn.occam_forward(params, xs[i], net, mode="compiled")
                     for i in range(xs.shape[0])])
    assert_close(got, ref, err_msg=name)


def test_whole_net_single_jit():
    net, params, xs, ref = make_case(
        [(C, 3, 1, 1, 4), (P, 2, 2, 0, 0), (C, 3, 1, 1, 8)], 12, 3, batch=1)
    got = cnn.occam_forward_jit(params, xs[0], net, (1,))
    assert_close(got, ref[0])


@pytest.mark.parametrize("name,specs,hw,ch", SPAN_CASES[:4])
def test_kernel_scratch_is_exactly_the_closure(name, specs, hw, ch):
    """Property: the generated kernel's ring scratch bytes equal
    |DC(a,b)| x dtype size, and scratch + resident filters equal
    span_footprint_elems x dtype size (Eqn. 1's left-hand side)."""
    net = chain("t", specs, in_h=hw, in_w=hw, in_ch=ch)
    # the span the kernel runs: the published one, or its space-to-depth
    # form where the first layer is a strided conv (k3-s2)
    knet, a, b, _ = span_input_rewrite(net, 0, net.n_layers)
    scratch, weights = span_kernel_vmem_elems(net, 0, net.n_layers)
    itemsize = jnp.dtype(jnp.float32).itemsize
    assert scratch * itemsize == \
        closure.span_closure_elems(knet, a, b) * itemsize
    assert (scratch + weights) * itemsize == \
        closure.span_footprint_elems(knet, a, b) * itemsize


S2D_CASES = [
    # (name, specs, hw, in_ch, residual_edges); every first layer is a
    # strided conv with s*s*C_in <= 128, so the span takes the
    # space-to-depth input
    ("k11-s4-pad", [(C, 11, 4, 0, 8), (P, 3, 2, 0, 0), (C, 3, 1, 1, 8)],
     31, 3, ()),                             # 31 -> 32 rows and columns
    ("k11-s4-crop", [(C, 11, 4, 0, 8), (C, 3, 1, 1, 8)], 30, 3, ()),
    ("k7-s2-p3", [(C, 7, 2, 3, 8), (P, 3, 2, 1, 0)], 15, 3, ()),
    ("k3-s2-p1", [(C, 3, 2, 1, 4), (C, 3, 1, 1, 8)], 9, 3, ()),
    ("k7-s2-res", [(C, 7, 2, 3, 4), (P, 3, 2, 1, 0), (C, 3, 1, 1, 4),
                   (C, 3, 1, 1, 4)], 16, 3, ((2, 4),)),
]


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("out_rows", [1, 2])
@pytest.mark.parametrize("name,specs,hw,ch,edges", S2D_CASES)
def test_strided_stem_takes_space_to_depth_input(name, specs, hw, ch, edges,
                                                 out_rows):
    """A span opening with a strided conv runs as a stride-1 conv over
    the folded input and still equals the oracle (batch 2, one or two
    output rows a step, a residual source downstream of the input), and
    INPUT_REWRITES records it under the published net and span."""
    net = chain(f"s2d-{name}", specs, in_h=hw, in_w=hw, in_ch=ch,
                residual_edges=edges)
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, hw, hw, ch))
    ref = jax.vmap(lambda im: cnn.reference_forward(params, im, net))(xs)
    got = span_forward(xs, params, net, 0, net.n_layers, interpret=True,
                       out_rows=out_rows)
    assert_close(got, ref, err_msg=name)
    s = specs[0][2]
    assert (net.name, 0, net.n_layers, s, s * s * ch) in \
        kernel.INPUT_REWRITES
    assert (net.name, 0, net.n_layers, True) in kernel.LOWERINGS


@pytest.mark.pallas_interpret
def test_space_to_depth_span_after_the_first_map_spills():
    """The rewrite of a span that starts past map 0 (span (1, 3), its
    first layer a stride-2 conv over 4 channels) keeps the published map
    indices of what it spills: map 2 leaves the span for edge (2, 4)."""
    net = chain("s2d-mid", [(C, 3, 1, 1, 4), (C, 3, 2, 1, 8),
                            (C, 3, 1, 1, 8), (C, 3, 1, 1, 8)],
                in_h=12, in_w=12, in_ch=3, residual_edges=((2, 4),))
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 12, 3))
    maps = jax.vmap(lambda im: cnn.reference_forward(
        params, im, net, collect=True))(xs)
    got, spilled = span_forward(maps[1], params[1:3], net, 1, 3,
                                interpret=True, spill=(2,))
    assert_close(got, maps[3])
    assert_close(spilled[2], maps[2])
    assert ("s2d-mid", 1, 3, 2, 16) in kernel.INPUT_REWRITES


def test_space_to_depth_rule():
    """The rewrite engages by shape alone: a strided conv opens the span,
    its folded channels fit one lane tile, and only it reads map a."""
    alexnet = zoo.get_network("alexnet")
    knet, a, b, s = span_input_rewrite(alexnet, 0, 8)
    assert (a, b, s) == (0, 8, 4)
    assert knet.map_shape(0) == (57, 57, 48)
    first = knet.layers[0]
    assert (first.kind, first.k, first.stride, first.padding,
            first.out_ch) == ("conv", 3, 1, 0, 64)
    assert knet.map_shape(b) == alexnet.map_shape(8)
    for name in ("resnet18", "resnet50"):          # 7x7 stride-2 stems
        assert span_input_rewrite(zoo.get_network(name), 0, 2)[3] == 2
    # interior strided layers and pools keep the column phases
    assert span_input_rewrite(alexnet, 1, 3)[3] == 1
    # map a is a residual source: the shortcut reads its rows too
    res = chain("r", [(C, 3, 2, 1, 4), (C, 3, 1, 1, 4)], in_h=8, in_w=8,
                in_ch=3, residual_edges=((0, 2),))
    assert span_input_rewrite(res, 0, 2) == (res, 0, 2, 1)
    # s*s*C_in = 4 * 64 > 128 lanes
    wide = chain("w", [(C, 3, 2, 1, 8)], in_h=8, in_w=8, in_ch=64)
    assert span_input_rewrite(wide, 0, 1) == (wide, 0, 1, 1)
    # no span of vgg19.sat's plan opens with a strided layer
    config = json.loads((pathlib.Path(__file__).parents[1] / "benchmarks"
                         / "chip" / "configs" / "vgg19.json").read_text())
    vgg = zoo.get_network(config["net"]["name"])
    assert len(config["routes"]) == 9
    for a, b, _ in config["routes"]:
        assert span_input_rewrite(vgg, a, b) == (vgg, a, b, 1)


@pytest.mark.parametrize("name,specs,hw,ch,edges", S2D_CASES)
def test_kernel_scratch_is_the_closure_of_the_rewritten_net(name, specs, hw,
                                                            ch, edges):
    """For a span that takes the space-to-depth input, the kernel's ring
    scratch is exactly the closure of the rewritten span, and scratch +
    its regrouped filters its footprint. (The DP still sizes the
    published span: ``closure`` is not told of the rewrite.)"""
    net = chain("t", specs, in_h=hw, in_w=hw, in_ch=ch, residual_edges=edges)
    n = net.n_layers
    knet, a, b, s = span_input_rewrite(net, 0, n)
    assert s == specs[0][2] and knet.map_shape(0)[2] == s * s * ch
    scratch, weights = span_kernel_vmem_elems(net, 0, n)
    assert scratch == closure.span_closure_elems(knet, a, b)
    assert scratch + weights == closure.span_footprint_elems(knet, a, b)
    assert weights == knet.span_weight_elems(a, b)


RESIDUAL_CASES = [
    # (name, specs, hw, in_ch, residual_edges)
    ("res-k1", [(C, 1, 1, 0, 4), (C, 1, 1, 0, 4), (C, 1, 1, 0, 4)], 8, 3,
     ((0, 2),)),
    ("res-k3", [(C, 3, 1, 1, 4)] * 4, 10, 3, ((0, 2), (1, 4))),
    ("res-k3-s2", [(C, 3, 2, 1, 4), (C, 3, 1, 1, 4), (C, 3, 1, 1, 4)],
     12, 3, ((1, 3),)),
]


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("name,specs,hw,ch,edges", RESIDUAL_CASES)
def test_residual_span_kernel_matches_scan_and_oracle(name, specs, hw, ch,
                                                      edges):
    """Residual spans are first-class kernel bodies: pallas == scan ==
    oracle across k in {1,3}, stride in {1,2}, batch > 1. The add comes
    from the in-span ring (no DRAM round-trip)."""
    net = chain("r", specs, in_h=hw, in_w=hw, in_ch=ch,
                residual_edges=edges)
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, hw, hw, ch))
    ref = jax.vmap(lambda im: cnn.reference_forward(params, im, net))(xs)
    got = span_forward(xs, params, net, 0, net.n_layers, interpret=True)
    assert_close(got, ref, err_msg=name)
    scan = jnp.stack([cnn.occam_forward(params, xs[i], net, mode="compiled")
                      for i in range(xs.shape[0])])
    assert_close(scan, ref, err_msg=name)


def test_kernel_names_missing_crossing_sources():
    """A span whose residual source lives before its input needs that map
    as a DRAM operand — omitting it fails loudly, naming the source."""
    net = chain("t", [(C, 3, 1, 1, 4)] * 3, in_h=8, in_w=8,
                in_ch=3, residual_edges=((0, 3),))
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    xs = jnp.zeros((1, 8, 8, 4))
    with pytest.raises(ValueError, match="residual sources \\[0\\]"):
        span_forward(xs, params[1:3], net, 1, 3, interpret=True)


def test_dispatch_from_partition_result():
    """DP partition of a strided conv/pool net: every residual-free span
    routes to the pallas kernel (>= 3-deep, stride 2, batch > 1) and the
    engine output matches the oracle with model==machine traffic."""
    specs = [(C, 3, 1, 1, 8), (C, 3, 1, 1, 8), (P, 2, 2, 0, 0),
             (C, 3, 1, 1, 16), (C, 3, 2, 1, 16), (C, 3, 1, 1, 8)]
    net, params, xs, ref = make_case(specs, 16, 4, batch=2)
    res = partition_cnn(net, 3000)
    assert res.n_spans >= 2  # capacity actually forces a split
    routes = span_engine.plan_routes(net, res)
    assert all(r.route == span_engine.ROUTE_PALLAS for r in routes)
    assert any(r.end - r.start >= 3 for r in routes)  # >= 3-deep span
    ctr = cnn.TrafficCounter()
    got = span_engine.execute_partition(params, xs, net, res, counter=ctr,
                                        interpret=True)
    assert_close(got, ref)
    assert ctr.total == xs.shape[0] * cnn.predicted_transfers(
        net, res.boundaries)


@pytest.mark.pallas_interpret
def test_dispatch_residual_spans_to_pallas():
    """Residual-crossing spans route to the fused kernel — no silent scan
    substitution — and traffic still matches the DP model (spill
    accounting included)."""
    net = chain("r", [(C, 3, 1, 1, 4)] * 4, in_h=12, in_w=12, in_ch=3,
                residual_edges=((1, 4),))
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 12, 3))
    ref = jax.vmap(lambda im: cnn.reference_forward(params, im, net))(xs)
    routes = span_engine.plan_routes(net, [2])
    assert all(r.route == span_engine.ROUTE_PALLAS for r in routes)
    ctr = cnn.TrafficCounter()
    got = span_engine.execute_partition(params, xs, net, [2], counter=ctr,
                                        interpret=True)
    assert_close(got, ref)
    assert ctr.total == 2 * cnn.predicted_transfers(net, [2])


@pytest.mark.pallas_interpret
def test_straddled_and_split_edge_spans_take_the_kernel():
    """Every role a partition can hand a span — straddled by an edge,
    spilling an interior source, adding a crossing source from DRAM —
    stays on the pallas route. Edge (1, 4) over boundaries [2, 3]:
    span (2, 3) is straddled, span (0, 2) spills the source as an extra
    kernel output, span (3, 4) adds it from a DRAM operand."""
    net = chain("r", [(C, 3, 1, 1, 4)] * 4, in_h=12, in_w=12, in_ch=3,
                residual_edges=((1, 4),))
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 12, 3))
    ref = jax.vmap(lambda im: cnn.reference_forward(params, im, net))(xs)
    routes = {(r.start, r.end): r.route
              for r in span_engine.plan_routes(net, [2, 3])}
    assert routes[(2, 3)] == span_engine.ROUTE_PALLAS
    assert routes[(0, 2)] == span_engine.ROUTE_PALLAS  # source spill
    assert routes[(3, 4)] == span_engine.ROUTE_PALLAS  # DRAM-operand add
    ctr = cnn.TrafficCounter()
    got = span_engine.execute_partition(params, xs, net, [2, 3], counter=ctr,
                                        interpret=True)
    assert_close(got, ref)
    assert ctr.total == 2 * cnn.predicted_transfers(net, [2, 3])


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("t", [1, 2, 4])
def test_multirow_tiles_match_oracle(t):
    """out_rows > 1 tiles: the kernel emits t output row-planes per grid
    step and still equals the oracle (strided net + residual edge)."""
    net = chain("r", [(C, 3, 2, 1, 4), (C, 3, 1, 1, 4), (C, 3, 1, 1, 4)],
                in_h=12, in_w=12, in_ch=3, residual_edges=((1, 3),))
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 12, 3))
    ref = jax.vmap(lambda im: cnn.reference_forward(params, im, net))(xs)
    got = span_forward(xs, params, net, 0, net.n_layers, interpret=True,
                       out_rows=t)
    assert_close(got, ref, err_msg=f"t={t}")
    # the dispatcher threads the same knob end to end
    res = partition_cnn(net, 10**6)
    via_engine = span_engine.execute_partition(params, xs, net, res,
                                               interpret=True, out_rows=t)
    assert_close(via_engine, ref, err_msg=f"t={t} via engine")


@pytest.mark.parametrize("t", [2, 4])
def test_kernel_scratch_is_the_closure_at_multirow_tiles(t):
    """The scratch==closure identity holds at every tile height: ring
    elems == |DC(a, b; t)| and scratch + weights == the grown footprint
    Eqn. 6 charges for t output rows per step."""
    net = chain("t", [(C, 3, 1, 1, 4), (C, 3, 1, 1, 8), (C, 3, 1, 1, 4)],
                in_h=12, in_w=12, in_ch=3)
    a, b = 0, net.n_layers
    scratch, weights = span_kernel_vmem_elems(net, a, b, out_rows=t)
    assert scratch == closure.span_closure_elems(net, a, b, t)
    assert scratch + weights == closure.span_footprint_elems(
        net, a, b, out_rows=t)


def test_engine_accepts_single_image():
    net, params, xs, ref = make_case([(C, 3, 1, 1, 4), (C, 3, 2, 1, 8)],
                                     10, 3, batch=1)
    got = span_engine.execute_partition(params, xs[0], net, [],
                                        interpret=True)
    assert got.shape == ref[0].shape
    assert_close(got, ref[0])


def test_api_span_executor_is_deprecated_shim():
    """The legacy one-call entry survives as a staged-API shim: same
    outputs, but with a DeprecationWarning pointing at repro.occam."""
    from repro.models.api import span_executor

    net, params, xs, ref = make_case(
        [(C, 3, 1, 1, 8), (C, 3, 1, 1, 8), (P, 2, 2, 0, 0),
         (C, 3, 1, 1, 16)], 12, 4, batch=2)
    with pytest.warns(DeprecationWarning, match="repro.occam"):
        y, res = span_executor(params, xs, net, 3000, interpret=True)
    assert res.n_spans >= 1
    assert_close(y, ref)


def test_staged_api_executes_partition():
    """The staged surface drives the same engines: plan -> place ->
    compile -> run equals the oracle with model==machine traffic."""
    from repro import occam

    net, params, xs, ref = make_case(
        [(C, 3, 1, 1, 8), (C, 3, 1, 1, 8), (P, 2, 2, 0, 0),
         (C, 3, 1, 1, 16)], 12, 4, batch=2)
    dep = occam.plan(net, 3000, batch=2).place().compile(interpret=True)
    assert_close(dep.run(params, xs), ref)
    assert dep.report().matches_prediction


def test_starved_rings_fail_schedule_validation():
    """The compiled engine preserves the necessity proof: shrinking the
    closure by one row is detected by schedule replay validation."""
    net = chain("t", [(C, 3, 1, 1, 4), (C, 3, 1, 1, 4)], in_h=10, in_w=10,
                in_ch=3)
    real = closure.span_row_counts

    def starved(n, i, j, out_rows=1):
        return [max(r - 1, 1) for r in real(n, i, j, out_rows)]

    closure.span_row_counts = starved
    try:
        with pytest.raises(AssertionError, match="ring violation"):
            closure.span_schedule(net, 0, 2)
    finally:
        closure.span_row_counts = real
