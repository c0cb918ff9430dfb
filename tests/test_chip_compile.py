"""The fused-span kernel compiles for a TPU v5e, with no chip attached.

Interpret mode runs the kernel body as Python and accepts constructs the
TPU's kernel compiler (Mosaic) refuses: strided value slices, or more
VMEM than a kernel may use by default. These tests hand ``_span_pallas``
shapes on a described ``v5e:2x2`` topology and compile it with
``interpret=False``, one span per feature, at real widths. Nothing runs;
a pass says the chip's compiler accepts the kernel, not that it is right
(the interpret-mode equality tests say that).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import closure
from repro.kernels.fused_span.kernel import (INPUT_REWRITES, LOWERINGS,
                                             _span_pallas, span_pallas_call)
from repro.models import zoo


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _lower_args(net, a, b, sharding, batch=2):
    """``_span_pallas`` arguments as shapes on ``sharding``, plus its
    static keywords (what ``span_pallas_call`` would pass)."""
    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    schedule = closure.span_schedule(net, a, b)
    src_keys = tuple(sorted({s for (s, t) in net.residual_edges
                             if s < a < t <= b}))
    wb = []
    for layer in net.layers[a:b]:
        if layer.kind == "conv":
            wb += [sd((layer.k, layer.k, layer.in_ch, layer.out_ch)),
                   sd((layer.out_ch,))]
    args = (sd((batch,) + net.map_shape(a)), tuple(wb),
            tuple(sd((batch,) + net.map_shape(s)) for s in src_keys))
    return args, dict(net=net, a=a, b=b, schedule=schedule, spill=(),
                      src_keys=src_keys, interpret=False)


@pytest.mark.parametrize("name,a,b", [
    ("alexnet", 0, 1),     # 11x11 stride-4 stem on 3 channels
    ("alexnet", 1, 2),     # 3x3 stride-2 max-pool
    ("resnet18", 6, 8),    # stride-2 block with its residual downsample
    ("vggnet", 16, 17),    # 3x3 conv, 512 -> 512 channels
    ("alexnet", 0, 8),     # whole body: past the default scoped VMEM
    ("vggnet", 0, 6),      # 2x2 stride-2 pools over 224- and 112-wide rows
], ids=["stem-s4", "pool-s2", "residual-downsample", "conv-512ch",
        "alexnet-body-vmem", "vgg-pools-224"])
def test_span_kernel_compiles_for_v5e(name, a, b, one_chip,
                                      no_persistent_cache):
    net = zoo.get_network(name)
    args, kw = _lower_args(net, a, b, one_chip)
    compiled = _span_pallas.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_step(net, a, b, sharding):
    """The HLO text of a jitted step that runs SPAN(a, b) through
    ``span_pallas_call``, as the serving step compiles it."""
    (xs, wb, _srcs), _kw = _lower_args(net, a, b, sharding)
    convs = [i for i, layer in enumerate(net.layers[a:b])
             if layer.kind == "conv"]

    def step(xs, wb):
        layer_params = [{} for _ in net.layers[a:b]]
        for j, i in enumerate(convs):
            layer_params[i] = {"w": wb[2 * j], "b": wb[2 * j + 1]}
        return span_pallas_call(xs, layer_params, net, a, b,
                                interpret=False)[0]

    return jax.jit(step).lower(xs, wb).compile().as_text()


def _kernel_calls(text):
    """(name, scoped VMEM bytes requested) of each kernel custom call."""
    calls = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = line.split(" = ", 1)[0].split()[-1].lstrip("%")
        vmem = re.search(r'"scoped_memory_configs":\[\{[^]]*"size":"(\d+)"',
                         line)
        calls.append((name, int(vmem.group(1))))
    return calls


def test_span_kernel_carries_its_stable_name(one_chip, no_persistent_cache):
    """Compiled inside a jitted step, as the serving step compiles it, a
    span kernel's custom call is named for its span and still contains
    ``_span_pallas`` (what the benchmark's kernel match reads)."""
    net = zoo.get_network("alexnet")
    a, b = 0, 3                 # stem, pool, conv
    calls = _kernel_calls(_compile_step(net, a, b, one_chip))
    assert len(calls) == 1
    name = calls[0][0]
    assert re.fullmatch(r"_span_pallas_0_3(\.\d+)?", name), name
    assert "_span_pallas" in name


@pytest.mark.parametrize("a,b", [(0, 8), (0, 1)])
def test_strided_stem_compiles_on_space_to_depth_input(a, b, one_chip,
                                                       no_persistent_cache):
    """AlexNet's 11x11 stride-4 stem compiles as a 3x3 conv over the
    48-channel folded input: one kernel, named and recorded for the
    published span, asking for less VMEM than the un-rewritten kernel
    (3 channels pad to 128 lanes in its input blocks and ring)."""
    net = zoo.get_network("alexnet")
    calls = _kernel_calls(_compile_step(net, a, b, one_chip))
    assert len(calls) == 1
    name, vmem = calls[0]
    assert re.fullmatch(rf"_span_pallas_{a}_{b}(\.\d+)?", name), name
    assert ("alexnet", a, b, False) in LOWERINGS
    assert ("alexnet", a, b, 4, 48) in INPUT_REWRITES
    args, kw = _lower_args(net, a, b, one_chip)
    published = _kernel_calls(_span_pallas.lower(*args, **kw).compile()
                              .as_text())
    assert vmem < published[0][1]
