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

import jax
import jax.numpy as jnp
import pytest

from repro.core import closure
from repro.kernels.fused_span.kernel import _span_pallas
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
], ids=["stem-s4", "pool-s2", "residual-downsample", "conv-512ch",
        "alexnet-body-vmem"])
def test_span_kernel_compiles_for_v5e(name, a, b, one_chip,
                                      no_persistent_cache):
    net = zoo.get_network(name)
    args, kw = _lower_args(net, a, b, one_chip)
    compiled = _span_pallas.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
