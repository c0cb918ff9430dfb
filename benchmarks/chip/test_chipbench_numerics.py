"""The yardstick itself: operation and byte counts, the plain reference,
its lower-precision controls, and the comparison that decides
``correct``."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchspec
import devicepeaks
import netref
import opcount


def _config(name):
    bench = benchspec.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[name]
    return benchspec._load_json(os.path.join(benchspec.ROOT, entry["file"]))


SMALL = {"name": "small", "in_h": 19, "in_w": 19, "in_ch": 3,
         "layers": [["conv", 5, 2, 1, 8], ["pool", 3, 2, 1, 8],
                    ["conv", 3, 1, 1, 16], ["conv", 3, 1, 1, 8],
                    ["pool", 2, 2, 0, 8]],
         "residual_edges": []}
RESIDUAL = {"name": "res", "in_h": 16, "in_w": 16, "in_ch": 4,
            "layers": [["conv", 3, 1, 1, 8], ["conv", 3, 1, 1, 8],
                       ["conv", 3, 2, 1, 16], ["conv", 3, 1, 1, 16]],
            "residual_edges": [[1, 2], [2, 4]]}


@pytest.mark.parametrize("name,macs", [("alexnet", 655_566_528),
                                       ("vgg19", 19_508_428_800)])
def test_op_count_matches_the_plan(name, macs):
    from repro import occam
    from repro.core.graph import net_from_dict

    net = _config(name)["net"]
    plan = occam.plan(net_from_dict(net), 4_194_304)
    assert plan.predicted.compute_macs == macs
    assert netref.conv_macs(net) == macs
    assert opcount.span_flops(net, 0, len(net["layers"]), 8) == 16 * macs


def test_one_span_by_hand():
    """AlexNet span (0, 4): conv1 and conv2 with their pools."""
    net = _config("alexnet")["net"]
    conv1 = 55 * 55 * 64 * 11 * 11 * 3
    conv2 = 27 * 27 * 192 * 5 * 5 * 64
    assert opcount.span_flops(net, 0, 4, 8) == 2 * (conv1 + conv2) * 8
    per_image = 227 * 227 * 3 + 13 * 13 * 192
    weights = 11 * 11 * 3 * 64 + 64 + 5 * 5 * 64 * 192 + 192
    assert opcount.span_bytes(net, 0, 4, 8) == 4 * (8 * per_image + weights)
    peaks = devicepeaks.peaks("TPU v5 lite")
    t, bound = opcount.least_time_s(opcount.span_flops(net, 0, 4, 8),
                                    opcount.span_bytes(net, 0, 4, 8), peaks)
    assert bound == "compute"
    assert t == pytest.approx(2 * (conv1 + conv2) * 8 / 197e12)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        devicepeaks.peaks("TPU v4")


@pytest.mark.parametrize("net", [SMALL, RESIDUAL], ids=["chain", "residual"])
def test_reference_matches_the_repository_oracle(net):
    from repro.core.graph import net_from_dict
    from repro.models import cnn

    params = netref.make_params(net, jax.random.key(3))
    pool = netref.make_pool(net, 3, np.random.default_rng(1))
    ours = netref.reference_outputs(params, pool, net, block=2)
    spec = net_from_dict(net)
    with jax.default_matmul_precision("highest"):
        theirs = np.stack([np.asarray(cnn.reference_forward(
            params, jnp.asarray(x), spec)) for x in pool])
    assert ours.shape == (3,) + netref.map_shape(net, len(net["layers"]))
    assert netref.rel_err(theirs, ours) < 1e-5


def test_params_are_made_from_the_seed():
    a = netref.make_params(SMALL, jax.random.key(5))
    b = netref.make_params(SMALL, jax.random.key(5))
    c = netref.make_params(SMALL, jax.random.key(6))
    assert all(np.array_equal(x["w"], y["w"]) for x, y in zip(a, b) if x)
    assert not np.array_equal(a[0]["w"], c[0]["w"])
    assert a[1] == {} and a[0]["w"].shape == (5, 5, 3, 8)


def test_envelope_reads_the_worst_served_output():
    ref = np.random.default_rng(0).standard_normal((4, 3, 3, 2))
    env = netref.Envelope(4, (3, 3, 2))
    env.fold([0, 1], ref[[0, 1]].astype(np.float32))
    env.fold([1, 1], ref[[1, 1]].astype(np.float32))
    assert env.worst_rel_err(ref) < 1e-6
    bad = ref[[2]].astype(np.float32)
    bad[0, 1, 1, 0] += 0.5 * np.abs(ref[2]).max()
    env.fold([2], bad)
    assert env.worst_rel_err(ref) == pytest.approx(0.5, rel=1e-5)
    env.fold([3], np.full((1, 3, 3, 2), np.nan, np.float32))
    assert env.worst_rel_err(ref) == float("inf")
    env.fold([0], np.zeros((1, 3, 2, 2), np.float32))
    assert env.bad_shapes == 1
    assert list(env.served) == [1, 3, 1, 1]


@pytest.mark.parametrize("name", ["alexnet", "vgg19"])
def test_fp8_control_fails_the_limit(name):
    """The control, the reference with fp8 operands, is not correct at
    the configuration's limit, while bf16 operands (what the chip's
    default precision gives the program's fp32 dots) pass it. At the
    configuration's own widths on a 67x67 input, so the test runs on the
    CPU; the readings at the cells' own sizes are in PERF.md."""
    cfg = _config(name)
    net = dict(cfg["net"], in_h=67, in_w=67)
    params = netref.make_params(net, jax.random.key(11))
    pool = netref.make_pool(net, 2, np.random.default_rng(11))
    ref = netref.reference_outputs(params, pool, net, block=2)
    fp8 = netref.reference_outputs(params, pool, net, "fp8", block=2)
    bf16 = netref.reference_outputs(params, pool, net, "bf16", block=2)
    limit = cfg["check"]["max_rel_err"]
    assert netref.rel_err(fp8, ref) > limit
    assert netref.rel_err(bf16, ref) < limit
