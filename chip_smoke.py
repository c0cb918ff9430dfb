#!/usr/bin/env python3
"""Chip smoke test: the plan -> serve path on a TPU at AlexNet 227x227x3.

    python3 chip_smoke.py             # one chip: autoplan -> AsyncEngine
    python3 chip_smoke.py --chips 4   # four-stage STAP pipeline, four chips

The default phase plans AlexNet (``zoo.alexnet``, published input and
channel widths, seeded random weights) for one chip with
``occam.autoplan``, serves it through ``Frontier.serve`` -> ``AsyncEngine``
to two tenants with ragged requests, and checks every returned image
against ``cnn.reference_forward`` run on the chip at highest matmul
precision. ``--chips 4`` runs only the pipeline phase: the paper's 3 MB
plan (four spans) placed on four chips through ``StapRing``, compared with
the same plan deployed on one chip and with the reference.

It fails — non-zero exit, no result line — when JAX finds no TPU. The
last line of a passing run is one JSON object naming the device. Lines
before it are informational: times printed there include compilation and
are not a benchmark.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import occam  # noqa: E402
from repro.kernels.fused_span import kernel as span_kernel  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import cnn, zoo  # noqa: E402

SEED = 0

# One chip. The DP's capacity is the v5e's default scoped VMEM budget per
# kernel, 16 MiB, in fp32 elements: 16 * 2**20 / 4 = 4,194,304. AlexNet's
# whole body, span (0, 8), needs 2,989,051 (520,507 ring + 2,468,544
# filter elements), so the plan is that one span. What the kernel asks
# the compiler for is derived from its own buffers in their tiled layout
# (kernels/fused_span/kernel.py), not from this number.
VMEM_ELEMS_1 = 16 * 2**20 // 4

# Four chips: the paper's 3 MB per chip, 3 * 2**20 / 4 = 786,432 fp32
# elements. AlexNet then plans as four spans, (0,4) (4,5) (5,6) (6,8),
# where (5,6) is a lower-bound span (one layer larger than the capacity).
VMEM_ELEMS_4 = 3 * 2**20 // 4

ROUND_BATCH = 8
# (tenant, images): ragged sizes 1 and 3, then one full round; the last
# round is a masked partial one
REQUESTS = (("alice", 1), ("bob", 3), ("alice", ROUND_BATCH))

# Bound on max|y - ref| / max|ref|. The reference runs at highest
# precision; the kernel's conv dots and the oracle's lax.conv carry no
# precision argument, so at the chip's default they may round fp32
# operands to bf16 (relative rounding 2**-9 per operand, 2**-8 per
# product). Five conv layers, each adding at most that relative error to
# the map it writes: 5 * 2**-8 = 1.95e-2.
REF_BOUND = 2e-2
# Bound on the pipeline against the one-chip deployment of the same plan:
# the same span bodies on the same images, so only fp32 accumulation
# order may differ (2**-24 per add over <= 3,456-term dot products).
SAME_PLAN_BOUND = 1e-3


def rel_err(y, ref) -> float:
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


def make_requests(net, key):
    shape = net.map_shape(0)
    keys = jax.random.split(key, len(REQUESTS))
    return [(tenant, jax.random.normal(k, (n,) + shape, jnp.float32))
            for (tenant, n), k in zip(REQUESTS, keys)]


def reference(params, net):
    """Per-image oracle, vmapped, at highest matmul precision."""
    fn = jax.jit(jax.vmap(lambda p, x: cnn.reference_forward(p, x, net),
                          in_axes=(None, 0)))

    def run(xs):
        with jax.default_matmul_precision("highest"):
            return fn(params, xs)

    return run


def check_routes(plan) -> None:
    """Every span that is not a lower-bound span routes to the kernel."""
    fits = {(sp.start, sp.end): sp.fits for sp in plan.partition.spans}
    for r in plan.routes:
        lower_bound = not fits[(r.start, r.end)] and r.end - r.start == 1
        print(f"span ({r.start}, {r.end}) -> {r.route} ({r.reason})")
        if not lower_bound and r.route != "pallas":
            raise AssertionError(f"span ({r.start}, {r.end}) routes "
                                 f"{r.route}, not pallas")


def check_kernels() -> None:
    """Span kernels were lowered, and none in interpret mode."""
    lowered = span_kernel.LOWERINGS
    if not lowered:
        raise AssertionError("no span kernel was lowered")
    modes = sorted({interp for *_, interp in lowered})
    print(f"span kernels lowered: {sorted({(a, b) for _, a, b, _ in lowered})}"
          f", interpret={modes}")
    if modes != [False]:
        raise AssertionError(f"kernels lowered with interpret={modes}")
    print(f"space-to-depth inputs (net, a, b, stride, channels): "
          f"{sorted(set(span_kernel.INPUT_REWRITES))}")
    print(f"column selections per image (net, a, b, count): "
          f"{sorted(set(span_kernel.COLUMN_SELECTS))}")


async def serve_requests(engine, requests):
    """Submit every request, drain, and return outputs plus per-request
    wall seconds (submit to result, informational)."""
    async def timed(ticket):
        y = await ticket
        return y, time.perf_counter() - t0

    async with engine:
        t0 = time.perf_counter()
        waits = []
        for tenant, xs in requests:
            ticket = await engine.submit(xs, tenant=tenant)
            waits.append(asyncio.ensure_future(timed(ticket)))
        await engine.drain()
        outs, secs = zip(*await asyncio.gather(*waits))
        jax.block_until_ready(outs)
        report = engine.session.report()
        compiles = engine.compile_count
    return outs, secs, report, compiles


def check_served(engine, requests, ref_fn):
    outs, secs, report, compiles = asyncio.run(
        serve_requests(engine, requests))
    net = engine.deployment.plan.net
    worst = 0.0
    for (tenant, xs), y, s in zip(requests, outs, secs):
        err = rel_err(y, ref_fn(xs))
        worst = max(worst, err)
        print(f"request {tenant} x{xs.shape[0]}: out {tuple(y.shape)}, "
              f"rel err vs reference {err:.3e}, done after {s:.3f} s")
        if y.shape != (xs.shape[0],) + net.map_shape(net.n_layers):
            raise AssertionError(f"output shape {y.shape}")
        if not np.all(np.isfinite(np.asarray(y))):
            raise AssertionError("non-finite output")
    print(f"worst rel err vs reference {worst:.3e} (bound {REF_BOUND:g})")
    if worst > REF_BOUND:
        raise AssertionError(f"rel err {worst:.3e} > {REF_BOUND:g}")
    print(f"matches_prediction={report.matches_prediction} "
          f"compile_count={compiles}")
    if not report.matches_prediction:
        raise AssertionError("measured traffic != prediction")
    if compiles != 1:
        raise AssertionError(f"compile_count {compiles} != 1")
    check_kernels()
    return outs


def one_chip(net, params, requests, ref_fn) -> None:
    frontier = occam.autoplan(net, occam.Fleet(chips=1,
                                               vmem_elems=VMEM_ELEMS_1))
    cand = frontier.best()
    print(f"plan at {VMEM_ELEMS_1} elems: {cand.kind}, "
          f"{len(cand.plan.routes)} span(s)")
    check_routes(cand.plan)
    engine = frontier.serve(params, round_batch=ROUND_BATCH,
                            autoscale=False, audit="error")
    t0 = time.perf_counter()
    check_served(engine, requests, ref_fn)
    print(f"one-chip phase {time.perf_counter() - t0:.1f} s "
          f"(compile included)")


def four_chips(net, params, requests, ref_fn) -> None:
    n_chips = 4
    devices = jax.devices()
    if len(devices) < n_chips:
        raise AssertionError(f"{n_chips} devices needed, have "
                             f"{len(devices)}")
    frontier = occam.autoplan(net, occam.Fleet(chips=n_chips,
                                               vmem_elems=VMEM_ELEMS_4))
    cand = frontier.best("throughput")
    print(f"plan at {VMEM_ELEMS_4} elems: {cand.kind}, replicas "
          f"{cand.replicas}")
    check_routes(cand.plan)
    if cand.kind != occam.PIPELINE or cand.replicas != (1,) * n_chips:
        raise AssertionError(f"expected a {n_chips}-stage pipeline, got "
                             f"{cand.kind} {cand.replicas}")
    engine = frontier.serve(params, objective="throughput",
                            round_batch=ROUND_BATCH, autoscale=False,
                            audit="error")
    t0 = time.perf_counter()
    outs = check_served(engine, requests, ref_fn)
    print(f"pipeline phase {time.perf_counter() - t0:.1f} s "
          f"(compile included)")

    # the stages really sit on distinct devices, in ring order
    ring = engine.deployment.ring(engine.session.microbatch)
    stage_devs = list(ring.mesh.devices.flat)
    for i, d in enumerate(stage_devs):
        print(f"stage {i}: span {ring.stages[i].span} on device {d.id} "
              f"coords {getattr(d, 'coords', None)}")
    if len({d.id for d in stage_devs}) != n_chips:
        raise AssertionError(f"stages share devices: {stage_devs}")
    fed = engine.session.input_sharding.device_set
    print(f"round buffers placed on devices {sorted(d.id for d in fed)}")
    if fed != set(stage_devs):
        raise AssertionError(f"round buffer on {fed}, ring on {stage_devs}")

    # the same plan on one chip (device 0), same process
    single = cand.plan.place().compile()
    dev0 = devices[0]
    worst = 0.0
    for (_tenant, xs), y in zip(requests, outs):
        y1 = single.run(params, jax.device_put(xs, dev0))
        if y1.devices() != {dev0}:
            raise AssertionError(f"one-chip run left device 0: "
                                 f"{y1.devices()}")
        worst = max(worst, rel_err(y, y1))
    print(f"pipeline vs one-chip deployment on device {dev0.id}: worst rel "
          f"err {worst:.3e} (bound {SAME_PLAN_BOUND:g})")
    if worst > SAME_PLAN_BOUND:
        raise AssertionError(f"pipeline differs from the one-chip "
                             f"deployment: {worst:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: one-chip serve phase; 4: STAP pipeline phase")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache(ROOT)
    print(f"device: {dev.device_kind} x{len(jax.devices())}; compile cache "
          f"{cache}")

    net = zoo.alexnet()
    print(f"net: {net.name} input {net.map_shape(0)} -> output "
          f"{net.map_shape(net.n_layers)}, {net.n_layers} layers")
    key_p, key_x = jax.random.split(jax.random.PRNGKey(SEED))
    params = cnn.init_params(key_p, net)
    requests = make_requests(net, key_x)
    ref_fn = reference(params, net)
    if args.chips == 4:
        four_chips(net, params, requests, ref_fn)
    else:
        one_chip(net, params, requests, ref_fn)
    if os.path.isdir(cache):
        print(f"compile cache entries: {len(os.listdir(cache))}")
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
