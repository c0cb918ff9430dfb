"""Measured-cost planning (``occam.calibrate``): tick timers, the
sum-of-replicas packer (§III-E), cost-model fitting, plan schema v4
calibration blocks, deterministic frontier tie-breaking, frontier
re-scoring without re-running the DP, packed-ring serving, and the
per-stage utilization view in ``AsyncEngine.serving_stats()``."""
import asyncio
import itertools
import json
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from conftest import require_devices
from repro import occam
from repro.core.graph import chain
from repro.core.stap import StapPlan, steady_schedule
from repro.models import cnn
from repro.occam import search
from repro.occam.calibrate import (ChipAssignment, CostModel, StageProfile,
                                   TickTimers, pack_replicas,
                                   rescore_frontier)
from repro.occam.calibrate.cost_model import fit_cost_model
from repro.occam.calibrate.rescore import rescore_candidate

C, P = "conv", "pool"
CAPACITY = 6000


def _vgg(hw=16):
    specs = [(C, 3, 1, 1, 8), (C, 3, 1, 1, 8), (P, 2, 2, 0, 0),
             (C, 3, 1, 1, 16), (C, 3, 1, 1, 16), (P, 2, 2, 0, 0),
             (C, 3, 1, 1, 16)]
    return chain("vgg_mini", specs, in_h=hw, in_w=hw, in_ch=3)


def _ref(params, net, xs):
    return jax.vmap(lambda im: cnn.reference_forward(params, im, net))(xs)


def assert_close(got, ref):
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.fixture(scope="module")
def packed_case():
    """An unbalanced (3, 2, 1) pipeline on 6 packed chips (the rect mesh
    would need 9 — more than the emulated host has), shared across the
    packed-serving tests."""
    require_devices(6)
    net = _vgg()
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    plan = occam.plan(net, CAPACITY, batch=2)
    dep = plan.place(replicas=(3, 2, 1), microbatch=2,
                     packing="sum").compile()
    return net, params, plan, dep


# --------------------------------------------------------------------------
# TickTimers (pure host-side)
# --------------------------------------------------------------------------

def test_tick_timers_window_and_busy_fraction():
    now = [0.0]
    t = TickTimers(horizon_s=10.0, clock=lambda: now[0])
    assert t.window() == (0, 0.0)
    assert t.busy_fraction() == 0.0
    for _ in range(4):
        now[0] += 1.0
        t.record(0.5)
    assert t.count == 4 and t.total_s == pytest.approx(2.0)
    n, busy = t.window()
    assert n == 4 and busy == pytest.approx(2.0)
    assert t.mean_s() == pytest.approx(0.5)
    # observed span: from the first tick's start (t=0.5) to now (t=4)
    assert t.busy_fraction() == pytest.approx(2.0 / 3.5)
    # events roll off the horizon; lifetime totals do not
    now[0] = 100.0
    assert t.window() == (0, 0.0)
    assert t.count == 4 and t.total_s == pytest.approx(2.0)


# --------------------------------------------------------------------------
# The sum-of-replicas packer (§III-E)
# --------------------------------------------------------------------------

def test_pack_replicas_property_sweep():
    """For every replica vector up to 3 stages x 3 replicas: the packing
    occupies exactly sum(replicas) chips (never more than the rectangle),
    chip_of/stage_of are inverse bijections, every schedule slot has
    exactly one owner chip per stage, and every slot's hop routing is a
    permutation of the chips."""
    for n in (1, 2, 3):
        for reps in itertools.product((1, 2, 3), repeat=n):
            asg = pack_replicas(reps)
            assert asg.n_chips == sum(reps)
            assert asg.n_chips <= asg.rect_chips == n * max(reps)
            assert asg.chips_saved == asg.rect_chips - asg.n_chips
            chips = [asg.chip_of(s, r) for s in range(n)
                     for r in range(reps[s])]
            assert sorted(chips) == list(range(asg.n_chips))
            for s in range(n):
                for r in range(reps[s]):
                    assert asg.stage_of(asg.chip_of(s, r)) == s
            assert tuple(asg.stage_ids()) == tuple(
                asg.stage_of(c) for c in range(asg.n_chips))

            times = tuple(float(i + 1) for i in range(n))
            thr = 1.0 / max(t / r for t, r in zip(times, reps))
            steady = steady_schedule(
                StapPlan(times, reps, thr, sum(times), sum(reps)))
            owner = np.asarray(asg.owner_table(steady))
            assert owner.shape == (asg.n_chips, steady.round_width)
            for s in range(n):
                rows = [asg.chip_of(s, r) for r in range(reps[s])]
                # each slot owned by exactly one of the stage's chips
                assert (owner[rows].sum(axis=0) == 1).all()
            for w in range(steady.round_width):
                perm = asg.slot_perm(steady, w)
                assert len(perm) == n - 1      # one hop per crossed cut
                srcs = [a for a, _b in perm]
                dsts = [b for _a, b in perm]
                assert len(set(srcs)) == len(srcs)
                assert len(set(dsts)) == len(dsts)
                for i, (src, dst) in enumerate(perm):
                    # the slot's owner at stage i ships straight to the
                    # slot's owner at stage i+1
                    assert asg.stage_of(src) == i
                    assert asg.stage_of(dst) == i + 1
                    assert src == asg.chip_of(i, steady.replica_of(i, w))
                    assert dst == asg.chip_of(
                        i + 1, steady.replica_of(i + 1, w))


def test_pack_replicas_validates():
    with pytest.raises(ValueError):
        pack_replicas(())
    with pytest.raises(ValueError):
        pack_replicas((2, 0))
    asg = pack_replicas((2, 1))
    with pytest.raises(ValueError):
        asg.chip_of(1, 1)   # stage 1 has a single replica


# --------------------------------------------------------------------------
# Cost-model fitting + serialization
# --------------------------------------------------------------------------

def test_fit_recovers_affine_model_exactly():
    rate, ovh = 2.0e9, 1.5e-3
    macs = [1e9, 4e9, 9e9]
    secs = [m / rate + ovh for m in macs]
    cm = fit_cost_model(macs, secs, hop_seconds=2e-4, hop_elems=1000,
                        analytic_macs_per_s=1e12)
    assert cm.macs_per_s == pytest.approx(rate, rel=1e-9)
    assert cm.stage_overhead_s == pytest.approx(ovh, rel=1e-9)
    assert cm.link_s_per_elem == pytest.approx(2e-7)
    assert cm.residual == pytest.approx(0.0, abs=1e-9)
    assert cm.samples == 3
    assert cm.compute_overhead_factor == pytest.approx(1e12 / rate)
    assert cm.stage_seconds(2e9) == pytest.approx(2e9 / rate + ovh)
    assert cm.hop_seconds(500) == pytest.approx(1e-4)


def test_fit_degenerate_single_stage_falls_back():
    cm = fit_cost_model([1e9], [1.0])
    assert cm.macs_per_s == pytest.approx(1e9)
    assert cm.stage_overhead_s == 0.0


def test_cost_model_roundtrip_and_version_gate():
    cm = CostModel(macs_per_s=1e9, stage_overhead_s=1e-3,
                   link_s_per_elem=1e-8, hbm_elems_per_s=1e10,
                   analytic_macs_per_s=1e12, samples=3, residual=0.1)
    assert CostModel.from_dict(json.loads(json.dumps(cm.to_dict()))) == cm
    with pytest.raises(ValueError, match="newer"):
        CostModel.from_dict({"version": 99, "macs_per_s": 1e9})
    with pytest.raises(ValueError):
        CostModel(macs_per_s=0.0)


def test_stage_profile_roundtrip():
    prof = StageProfile(spans=((0, 3), (3, 7)), replicas=(2, 1),
                        stage_macs=(1e6, 2e6), stage_seconds=(1e-3, 2e-3),
                        payload_elems=(512,), hop_seconds=1e-4,
                        microbatch=2, round_batch=4, tick_mean_s=5e-3,
                        tick_count=7, tick_busy_fraction=0.5)
    assert StageProfile.from_dict(
        json.loads(json.dumps(prof.to_dict()))) == prof


# --------------------------------------------------------------------------
# Plan schema v4: the calibration block
# --------------------------------------------------------------------------

def test_plan_v4_calibration_roundtrip_both_ways():
    net = _vgg()
    plan = occam.plan(net, CAPACITY, batch=2)
    assert plan.calibration is None
    assert plan.to_dict()["calibration"] is None
    cm = CostModel(macs_per_s=1e9, stage_overhead_s=1e-3)
    cal = plan.with_calibration(cm)
    d = cal.to_dict()
    assert d["version"] == 5 and d["calibration"]["macs_per_s"] == 1e9
    loaded = occam.plan_from_json(cal.to_json())
    assert loaded.calibration == cm
    assert loaded.boundaries == plan.boundaries
    # downgrade direction: a v3 reader-shaped document (no calibration
    # entry) loads uncalibrated; a v3-stamped document IGNORES a stray
    # calibration key (the block is a v4 concept)
    d3 = cal.to_dict()
    d3["version"] = 3
    assert occam.plan_from_dict(d3).calibration is None
    d4 = cal.to_dict()
    del d4["calibration"]
    assert occam.plan_from_dict(d4).calibration is None


# --------------------------------------------------------------------------
# Deterministic frontier tie-breaking
# --------------------------------------------------------------------------

def test_frontier_tie_break_is_order_independent():
    """Candidates with byte-identical scores sort by structure (kind,
    replicas, boundaries), so best()/for_rate() never depend on
    enumeration order."""
    net = _vgg()
    fleet = occam.Fleet(chips=8, vmem_elems=CAPACITY)
    plan = search._make_plan(net, CAPACITY, 1,
                             occam.plan(net, CAPACITY).partition, fleet)
    kw = dict(plan=plan, kind=occam.PIPELINE, stage_times=(1.0, 1.0, 1.0),
              traffic=100.0, period=0.5, fill_latency=2.0, chips=4)
    a = search.Candidate(replicas=(1, 1, 2), **kw)
    b = search.Candidate(replicas=(2, 1, 1), **kw)
    for order in ((a, b), (b, a)):
        f = search.Frontier(fleet, "throughput", tuple(order))
        assert f.best().replicas == (1, 1, 2)
        assert f.for_rate(1.0).replicas == (1, 1, 2)
        assert f.for_rate(1e9).replicas == (1, 1, 2)


# --------------------------------------------------------------------------
# Re-scoring: measured rates re-rank the frontier, DP never re-runs
# --------------------------------------------------------------------------

def test_rescore_flips_winner_without_rerunning_dp(monkeypatch):
    """Golden flip: analytically the deep-replica (8,4,1) vector wins
    throughput; under a measured 7s per-stage overhead the balanced
    (4,4,4) vector must win (overhead amortizes over replicas). The DP
    is monkeypatched to explode — re-scoring never reaches it."""
    net = _vgg()
    fleet = occam.Fleet(chips=13, vmem_elems=CAPACITY, macs_per_s=1e9)
    plan = search._make_plan(net, CAPACITY, 1,
                             occam.plan(net, CAPACITY).partition, fleet)
    macs = (8e9, 4e9, 1e9)
    a = search._score(net, plan, fleet, occam.PIPELINE, (8, 4, 1), macs)
    b = search._score(net, plan, fleet, occam.PIPELINE, (4, 4, 4), macs)
    assert a.period == pytest.approx(1.0)
    assert b.period == pytest.approx(2.0)
    assert a.chips == 13 and b.chips == 12    # sum, not rectangles
    frontier = search.Frontier(fleet, "throughput", (a, b))
    assert frontier.best().replicas == (8, 4, 1)

    def boom(*_a, **_k):  # pragma: no cover - must never run
        raise AssertionError("rescore re-ran the DP")

    monkeypatch.setattr("repro.core.partition.optimal_partition", boom)
    cm = CostModel(macs_per_s=1e9, stage_overhead_s=7.0)
    f2 = frontier.rescore(cm)
    best = f2.best()
    assert best.replicas == (4, 4, 4)
    assert best.period == pytest.approx(15.0 / 4)   # (4e9/1e9 + 7) / 4
    assert best.traffic == a.traffic                # placement facts fixed
    assert best.plan.calibration is cm              # provenance attached
    assert f2.stats["calibration"]["stage_overhead_s"] == 7.0
    # (8,4,1) is now dominated (slower AND more chips) and drops
    assert all(c.replicas != (8, 4, 1) for c in f2)
    # the rescored frontier ships with per-plan calibration blocks
    f3 = search.frontier_from_json(f2.to_json())
    assert f3.best().plan.calibration == cm


def test_rescore_single_applies_measured_hbm_floor():
    net = _vgg()
    fleet = occam.Fleet(chips=1, vmem_elems=CAPACITY, macs_per_s=1e9)
    plan = search._make_plan(net, CAPACITY, 1,
                             occam.plan(net, CAPACITY).partition, fleet)
    cand = search._score(net, plan, fleet, occam.SINGLE, (1, 1, 1),
                         (1e9, 1e9, 1e9))
    slow_hbm = CostModel(macs_per_s=1e9, hbm_elems_per_s=1.0)
    r = rescore_candidate(cand, slow_hbm)
    assert r.period == pytest.approx(cand.traffic)  # elems / 1 elem-per-s
    fast_hbm = CostModel(macs_per_s=1e9)
    assert rescore_candidate(cand, fast_hbm).period == pytest.approx(3.0)


# --------------------------------------------------------------------------
# Sum-of-replicas placement: search accounting + packed serving
# --------------------------------------------------------------------------

def test_autoplan_accounts_chips_as_sum_of_replicas():
    net = _vgg()
    fleet = occam.Fleet(chips=6, vmem_elems=CAPACITY)
    frontier = occam.autoplan(net, fleet, batch=2)
    pipes = [c for c in frontier if c.kind == occam.PIPELINE]
    assert pipes
    for c in pipes:
        assert c.chips == sum(c.replicas) <= fleet.chips
    unbalanced = [c for c in pipes
                  if sum(c.replicas) < len(c.replicas) * max(c.replicas)]
    for c in unbalanced:
        assert c.placement().packing == "sum"
        assert c.placement().devices_needed == sum(c.replicas)


def test_fleet_max_replicas_packings():
    fleet = occam.Fleet(chips=9, vmem_elems=CAPACITY)
    assert fleet.max_replicas(3) == 3               # 3x3 rectangle
    assert fleet.max_replicas(3, packing="sum") == 7  # 1+1+7
    assert fleet.max_replicas(10) == 0
    assert fleet.max_replicas(10, packing="sum") == 0


def test_single_placement_rejects_sum_packing():
    net = _vgg()
    plan = occam.plan(net, CAPACITY)
    with pytest.raises(ValueError, match="pipeline"):
        plan.place(packing="sum")
    with pytest.raises(ValueError, match="packing"):
        plan.place(chips=4, packing="diagonal")


def test_packed_ring_serves_unbalanced_plan_exactly(packed_case):
    """(3,2,1) on 6 chips: outputs bit-match the single-chip reference,
    measured traffic matches the plan prediction, ONE lowering serves
    the stream, and the partition is the rect plan's partition."""
    net, params, plan, dep = packed_case
    assert dep.placement.packing == "sum"
    assert dep.placement.devices_needed == 6
    assert dep.placement.chips == 6
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (24,) + net.map_shape(0))
    with dep.serve(params) as s:
        t1 = s.submit(xs[:10])
        t2 = s.submit(xs[10:])
        done = dict((tk.uid, y) for tk, y in s.results())
        got = np.concatenate([done[t1.uid], done[t2.uid]])
        assert s.compile_count == 1
        # the ticks' device times reach the timers once they finish
        rep = s.sync().report()
    assert_close(got, _ref(params, net, xs))
    assert rep.matches_prediction
    ring = dep.ring(2)
    r = ring.report()
    assert r["packing"] == "sum" and r["mesh_shape"] == [6]
    assert r["replicas"] == [3, 2, 1] and r["chips"] == 6
    # same partition as any other placement of this plan
    assert plan.boundaries == occam.plan(net, CAPACITY, batch=2).boundaries
    # serving ticked the ring timers
    assert ring.timers.count > 0
    assert rep.timing is not None and rep.timing["tick_count"] > 0


def test_profile_and_calibrate_packed_deployment(packed_case):
    net, params, plan, dep = packed_case
    prof = dep.profile(params, iters=2)
    assert prof.replicas == (3, 2, 1)
    assert len(prof.stage_seconds) == 3 == len(prof.spans)
    assert all(t > 0 for t in prof.stage_seconds)
    assert len(prof.payload_elems) == 2
    assert prof.hop_seconds > 0          # a real boundary hop was timed
    assert StageProfile.from_dict(prof.to_dict()) == prof
    cm = occam.calibrate(dep, params, rounds=2)
    assert cm.macs_per_s > 0 and cm.samples == 3
    assert cm.link_s_per_elem >= 0
    assert cm.compute_overhead_factor > 1.0   # CPU sits under the paper's
    assert cm.stage_seconds(1e6) > 0          # scaled-slice roofline


def test_rescore_preserves_deployment_cache(packed_case):
    """A re-scored winner re-deploys from the original candidate's
    cache — no recompile — and the cached deployment re-points at the
    rescored candidate/frontier."""
    net, params, _plan, _dep = packed_case
    fleet = occam.Fleet(chips=6, vmem_elems=CAPACITY)
    frontier = occam.autoplan(net, fleet, batch=2)
    best = frontier.best()
    dep = best.deploy()
    cm = CostModel(macs_per_s=1e9, stage_overhead_s=1e-6)
    f2 = frontier.rescore(cm)
    twin = next(c for c in f2
                if c.kind == best.kind and c.replicas == best.replicas
                and c.plan.boundaries == best.plan.boundaries)
    dep2 = twin.deploy()
    assert dep2 is dep                    # cache hit, zero lowerings
    assert dep2.candidate is twin
    assert dep2.frontier is f2


# --------------------------------------------------------------------------
# AsyncEngine utilization view
# --------------------------------------------------------------------------

def test_engine_serving_stats_utilization(packed_case):
    net, params, _plan, dep = packed_case

    async def drive():
        eng = occam.AsyncEngine(dep, params)
        async with eng:
            xs = jax.random.normal(jax.random.PRNGKey(2),
                                   (36,) + net.map_shape(0))
            y = await (await eng.submit(xs))
            assert y.shape[0] == 36
            return eng.serving_stats()

    stats = asyncio.run(drive())
    assert set(stats) >= {"pending_lanes", "rounds_served", "utilization"}
    util = stats["utilization"]
    assert len(util) == 3                 # one entry per pipeline stage
    assert all(0.0 <= u <= 1.0 for u in util)
    assert max(util) > 0.0                # traffic ran; timers ticked
    # the bottleneck stage carries the ring's full duty cycle
    plan = dep.placement.stap
    per = [t / r for t, r in zip(plan.stage_times, plan.replicas)]
    assert util[per.index(max(per))] == pytest.approx(max(util))


# --------------------------------------------------------------------------
# Acceptance (slow): 4-3-2 on nine chips, calibrate-vs-measured band
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_four_three_two_serves_on_nine_chips():
    """The paper's sum-of-replicas example: a 4-3-2 plan occupies 9
    chips (the rect mesh would need 12). Needs a 9-device host, so it
    runs in a subprocess with its own XLA override."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=9"
        import jax, numpy as np
        from repro import occam
        from repro.core.graph import chain
        from repro.models import cnn

        specs = [("conv", 3, 1, 1, 8), ("conv", 3, 1, 1, 8),
                 ("pool", 2, 2, 0, 0), ("conv", 3, 1, 1, 16),
                 ("conv", 3, 1, 1, 16), ("pool", 2, 2, 0, 0),
                 ("conv", 3, 1, 1, 16)]
        net = chain("vgg_mini", specs, in_h=16, in_w=16, in_ch=3)
        params = cnn.init_params(jax.random.PRNGKey(0), net)
        plan = occam.plan(net, 6000, batch=1)
        rect = occam.plan(net, 6000, batch=1)
        dep = plan.place(replicas=(4, 3, 2), packing="sum").compile()
        assert dep.placement.devices_needed == 9, dep.placement
        assert dep.placement.chips == 9
        assert plan.boundaries == rect.boundaries   # partition unchanged
        xs = jax.random.normal(jax.random.PRNGKey(1),
                               (24,) + net.map_shape(0))
        ref = jax.vmap(lambda im: cnn.reference_forward(params, im,
                                                        net))(xs)
        with dep.serve(params) as s:
            s.submit(xs)
            [(t, y)] = s.results()
            rep = s.report()
            assert s.compile_count == 1, s.compile_count
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        assert rep.matches_prediction
        print("NINE-CHIP OK")
    """)
    env = dict(**__import__("os").environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "NINE-CHIP OK" in out.stdout


@pytest.mark.slow
def test_calibrated_period_within_band_of_measured(packed_case):
    """Acceptance: the re-scored winner's period sits within a (loose,
    CPU-noise-tolerant) band of the steady serving rate actually
    measured — the analytic roofline misses by orders of magnitude on
    this host; the calibrated model must not."""
    import time

    net, params, _plan, dep = packed_case
    fleet = occam.Fleet(chips=6, vmem_elems=CAPACITY)
    frontier = occam.autoplan(net, fleet, batch=2)
    cm = occam.calibrate(dep, params, rounds=3)
    best = frontier.rescore(cm).best()
    bdep = best.deploy()
    xs = jax.random.normal(jax.random.PRNGKey(3),
                           (bdep.placement.serve_geometry(None)[0] * 8,)
                           + net.map_shape(0))
    with bdep.serve(params) as s:
        s.submit(xs)          # warm the lowering
        s.results()
        t0 = time.perf_counter()
        s.submit(xs)
        s.results()
        s.sync()
        measured = (time.perf_counter() - t0) / xs.shape[0]
    analytic_period = next(
        c for c in frontier
        if c.kind == best.kind and c.replicas == best.replicas
        and c.plan.boundaries == best.plan.boundaries).period
    # the calibrated prediction must land within 10x of the machine;
    # the analytic roofline is off by >100x on emulated CPU devices
    assert best.period == pytest.approx(measured, rel=9.0)
    assert measured / analytic_period > 100.0
