"""Async-engine benchmark: saturated continuous-batching throughput vs
the steady-tick prediction, plus ticket latency under Poisson arrivals.

Where ``benchmarks/occam_serve.py`` hand-pumps a session with
back-to-back submits, this drives the same replicated deployment through
``occam.serve.AsyncEngine`` — the admission queue, round packer,
double-buffered staging and asyncio loop all sit between the caller and
the compiled tick, and the measurement answers two questions:

* **Saturation**: with the queue kept full, does engine throughput stay
  on the steady-tick prediction (the orchestration layer must cost ~0 —
  ticks dispatch asynchronously while the host packs the next round)?
  Timed between ticket completions at steady state, paired-calibration
  best-of windows exactly as the serve benchmark.
* **Latency under load**: a Poisson arrival sweep at fractions of the
  predicted capacity; each rate reports achieved arrival rate, round
  occupancy and p50/p99 ticket latency from the engine's own metrics
  ring (fresh engine per rate — they share ONE compiled ring, which the
  result row asserts via ``engine_compile_count``).

Writes machine-readable results to ``results/BENCH_async.json``:

    PYTHONPATH=src python -m benchmarks.occam_async       # direct
    PYTHONPATH=src python -m benchmarks.run               # via harness
"""
from __future__ import annotations

import asyncio
import json
import os
import statistics
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OUT = os.path.join(_ROOT, "results", "BENCH_async.json")

ROUNDS_TIMED = 24   # steady-state ticket completions per timed window
PREFILL = 8         # tickets resolved before the window opens
REPS = 3
POISSON_FRACS = (0.5, 0.8)   # arrival rate as a fraction of capacity
POISSON_REQUESTS = 32


def occam_async():
    """Harness entry (`benchmarks.run`): spawn the flagged subprocess and
    report measured/predicted saturated engine throughput (1.0 = exact)."""
    from benchmarks.emulated import run_child

    row = run_child("benchmarks.occam_async", _OUT)
    return [row], row["async_thr_measured_over_predicted"]


async def _saturated_window(eng, xs_round, prefill: int,
                            rounds_timed: int) -> float:
    """Seconds for ``rounds_timed`` steady-state round completions:
    submit prefill+timed full-round requests back to back (the engine
    double-buffers packing against the in-flight tick), then clock the
    span between the *data* of ticket ``prefill-1`` and of the last
    ticket materializing. The block_until_ready calls are the point:
    tickets resolve on host-side delivery while JAX's async dispatch is
    still computing the arrays, so future-resolution timestamps would
    measure bookkeeping, not device work. Completions are FIFO and
    every round is already dispatched by the first block, so the span
    is exactly ``rounds_timed`` round exits with every stage busy."""
    import jax

    tickets = [await eng.submit(xs_round)
               for _ in range(prefill + rounds_timed)]
    jax.block_until_ready(await tickets[prefill - 1])
    t0 = time.perf_counter()
    jax.block_until_ready(await tickets[-1])
    return time.perf_counter() - t0


async def _poisson_sweep(dep, params, xs_round, frac: float,
                         predicted_rate: float, n_requests: int) -> dict:
    """One Poisson arrival rate: round-sized requests at exponential
    inter-arrival gaps targeting ``frac`` of predicted capacity; report
    the engine's own metrics (achieved rate, occupancy, p50/p99)."""
    import jax
    import numpy as np

    from repro.occam.serve import AsyncEngine

    rb = xs_round.shape[0]
    target = frac * predicted_rate                  # images/s
    rng = np.random.default_rng(int(frac * 1000))
    gaps = rng.exponential(rb / target, n_requests)
    eng = AsyncEngine(dep, params, max_pending=1 << 20, max_wait_ms=50.0,
                      metrics_window_ms=100.0)
    arrivals = np.cumsum(gaps)          # absolute schedule: open-loop
    async with eng:                      # rate independent of service time
        t0 = time.perf_counter()
        tickets = []
        for a in arrivals:
            lead = float(a) - (time.perf_counter() - t0)
            if lead > 0:
                await asyncio.sleep(lead)
            tickets.append(await eng.submit(xs_round))
        # block on the data, not just ticket resolution (async dispatch)
        jax.block_until_ready(await asyncio.gather(*tickets))
        wall = time.perf_counter() - t0
        snap = eng.metrics.snapshot()
        compile_count = eng.compile_count
    images = n_requests * rb
    return {
        "rate_frac": frac,
        "target_images_per_s": round(target, 1),
        "achieved_images_per_s": round(images / wall, 1),
        "round_occupancy": snap["round_occupancy"],
        "latency_p50_ms": None if snap["latency_p50_s"] is None
        else round(snap["latency_p50_s"] * 1e3, 2),
        "latency_p99_ms": None if snap["latency_p99_s"] is None
        else round(snap["latency_p99_s"] * 1e3, 2),
        "engine_compile_count": compile_count,
    }


def async_measurement(rounds_timed: int = ROUNDS_TIMED, reps: int = REPS,
                      prefill: int = PREFILL,
                      poisson_fracs=POISSON_FRACS,
                      poisson_requests: int = POISSON_REQUESTS) -> dict:
    """One in-process measurement (devices must already be available):
    same replicated deployment as the serve benchmark, driven through
    ``AsyncEngine`` — saturated best-of windows against the steady-tick
    prediction, then the Poisson latency sweep. Returns the result row."""
    import jax

    from benchmarks.occam_stap import (CAPACITY, HW, MICROBATCH,
                                       bench_case, stage_timers)
    from repro import occam
    from repro.models import cnn
    from repro.occam.serve import AsyncEngine

    net, res = bench_case()
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    plan = occam.plan(net, CAPACITY, batch=MICROBATCH)
    assert plan.boundaries == list(res.boundaries)
    s = plan.n_spans

    unrep = plan.place(pipeline=True, microbatch=MICROBATCH).compile() \
        .pipeline(8)
    solo_sampler = stage_timers(unrep, params)
    t_solo = tuple(statistics.median(ts) for ts in
                   zip(*(solo_sampler() for _ in range(3))))
    place = plan.place(chips=s + 1, stage_times=t_solo,
                       max_replicas=jax.device_count() // s,
                       microbatch=MICROBATCH)
    steady = place.steady_schedule()
    dep = place.compile()

    key = jax.random.PRNGKey(1)
    xs = jax.random.normal(key, (1, HW, HW, 3))
    rb = place.serve_geometry(None)[0]
    xs_round = jax.random.normal(key, (rb, HW, HW, 3))
    dep_sampler = stage_timers(unrep, params, replicas=place.stap.replicas)

    async def drive() -> dict:
        # max_wait_ms: the warmup's sub-round sizes (1, 3, 2*rb+1) leave
        # partial rounds that must age out — without an SLO they wait
        # for more traffic forever. Saturated windows use full rounds
        # only, so the SLO never touches the timed path.
        eng = AsyncEngine(dep, params, max_pending=1 << 20,
                          max_wait_ms=20.0, metrics_window_ms=100.0)
        async with eng:
            # warm across MIXED request sizes — the zero-new-lowerings
            # guarantee is part of what this benchmark records
            for size in (1, 3, rb, 2 * rb + 1):
                x = jax.random.normal(key, (size, HW, HW, 3))
                await (await eng.submit(x))
            compile_count = eng.compile_count
            # paired calibration best-of, as in benchmarks/occam_serve.py:
            # the CI host's CPU grant is bursty; each window pairs with a
            # calibration sampled right before it, closest-to-1 reported
            windows, best = [], None
            for _ in range(max(reps, 1) * 2):
                t_dep = dep_sampler()
                wall = await _saturated_window(eng, xs_round, prefill,
                                               rounds_timed)
                ratio = wall / (rounds_timed * steady.steady_tick_time(t_dep))
                windows.append(ratio)
                if best is None or abs(ratio - 1) < abs(best[0] - 1):
                    best = (ratio, t_dep, wall)
                if len(windows) >= reps and abs(best[0] - 1) <= 0.25:
                    break
            overlapped = eng.packs_overlapped
        ratio, t_dep, wall = best
        predicted_rate = rb / steady.steady_tick_time(t_dep)
        sweep = [await _poisson_sweep(dep, params, xs_round, frac,
                                      predicted_rate, poisson_requests)
                 for frac in poisson_fracs]
        images = rounds_timed * rb
        from benchmarks.audit_stamp import audit_verdict

        return {
            "audit": audit_verdict(place),
            "net": net.name, "hw": HW, "microbatch": MICROBATCH,
            "boundaries": list(res.boundaries),
            "replicas": list(place.stap.replicas),
            "chips": place.stap.chips,
            "round_batch": rb,
            "ring_depth": place.ring_depth,
            "rounds_timed": rounds_timed,
            "measurement_windows": len(windows),
            "window_ratios": [round(x, 3) for x in windows],
            "engine_compile_count": compile_count,
            "packs_overlapped": overlapped,
            "stage_times_deployed_ms": [round(t * 1e3, 2) for t in t_dep],
            "images_per_s_measured": round(images / wall, 1),
            "images_per_s_predicted_deployed": round(
                images / (rounds_timed * steady.steady_tick_time(t_dep)), 1),
            "us_per_image_async": round(wall / images * 1e6, 1),
            "async_thr_measured_over_predicted": round(1.0 / ratio, 3),
            "poisson": sweep,
        }

    return asyncio.run(drive())


def main() -> None:
    row = async_measurement()
    os.makedirs(os.path.dirname(_OUT), exist_ok=True)
    with open(_OUT, "w") as f:
        json.dump(row, f, indent=2)
    print(json.dumps(row, indent=2))


if __name__ == "__main__":
    from benchmarks.emulated import launch

    launch("benchmarks.occam_async", main)
