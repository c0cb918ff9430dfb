"""Run one cell once: plan, serve, drive the traffic for a window, check
what was served against the reference, and reduce it all to metrics.

The system under test is the normal serving path:
``occam.autoplan(net, Fleet)`` -> ``Frontier.serve(...)`` ->
``AsyncEngine.submit``. From it the harness takes the engine's outputs,
its plan and the span kernels' lowering record; everything else
(traffic, weights, reference, op counts, trace reduction) is the
benchmark's own.

A request's latency runs from when it was sent until its outputs are on
the host as numpy, materialised in a worker thread: a ticket resolves
when its round is dispatched, not when the device has finished it.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import dataclasses
import gc
import itertools
import os
import shutil
import sys
import time

import jax
import numpy as np

import benchspec
import devicepeaks
import loadgen
import netref
import tracereduce

DRAIN_S = 60.0          # how long past the close an answer may still come
# the profiled window of a --trace 1 run: long enough that the first
# round's start from an idle engine (~60 ms) is under 1% of it
TRACE_SECONDS = 10.0
MATERIALISE_THREADS = 4
# sizes the span kernels' compiled programs are keyed on never change
# inside a cell, so every program, however small, goes to the cache
MIN_CACHED_COMPILE_S = 0.0
# a traced window that completes images more slowly than this share of
# the measured window's rate ran on a slowed host (host tracing read
# 0.33): its device metrics would describe the profiler, and are left
# out of the result
TRACE_MIN_RATE_RATIO = 0.9
# the mode the chip lowers the span kernels in (interpret mode is the
# CPU's emulation, never what a chip serves)
KERNEL_INTERPRET = False


@dataclasses.dataclass
class Served:
    """One request as the load generator saw it."""
    n: int
    sent: float             # perf_counter when it was sent
    done: float | None = None
    status: str = "pending"  # ok | refused | error | lost


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""
    cell: benchspec.Cell
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    requests: list
    round_batch: int
    kernels: dict           # device plane -> [(a, b, batch, spill), ...]
    net: dict
    plan_offchip_bytes_per_image: float
    chips: int
    peaks: dict | None
    trace: dict | None = None       # of the traced window, after the main
    trace_window: tuple | None = None

    def completed_in_window(self) -> list:
        return [r for r in self.requests if r.status == "ok"
                and r.done is not None and r.done <= self.t_close]


# --------------------------------------------------------------------------
# Building the system under test
# --------------------------------------------------------------------------

def build(cell: benchspec.Cell, seed: int):
    """Weights, the image pool and the engine, from the seed."""
    from repro import occam
    from repro.core.graph import net_from_dict

    cfg, mix = cell.config, cell.traffic
    net = cfg["net"]
    params = netref.make_params(net, netref.key_from_seed(seed))
    jax.block_until_ready(params)
    pool = netref.make_pool(net, mix["pool"],
                            np.random.default_rng([seed, 0]))
    fleet = occam.Fleet(**cfg["fleet"])
    frontier = occam.autoplan(net_from_dict(net), fleet)
    engine = frontier.serve(
        params, objective=cfg.get("objective"),
        round_batch=cfg["round_batch"], autoscale=False, audit="error",
        max_pending=mix["max_pending"],
        max_wait_ms=mix["max_wait_ms"])
    return params, pool, engine


def _spill(net: dict, a: int, b: int) -> tuple[int, ...]:
    return tuple(sorted({s for s, t in net.get("residual_edges", ())
                         if a < s < b < t}))


def kernel_calls(engine, net: dict) -> dict:
    """Which span kernels the chip runs, and on how many images a call:
    ``{"/device:TPU:<id>": [(a, b, batch, spill), ...]}``."""
    dep = engine.deployment
    if dep.kind != "single":
        raise ValueError(f"the harness reads one-chip deployments; this "
                         f"configuration planned a {dep.kind!r} one")
    dev = jax.devices()[0]
    return {f"/device:TPU:{dev.id}":
            [(r.start, r.end, engine.round_batch, _spill(net, r.start, r.end))
             for r in dep.plan.routes if r.route == "pallas"]}


def plan_checks(plan, config: dict, lowerings) -> dict:
    """Exact checks of what was deployed against what the configuration
    states: the plan's spans and their routes, the plan's dtypes, and the
    mode every span kernel was lowered in (``lowerings``: the kernel's
    ``(net, a, b, interpret)`` record)."""
    routes = [[r.start, r.end, r.route] for r in plan.routes]
    differ = sum(1 for have, want in itertools.zip_longest(
        routes, config["routes"]) if have != want)
    q = plan.quant
    dtypes = [] if q is None else [q.weights, q.activations, q.boundary]
    lowered = {(a, b) for name, a, b, _ in lowerings
               if name == plan.net.name}
    return {
        "routes_not_as_configured": {"value": differ, "limit": 0},
        "dtypes_not_as_configured": {
            "value": sum(1 for d in dtypes if d != config["dtype"]),
            "limit": 0},
        "kernels_in_wrong_mode": {
            "value": sum(1 for *_, interp in lowerings
                         if interp != KERNEL_INTERPRET),
            "limit": 0},
        "pallas_spans_not_lowered": {
            "value": sum(1 for a, b, route in routes
                         if route == "pallas" and (a, b) not in lowered),
            "limit": 0},
    }


# --------------------------------------------------------------------------
# The window
# --------------------------------------------------------------------------

class Driver:
    """The load generator and the materialising threads of one run."""

    clock = staticmethod(time.perf_counter)

    def __init__(self, engine, pool, envelope):
        self.engine = engine
        self.pool = pool
        self.envelope = envelope
        self.requests: list[Served] = []
        self.errors: list[str] = []
        # (name, start, end) in time.time_ns while a traced window runs
        self.spans: list | None = None
        self._exec = concurrent.futures.ThreadPoolExecutor(
            MATERIALISE_THREADS, thread_name_prefix="materialise")

    def close(self) -> None:
        self._exec.shutdown(wait=True)

    @contextlib.contextmanager
    def _span(self, name: str):
        if self.spans is None:
            yield
            return
        start = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, start, time.time_ns()))

    def _materialise(self, y, idx):
        with self._span("bench.await_device"):
            y.block_until_ready()
        with self._span("bench.materialise"):
            self.envelope.fold(idx, np.asarray(y))

    async def send(self, req: loadgen.Request, record: bool = True) -> None:
        from repro.occam import AdmissionError

        rec = Served(len(req.pool_idx), self.clock())
        if record:
            self.requests.append(rec)
        loop = asyncio.get_running_loop()
        try:
            with self._span("bench.submit"):
                xs = self.pool[list(req.pool_idx)]
                ticket = await self.engine.submit(xs, tenant=req.tenant)
            y = await ticket
            await loop.run_in_executor(self._exec, self._materialise, y,
                                       req.pool_idx)
            rec.done = self.clock()
            rec.status = "ok"
        except AdmissionError:
            rec.status = "refused"
        except Exception as e:  # noqa: BLE001 - a failed request is counted
            rec.status = "error"
            self.errors.append(repr(e))

    async def closed_loop(self, mix: dict, seed: int, t_close: float) -> list:
        async def client(c):
            gen = loadgen.ClosedClient(mix, seed, c)
            while self.clock() < t_close:
                await self.send(gen.next())

        return [asyncio.create_task(client(c))
                for c in range(mix["clients"])]


async def warm(driver: Driver, cell, seed: int) -> None:
    """Every request size of the mix from every tenant, then one request
    per client, all through the engine."""
    reqs = loadgen.warm_requests(cell.traffic, seed)
    await asyncio.gather(*(driver.send(r, record=False) for r in reqs))
    gens = [loadgen.ClosedClient(cell.traffic, seed + 1, c)
            for c in range(cell.traffic["clients"])]
    await asyncio.gather(*(driver.send(g.next(), record=False)
                           for g in gens))


class GcTimer:
    """Python's collections while armed: count, total and longest pause."""

    def __init__(self):
        self.armed = False
        self.pauses: list[float] = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, _info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.armed and self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    ns_open: int            # time.time_ns, to place it on a trace
    ns_close: int
    compiles: int           # programs compiled or loaded inside it
    gc_pauses: list
    requests: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)

    def images_per_s(self) -> float:
        done = sum(r.n for r in self.requests if r.status == "ok"
                   and r.done is not None and r.done <= self.t_close)
        return done / (self.t_close - self.t_open)

    def longest_completion_gap_ms(self) -> float:
        """The longest time inside the window in which no request
        completed: a host stall shows here."""
        ts = sorted([self.t_open, self.t_close]
                    + [r.done for r in self.requests if r.done is not None
                       and self.t_open <= r.done <= self.t_close])
        return 1e3 * max(b - a for a, b in zip(ts, ts[1:]))


async def drive_window(driver: Driver, mix: dict, seed: int,
                       seconds: float, compiles: "CompileCounter",
                       gcs: GcTimer) -> Window:
    """Open the window on an idle engine, drive the mix for ``seconds``,
    close it, then wait up to ``DRAIN_S`` for the answers still due."""
    await driver.engine.drain()
    compiles.count, compiles.armed = 0, True
    gcs.pauses, gcs.armed = [], True
    ns_open = time.time_ns()
    t_open = driver.clock()
    t_close = t_open + seconds
    tasks = await driver.closed_loop(mix, seed, t_close)
    lead = t_close - driver.clock()
    if lead > 0:
        await asyncio.sleep(lead)
    ns_close = time.time_ns()
    compiles.armed = gcs.armed = False
    done, pending = await asyncio.wait(tasks, timeout=DRAIN_S)
    for t in pending:
        t.cancel()
    for t in done:
        t.result()
    for r in driver.requests:
        if r.status == "pending":
            r.status = "lost"
    return Window(t_open, t_close, ns_open, ns_close, compiles.count,
                  gcs.pauses, driver.requests)


def trace_options():
    """Device operations only: host tracing records the PJRT client's
    host-to-device transposes by the million and slowed the host
    threefold, so a window traced with it measured the profiler."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    return opts


class CompileCounter:
    """Compiles (and loads from the persistent cache) while armed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.armed = False
        self.count = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name")))


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------

def run_cell(cell: benchspec.Cell, seed: int, seconds: float, *,
             t_start: float, trace_dir: str | None = None,
             log=None) -> dict:
    """Run the cell once; returns the result line's fields."""
    from repro.kernels.fused_span import kernel

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    mix = cell.traffic
    if mix["loop"] != "closed":
        raise ValueError(f"mix loop {mix['loop']!r}: the generator drives "
                         f"closed loops")
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_CACHED_COMPILE_S)
    compiles = CompileCounter()
    gcs = GcTimer()
    net = cell.config["net"]
    params, pool, engine = build(cell, seed)
    dep = engine.deployment
    log(f"plan: {dep.kind}, spans "
        f"{[(r.start, r.end, r.route) for r in dep.plan.routes]}")
    out_shape = netref.map_shape(net, len(net["layers"]))
    envelope = netref.Envelope(mix["pool"], out_shape)
    driver = Driver(engine, pool, envelope)
    rb = engine.round_batch
    devices = jax.devices()
    platform = devices[0].platform
    kind = devices[0].device_kind

    async def main():
        async with engine:
            await warm(driver, cell, seed)
            # what set-up made lives on; the collector leaves it alone
            gc.freeze()
            win = await drive_window(driver, mix, seed, seconds, compiles,
                                     gcs)
            if trace_dir is None:
                return win, None
            # the device is traced over a short window of its own after
            # the measured one; the harness records its own spans
            main_requests, driver.requests = driver.requests, []
            driver.spans = []
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=trace_options())
            traced = await drive_window(driver, mix, seed + 1,
                                        min(seconds, TRACE_SECONDS),
                                        compiles, gcs)
            jax.profiler.stop_trace()
            traced.spans = driver.spans
            driver.requests, driver.spans = main_requests, None
            return win, traced

    win, traced = asyncio.run(main())
    t_open, t_close = win.t_open, win.t_close
    driver.close()
    gcs.close()
    setup_s = t_open - t_start
    gc_ms = [1e3 * p for p in win.gc_pauses]
    host = {"gc_collections": len(gc_ms), "gc_ms": sum(gc_ms),
            "gc_longest_ms": max(gc_ms, default=0.0),
            "longest_completion_gap_ms": win.longest_completion_gap_ms()}
    log(f"set-up {setup_s:.3f} s; window {seconds} s; compiles in window "
        f"{win.compiles} {sorted(set(compiles.names))}; host {host}")
    memory_peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        memory_peak = max(memory_peak, int(stats.get("peak_bytes_in_use", 0)))
    kernels = kernel_calls(engine, net)
    offchip = float(dep.plan.predicted.offchip_bytes)
    checks = plan_checks(dep.plan, cell.config, list(kernel.LOWERINGS))
    # free the system under test before the reference runs
    engine = dep = driver.engine = None
    gc.unfreeze()
    gc.collect()

    ref = netref.reference_outputs(params, pool, net)
    # nothing served compares as nothing right
    worst = envelope.worst_rel_err(ref) if envelope.served.any() \
        else float("inf")
    reqs = driver.requests
    sent = [r for r in reqs if r.sent < t_close]
    checked = reqs + (traced.requests if traced is not None else [])
    checks = {
        "max_rel_err": {"value": worst,
                        "limit": cell.config["check"]["max_rel_err"]},
        "lost_requests": {"value": sum(1 for r in checked
                                       if r.status == "lost"), "limit": 0},
        "error_requests": {"value": sum(1 for r in checked
                                        if r.status == "error"), "limit": 0},
        "bad_output_shapes": {"value": envelope.bad_shapes, "limit": 0},
        **checks,
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for e in driver.errors[:3]:
        log(f"request error: {e}")

    peaks = devicepeaks.PEAKS.get(kind)
    run = Run(cell=cell, seconds=seconds, setup_s=setup_s, t_open=t_open,
              t_close=t_close, requests=reqs, round_batch=rb,
              kernels=kernels, net=net,
              plan_offchip_bytes_per_image=offchip,
              chips=cell.chips, peaks=peaks)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(sent),
              "failed": sum(1 for r in sent if r.status != "ok")}
    breakdown = trace_info = None
    withheld = False
    if trace_dir is not None:
        if peaks is None:
            peaks = devicepeaks.peaks(kind)     # raises: unknown device
        trace = tracereduce.load(tracereduce.find_xplane(trace_dir))
        tracereduce.add_host_spans(trace, traced.spans + [
            (tracereduce.WINDOW_SPAN, traced.ns_open, traced.ns_close)])
        tracereduce.save(trace, os.path.join(trace_dir, "reduced.json"))
        lo, hi = tracereduce.window(trace)
        run.trace, run.trace_window = trace, (lo, hi)
        busy = [tracereduce.busy_ns(ops, lo, hi)
                for ops in trace["devices"].values()] or [0]
        device["busy_s"] = float(np.mean(busy)) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": tracereduce.top_ops(trace, lo, hi),
                     "idle_gaps": tracereduce.idle_gaps(trace, lo, hi)}
        ratio = traced.images_per_s() / max(win.images_per_s(), 1e-9)
        withheld = ratio < TRACE_MIN_RATE_RATIO
        trace_info = {"rate_ratio": ratio,
                      "device_metrics_withheld": withheld}
        log(f"traced window: {traced.images_per_s():.1f} images/s against "
            f"{win.images_per_s():.1f} measured (ratio {ratio:.4f}); "
            f"busy {device['busy_s']:.4f} of {device['window_s']:.4f} s"
            + ("; device metrics withheld" if withheld else ""))
        metric_entries = cell.per_layer
    else:
        metric_entries = cell.end_to_end
    metrics = {}
    for m in metric_entries:
        if withheld and m["source"] == "device_trace":
            continue
        value = benchspec.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
        result["trace"] = trace_info
    result["compiles_in_window"] = win.compiles
    result["host"] = host
    result["checks"] = checks
    return result


def clear_dir(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
