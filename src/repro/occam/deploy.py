"""Stages 3-4 of the deployment API: ``Placement.compile`` ->
:class:`Deployment` -> ``run`` / ``serve`` / ``report``.

Compiling binds the placement to engines through the registry: under
``backend="auto"`` each span keeps the route the planner picked; a forced
backend re-routes every span onto one engine (or raises
:class:`~repro.occam.registry.BackendError` if a span is ineligible —
never a silent substitution).

* Single-device deployments execute through
  ``repro.runtime.span_engine.execute_partition``.
* Pipeline deployments build (and cache, per stream batch size) a
  ``repro.runtime.stap_pipeline.StapPipeline`` over the placement's
  :class:`~repro.core.stap.StapPlan`. Stage bodies dispatch through the
  registry's ``make_spmd_body`` builders: kernel-routed spans run the
  fused Pallas kernel directly (interpret mode off TPU, the compiled
  kernel on real TPUs) — no scan substitution. Only the Python
  ``interpreted`` specification is rejected on pipeline placements (it
  cannot trace under SPMD).

Serving is a first-class surface, not a loop over ``run``:
``Deployment.serve()`` opens a :class:`Session` — a long-lived stream of
requests flowing through ONE compiled fixed shape. ``Session.submit``
packs ragged traffic into fixed ``round_batch`` rounds (a validity mask
covers the final partial round: masked lanes skip compute in the
pipeline, are dropped from outputs, and are excluded from measured
traffic), so mixed submit sizes never retrace. Pipeline sessions iterate
a single-tick :class:`~repro.runtime.stap_pipeline.StapRing` whose
per-chip buffers are O(round_batch) regardless of stream length.
``Session.pump`` exposes single-tick advancement to external drivers:
``occam.serve.AsyncEngine`` layers async continuous batching —
admission control, wall-clock SLOs, damped autoscaling — on that hook
without adding a single lowering.

Every ``run`` accumulates off-chip transfers into one
:class:`~repro.core.traffic.TrafficCounter`; ``report()`` returns the
plan's predicted per-image :class:`~repro.core.traffic.TrafficReport`
with the measurement attached — model vs machine in one object (sessions
carry their own, masked-lane-exact, measurement: ``Session.report``).
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import queue
import threading
import time
import weakref
from typing import TYPE_CHECKING, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.traffic import TrafficCounter, TrafficReport
from repro.models import cnn
from repro.runtime import span_engine

from . import registry
from .place import PIPELINE, SINGLE, Placement

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.runtime.stap_pipeline import StapPipeline, StapRing

    from .search import Candidate, Frontier

_log = logging.getLogger(__name__)


class Deployment:
    """A compiled, runnable placement. Build via ``Placement.compile``."""

    def __init__(self, placement: Placement, backend: str = registry.AUTO,
                 *, mesh=None, devices=None, interpret: bool | None = None):
        if backend != registry.AUTO:
            spec = registry.get_engine(backend)  # unknown names fail here
            if placement.kind == PIPELINE and not spec.spmd_capable:
                spmd = [registry.AUTO] + [e.name for e in
                                          registry.registered_engines()
                                          if e.spmd_capable]
                raise registry.BackendError(
                    f"backend {backend!r} cannot drive a pipeline "
                    f"placement (stage bodies run under shard_map; its "
                    f"EngineSpec is not spmd_capable — choose one of "
                    f"{spmd})")
        self.placement = placement
        self.plan = placement.plan
        self.backend = backend
        self.mesh = mesh
        self.devices = devices
        self.interpret = interpret
        # Forced backends re-route at compile time; BackendError surfaces
        # any span the engine cannot take. The plan's dtype policy rides
        # along so the forced engine's declared width envelope is honored
        # (spans compute in policy.compute — int8 boundaries dequantize
        # at span entry).
        quant = placement.plan.quant
        self.routes = self.plan.routes if backend == registry.AUTO else \
            span_engine.plan_routes(self.plan.net, self.plan.partition,
                                    backend=backend,
                                    out_rows=self.plan.out_rows,
                                    dtype=quant.compute if quant else None)
        self.counter = TrafficCounter()
        self._images = 0
        # set by Candidate.deploy: where this deployment sits on a
        # planning frontier (drives reconcile / Session.scale)
        self.candidate: "Candidate | None" = None
        self.frontier: "Frontier | None" = None
        self._pipes: dict[int, "StapPipeline"] = {}
        self._rings: dict[int, "StapRing"] = {}
        # single-device serving steps, one jit per round_batch; the dict
        # holds (fn, lowering-counter) pairs
        self._steps: dict[int, tuple] = {}
        self._per_image_cache: TrafficCounter | None = None

    # -- execution ----------------------------------------------------------

    @property
    def kind(self) -> str:
        return self.placement.kind

    def pipeline(self, batch: int) -> "StapPipeline":
        """The compiled STAP pipeline for streams of ``batch`` images
        (cached — repeated ``run`` calls at one batch size never
        retrace)."""
        from repro.runtime.stap_pipeline import StapPipeline

        if self.kind != PIPELINE:
            raise ValueError("single-device deployment has no pipeline; "
                             "use .run directly")
        pipe = self._pipes.get(batch)
        if pipe is None:
            pipe = StapPipeline(
                self.plan.net, self.plan.partition, batch,
                self.placement.microbatch, plan=self.placement.stap,
                mesh=self.mesh, devices=self.devices, routes=self.routes,
                out_rows=self.plan.out_rows, policy=self.plan.quant)
            self._pipes[batch] = pipe
        return pipe

    def ring(self, microbatch: int) -> "StapRing":
        """The compiled single-tick serving ring for ``microbatch`` images
        per slot (cached — every session at one round geometry shares ONE
        lowering)."""
        from repro.runtime.stap_pipeline import StapRing

        if self.kind != PIPELINE:
            raise ValueError("single-device deployment has no serving "
                             "ring; serve() runs whole rounds per tick")
        ring = self._rings.get(microbatch)
        if ring is None:
            ring = StapRing(
                self.plan.net, self.plan.partition, microbatch,
                plan=self.placement.stap, mesh=self.mesh,
                devices=self.devices, routes=self.routes,
                out_rows=self.plan.out_rows,
                packing=self.placement.packing, policy=self.plan.quant)
            self._rings[microbatch] = ring
        return ring

    def _per_image_profile(self) -> TrafficCounter:
        """Per-image transfer profile of this deployment's spans (cached —
        a pure function of the deployment; sessions scale it by their
        valid lanes for masked-lane accounting)."""
        if self._per_image_cache is None:
            from repro.runtime.stap_pipeline import plan_span_stages

            quant = self.plan.quant
            bpe = quant.boundary_bytes if quant is not None else 4.0
            per = TrafficCounter()
            for st in plan_span_stages(self.plan.net, self.plan.partition,
                                       routes=self.routes):
                a, b = st.span
                cnn.count_span_reads(per, self.plan.net, a, b, 1,
                                     bytes_per_elem=bpe)
                cnn.count_span_writes(per, self.plan.net, b, st.spill, 1,
                                      bytes_per_elem=bpe)
            self._per_image_cache = per
        return self._per_image_cache

    def _serve_step(self, round_batch: int):
        """SINGLE-kind serving step: one jitted whole-round execution at
        the fixed (round_batch, H, W, C) shape, cached per round_batch so
        every session at one geometry shares one lowering. Returns
        ``(fn, counts)`` where ``counts["lowerings"]`` increments at
        trace time (the one-compile regression signal)."""
        cached = self._steps.get(round_batch)
        if cached is not None:
            return cached
        counts = {"lowerings": 0}
        plan = self.plan

        # the function's name is the step's stable name in a profile
        def occam_serve_step(params, xs):
            counts["lowerings"] += 1
            return span_engine.execute_partition(
                params, xs, plan.net, plan.partition, counter=None,
                interpret=self.interpret, routes=self.routes,
                out_rows=plan.out_rows, policy=plan.quant)

        cached = (jax.jit(occam_serve_step), counts)
        self._steps[round_batch] = cached
        return cached

    def serve(self, params: Sequence[dict], *,
              round_batch: int | None = None,
              max_pending: int = 16,
              max_wait_ticks: int | None = None) -> "Session":
        """Open a continuous serving session (the steady-state surface).

        ``round_batch``: images per compiled round — the ONE fixed shape
        every request is packed into (default: the plan's recorded
        serving default, else round_width x the placement microbatch; for
        a pipeline it must be a multiple of the round width). Mixed
        ``submit`` sizes all serve from a single lowering; the final
        partial round of a flush is padded with masked lanes that skip
        compute, are dropped from outputs, and are excluded from measured
        traffic. ``max_pending``: completed rounds the session buffers
        before ``submit`` demands a ``results()`` drain (host-side
        backpressure). ``max_wait_ticks``: latency budget for sub-round
        traffic — a queued partial round auto-flushes once it has waited
        this many *subsequent* session ticks (``submit``/``ready``
        calls; the submit that starts the partial doesn't count, so
        later traffic always gets a chance to batch into it) without
        filling — a lone small request completes under polling without
        an explicit ``flush()``/``results()`` (default: wait
        indefinitely).

        Serving geometry is validated up front with clear errors —
        ``round_batch`` divisibility via ``Placement.serve_geometry``
        and the plan's recorded ``serving.ring_depth`` against the
        placement's actual ring — instead of surfacing as shape errors
        deep inside the compiled ring tick.
        """
        serving = self.plan.serving
        if (self.placement.kind == PIPELINE
                and serving.ring_depth is not None
                and serving.ring_depth != self.placement.ring_depth):
            raise ValueError(
                f"plan records serving.ring_depth {serving.ring_depth} "
                f"but this placement's ring is "
                f"{self.placement.ring_depth} rounds deep (one per "
                f"pipeline stage, {len(self.placement.replicas)} "
                f"stages); the plan document is stale or corrupted — "
                f"re-plan, or fix the serving block")
        # raises the serve_geometry ValueError here (with the offending
        # round_batch named) rather than mid-construction in StapRing
        self.placement.serve_geometry(round_batch)
        return Session(self, params, round_batch=round_batch,
                       max_pending=max_pending,
                       max_wait_ticks=max_wait_ticks)

    def reconcile(self, frontier: "Frontier | None" = None, *,
                  arrival_rate: float) -> "Deployment":
        """Serve-time autoscaling: the deployment for the cheapest
        frontier candidate meeting ``arrival_rate`` (images/s).

        Returns ``self`` when this deployment's own candidate is already
        the pick; otherwise the chosen candidate's (cached) deployment —
        compiled placements are reused per candidate, and the DP never
        re-runs (the frontier already holds every plan). ``frontier``
        defaults to the one this deployment was deployed from
        (``Candidate.deploy``).
        """
        f = frontier if frontier is not None else self.frontier
        if f is None:
            raise ValueError(
                "no frontier to reconcile against: deploy via "
                "occam.autoplan(...) -> Candidate.deploy(), or pass "
                "frontier=")
        cand = f.for_rate(arrival_rate)
        if self.candidate is not None and cand is self.candidate:
            return self
        # the pick inherits this deployment's bindings: same backend,
        # same interpret mode, same device pool. A pinned *mesh* cannot
        # carry over (its shape fits only this candidate's stage x
        # replica geometry) — its devices do.
        devices = self.devices
        if devices is None and self.mesh is not None:
            devices = tuple(self.mesh.devices.flat)
        return cand.deploy(self.backend, devices=devices,
                           interpret=self.interpret)

    def run(self, params: Sequence[dict], xs: jax.Array,
            counter: TrafficCounter | None = None) -> jax.Array:
        """Execute one batch. ``counter``, if given, also receives this
        call's transfers (the deployment always accumulates its own)."""
        r0, w0 = self.counter.reads, self.counter.writes
        rb0, wb0 = self.counter.read_bytes, self.counter.write_bytes
        if self.kind == SINGLE:
            y = span_engine.execute_partition(
                params, xs, self.plan.net, self.plan.partition,
                counter=self.counter, interpret=self.interpret,
                routes=self.routes, out_rows=self.plan.out_rows,
                policy=self.plan.quant)
            self._images += xs.shape[0] if xs.ndim == 4 else 1
        else:
            if xs.ndim != 4:
                raise ValueError("pipeline deployments stream batched "
                                 "(B, H, W, C)")
            y = self.pipeline(xs.shape[0]).run(params, xs,
                                               counter=self.counter)
            self._images += xs.shape[0]
        if counter is not None:
            counter.reads += self.counter.reads - r0
            counter.writes += self.counter.writes - w0
            counter.read_bytes += self.counter.read_bytes - rb0
            counter.write_bytes += self.counter.write_bytes - wb0
        return y

    # -- observability ------------------------------------------------------

    def profile(self, params: Sequence[dict], *,
                iters: int = 3) -> "object":
        """Measure this deployment's stages in isolation -> a
        JSON-shippable ``occam.calibrate.StageProfile``.

        Each span stage's body is jitted standalone and timed
        synchronized over ``iters`` runs at the placement's microbatch;
        pipeline deployments additionally time one boundary hop over the
        serving ring's own mesh and routing. Live tick-window stats join
        from the busiest serving ring built so far (zeros when nothing
        has served yet). ``occam.calibrate(deployment, params)`` fits a
        ``CostModel`` from the result.
        """
        from repro.runtime.stap_pipeline import (model_stage_times,
                                                 plan_span_stages)

        from .calibrate.timers import (StageProfile, measure_hop_seconds,
                                       measure_stage_seconds)

        plan = self.plan
        stages = plan_span_stages(plan.net, plan.partition,
                                  routes=self.routes)
        stage_macs = model_stage_times(plan.net, stages)
        payload_elems = tuple(int(st.out_spec.elems)
                              for st in stages[:-1])
        microbatch = self.placement.microbatch
        stage_seconds = measure_stage_seconds(
            plan.net, plan.partition, params, microbatch=microbatch,
            iters=iters, out_rows=plan.out_rows, routes=self.routes)
        hop = 0.0
        if self.kind == PIPELINE and len(stages) > 1:
            hop = measure_hop_seconds(self.ring(microbatch))
        round_batch, _mb = self.placement.serve_geometry(None)
        tick_mean = tick_busy = 0.0
        tick_count = 0
        rings = [r for r in self._rings.values() if r.timers.count]
        if rings:
            busiest = max(rings, key=lambda r: r.timers.count)
            tick_mean = busiest.timers.mean_s()
            tick_count = busiest.timers.count
            tick_busy = busiest.timers.busy_fraction()
        return StageProfile(
            spans=tuple(tuple(st.span) for st in stages),
            replicas=tuple(self.placement.replicas),
            stage_macs=tuple(float(m) for m in stage_macs),
            stage_seconds=stage_seconds,
            payload_elems=payload_elems,
            hop_seconds=hop,
            microbatch=microbatch,
            round_batch=round_batch,
            tick_mean_s=tick_mean,
            tick_count=tick_count,
            tick_busy_fraction=tick_busy)

    def _timing(self) -> dict | None:
        """Live tick-window stats from the busiest serving ring (None
        when no ring has timed a tick)."""
        rings = [r for r in self._rings.values() if r.timers.count]
        if not rings:
            return None
        t = max(rings, key=lambda r: r.timers.count).timers
        return {"tick_mean_s": t.mean_s(), "tick_count": t.count,
                "tick_busy_fraction": t.busy_fraction()}

    # -- reporting ----------------------------------------------------------

    def report(self) -> TrafficReport:
        """Predicted and measured traffic in one object (per-image
        prediction + everything counted since compile), with the live
        tick-timing window attached as ``report.timing`` once serving
        has run."""
        rep = self.plan.predicted.with_measured(self.counter, self._images)
        return dataclasses.replace(rep, timing=self._timing())

    def describe(self) -> dict:
        """Machine-readable deployment configuration (benchmarks, logs)."""
        d = {
            "kind": self.kind,
            "backend": self.backend,
            "boundaries": self.plan.boundaries,
            "routes": [[r.start, r.end, r.route] for r in self.routes],
            "batch": self.plan.batch,
            "capacity_elems": self.plan.capacity_elems,
            "predicted_transfers_per_image": self.plan.predicted_transfers,
            "images_run": self._images,
            "measured_transfers": self.counter.total,
            "measured_bytes": self.counter.total_bytes,
            "quant": (self.plan.quant.to_dict()
                      if self.plan.quant is not None else None),
        }
        if self.kind == PIPELINE:
            d["replicas"] = list(self.placement.replicas)
            d["chips"] = self.placement.chips
            d["microbatch"] = self.placement.microbatch
            pipes = {b: p.report() for b, p in self._pipes.items()}
            if pipes:
                d["pipelines"] = pipes
            rings = {r.round_batch: r.report()
                     for r in self._rings.values()}
            if rings:
                d["rings"] = rings
        return d


# --------------------------------------------------------------------------
# Continuous serving sessions
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServingStats:
    """Queue-side serving state of one :class:`Session` — the fields the
    async engine's metrics sample. Attached to ``Session.report()`` as
    ``report.serving`` and inlined into ``Session.describe()``."""

    pending_lanes: int       # images queued, not yet packed into a round
    in_flight_rounds: int    # rounds resident in the ring right now
    rounds_served: int       # ticks that carried >= 1 valid lane
    flush_count: int         # explicit / SLO-triggered drains
    waited_ticks: int        # total ticks queued partials spent aging


@dataclasses.dataclass(frozen=True)
class Ticket:
    """Handle for one ``Session.submit`` call: ``uid`` orders results
    (submit order is result order), ``images`` is the submit size."""

    uid: int
    images: int


class _Watcher:
    """A session's completion watcher: one thread that waits, in dispatch
    order, until the outputs handed to it are ready on the device
    (``block_until_ready`` releases the interpreter lock while it waits)
    and stamps that moment with ``time.perf_counter_ns``.

    A tick's outputs carry the time its dispatch returned. The device
    cannot start a round before it is dispatched, nor before the round
    ahead of it is done, so the round's device time is
    ``ready(i) - max(dispatch_end(i), ready(i-1))``; it goes to
    ``timers``. Other outputs (arrays derived from a delivered round)
    are only waited on. ``done(ready_ns)``, when given, is called on the
    watcher's thread once the outputs are ready."""

    def __init__(self, timers):
        self.timers = timers
        self._items: queue.SimpleQueue = queue.SimpleQueue()
        self._thread: threading.Thread | None = None
        self._cond = threading.Condition()
        self._put = 0               # items handed over
        self._completed = 0         # items whose outputs were ready
        self._last_ready = 0        # ready stamp of the last tick

    def put(self, outputs, *, tick: bool = False,
            done: Callable[[int], None] | None = None) -> None:
        dispatched = time.perf_counter_ns() if tick else None
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="occam-session-watcher", daemon=True)
            self._thread.start()
        self._put += 1
        self._items.put((outputs, dispatched, done))

    def _run(self) -> None:
        while (item := self._items.get()) is not None:
            outputs, dispatched, done = item
            try:
                jax.block_until_ready(outputs)
            except Exception:  # noqa: BLE001 - the watcher must keep going
                pass    # a failed computation raises where it is read
            ready = time.perf_counter_ns()
            if dispatched is not None:
                self.timers.record(
                    (ready - max(dispatched, self._last_ready)) / 1e9)
                self._last_ready = ready
            if done is not None:
                try:
                    done(ready)
                except Exception:  # noqa: BLE001 - counted as completed
                    _log.exception("completion callback failed")
            # hold nothing while waiting for the next item: a callback
            # may keep an engine, its session and its weights alive
            item = outputs = done = None
            with self._cond:
                self._completed += 1
                self._cond.notify_all()

    def wait(self) -> None:
        """Block until every output handed over so far was ready."""
        target = self._put
        with self._cond:
            self._cond.wait_for(lambda: self._completed >= target)

    def stop(self) -> None:
        """Let the thread end once it has seen what it was handed."""
        self._items.put(None)

    def close(self) -> None:
        """Wait for everything handed over, then end the thread."""
        if self._thread is not None:
            self.stop()
            self._thread.join()
            self._thread = None


class _TicketState:
    __slots__ = ("ticket", "chunks", "remaining")

    def __init__(self, ticket: Ticket):
        self.ticket = ticket
        self.chunks: list[jax.Array] = []   # output lanes, round by round
        self.remaining = ticket.images

    @property
    def done(self) -> bool:
        return self.remaining == 0

    def result(self) -> jax.Array:
        return self.chunks[0] if len(self.chunks) == 1 \
            else jnp.concatenate(self.chunks)


class Session:
    """A continuous serving session: requests of any size flow through
    ONE compiled fixed round shape. Build via :meth:`Deployment.serve`.

    ``submit(images) -> Ticket`` enqueues a request; the session packs
    the queue into fixed ``round_batch`` rounds and advances the
    pipeline eagerly as full rounds form. ``results()`` flushes — the
    final partial round is padded with *masked* lanes (they skip compute
    in the pipeline ring, never appear in outputs, and are excluded from
    measured traffic) and the ring drains — then returns every completed
    ``(ticket, outputs)`` pair in submit order. ``ready()`` peeks at
    completed tickets without flushing (results stay collectable).

    One lowering serves every submit size (``compile_count`` is the
    regression signal); a pipeline session iterates a single-tick
    :class:`~repro.runtime.stap_pipeline.StapRing` whose per-chip
    buffers are O(round_batch) however long the stream runs.
    ``report()`` attaches the session's masked-lane-exact measurement to
    the plan's per-image prediction — ``matches_prediction`` holds under
    any mix of submit sizes.
    """

    def __init__(self, deployment: Deployment, params: Sequence[dict], *,
                 round_batch: int | None = None, max_pending: int = 16,
                 max_wait_ticks: int | None = None):
        if max_pending < 1:
            raise ValueError("max_pending must be >= 1")
        if max_wait_ticks is not None and max_wait_ticks < 1:
            raise ValueError("max_wait_ticks must be >= 1 (or None to "
                             "wait indefinitely)")
        self.deployment = deployment
        self.params = params
        self.max_wait_ticks = max_wait_ticks
        self._waited = 0            # session ticks the queued partial aged
        placement = deployment.placement
        self.round_batch, self.microbatch = \
            placement.serve_geometry(round_batch)
        self.ring_depth = placement.ring_depth
        self.max_pending = max_pending
        from .calibrate.timers import TickTimers

        if deployment.kind == PIPELINE:
            self._ring = deployment.ring(self.microbatch)
            self.ring_depth = self._ring.ring_depth
            # pipeline sessions share the ring's tick timer (every
            # session at one geometry drives the same compiled tick)
            self.timers = self._ring.timers
            self._state = self._ring.init_state()
            # the all-masked drain round, in the ring's payload dtype
            # (quantized rings carry e.g. int8 slots)
            self._empty_round = jax.device_put(jnp.zeros(
                (self._ring.round_width, self.microbatch,
                 self._ring.payload_width), self._ring._payload_dtype),
                self._ring.in_sharding)
            self._masks = [np.zeros(self._ring.round_width, dtype=bool)
                           for _ in range(self.ring_depth)]
        else:
            self._ring = None
            self._state = None
            self.timers = TickTimers()
        # stamps each tick at device completion into ``timers``; a
        # session dropped unclosed lets its thread end
        self._watch = _Watcher(self.timers)
        weakref.finalize(self, self._watch.stop)
        # per-image transfer profile for masked-lane accounting: sessions
        # count per_image x valid lanes, never per_span x round size
        self._per_image = deployment._per_image_profile()
        self.counter = TrafficCounter()
        self._images = 0            # valid images entered (masked excluded)
        self._next_uid = 0
        self._tickets: dict[int, _TicketState] = {}
        self._queue: collections.deque = collections.deque()  # [uid, xs, off]
        self._queued = 0
        # rounds resident in the ring, oldest last: segment lists or None
        self._in_flight: collections.deque = collections.deque(
            [None] * (self.ring_depth - 1))
        self._banked_rounds = 0     # completed, not yet results()-collected
        self._closed = False
        # queue-side counters (surfaced via describe()/report().serving)
        self._flushes = 0           # explicit / SLO-triggered drains
        self._rounds_served = 0     # ticks that carried >= 1 valid lane
        self._waited_total = 0      # total ticks partials spent aging

    # -- the serving surface ------------------------------------------------

    def submit(self, images: jax.Array) -> Ticket:
        """Enqueue a request of any size -> :class:`Ticket`.

        ``images``: (B, H, W, C) or a single (H, W, C) image. Full rounds
        advance the pipeline immediately; a trailing remainder waits for
        more traffic (flush it with ``results()``).
        """
        if self._closed:
            raise RuntimeError("session is closed")
        had_partial = self._queued > 0
        xs = jnp.asarray(images)
        if xs.ndim == 3:
            xs = xs[None]
        if xs.ndim != 4 or xs.shape[0] < 1 or \
                xs.shape[1:] != self.deployment.plan.net.map_shape(0):
            raise ValueError(
                f"submit takes (B >= 1,) + "
                f"{self.deployment.plan.net.map_shape(0)} images, got "
                f"{tuple(xs.shape)}")
        ticket = Ticket(self._next_uid, int(xs.shape[0]))
        self._next_uid += 1
        self._tickets[ticket.uid] = _TicketState(ticket)
        self._queue.append([ticket.uid, xs, 0])
        self._queued += ticket.images
        while self._queued >= self.round_batch:
            # backpressure BEFORE popping the round: a refused submit
            # leaves the queue intact, so results() still serves it
            if self._banked_rounds >= self.max_pending:
                raise RuntimeError(
                    f"session holds {self._banked_rounds} completed "
                    f"rounds (max_pending={self.max_pending}); drain "
                    f"with results()")
            self._tick(*self._take_round())
        # age only a PRE-EXISTING partial: the submit that starts (or
        # extends) a fresh remainder must give later traffic at least
        # one tick to fill it, or max_wait_ticks=1 would degenerate to
        # flush-per-submit with no cross-submit batching ever
        if had_partial:
            self._age_partial()
        return ticket

    def ready(self) -> tuple[Ticket, ...]:
        """Tickets whose results are complete right now, in submit order.
        Never flushes on demand — but under a ``max_wait_ticks`` budget
        each call ages the queued partial round one tick, so polling
        eventually pushes a lone sub-round submit through."""
        self._age_partial()
        return tuple(ts.ticket for ts in self._tickets.values() if ts.done)

    def results(self, *, flush: bool = True
                ) -> list[tuple[Ticket, jax.Array]]:
        """Collect completed requests in submit order.

        ``flush=True`` (default) first packs any queued remainder into a
        masked partial round and drains the ring, so every outstanding
        ticket completes; ``flush=False`` returns only what full rounds
        already finished. Collected tickets leave the session.
        """
        if flush:
            self.flush()
        out = []
        for uid in list(self._tickets):
            ts = self._tickets[uid]
            if ts.done:
                out.append((ts.ticket, ts.result()))
                del self._tickets[uid]
        # recompute the backpressure gauge from what actually remains
        # buffered: each chunk on an open ticket is one delivered round
        # segment still held (a conservative, upper-bound round count) —
        # collecting nothing must not reset the max_pending bound
        self._banked_rounds = sum(len(ts.chunks)
                                  for ts in self._tickets.values())
        return out

    def flush(self) -> None:
        """Push the queued remainder through as a masked partial round
        and run drain ticks until the ring holds no live rounds. The
        session stays open — steady-state serving resumes on the next
        ``submit``."""
        self._flushes += 1
        while self._queued:     # full rounds a refused submit left behind,
            self._tick(*self._take_round())   # then the masked partial one
        while any(m is not None for m in self._in_flight):
            self._tick(None, 0)
        self._waited = 0

    def pump(self, *, allow_partial: bool = False) -> bool:
        """Advance the session by exactly ONE tick — the external-pumping
        hook async drivers build on (``occam.serve.AsyncEngine``).

        A queued full round ticks first. Otherwise, with
        ``allow_partial=True``, a queued remainder ticks through as one
        masked partial round — unlike :meth:`flush`, the ring is NOT
        drained, so steady-state serving continues around the aged
        request. Otherwise a round resident in the ring advances one
        empty tick toward delivery. Returns whether a tick ran (False =
        nothing to do: idle queue, empty ring).
        """
        if self._closed:
            raise RuntimeError("session is closed")
        if self._queued >= self.round_batch:
            if self._banked_rounds >= self.max_pending:
                raise RuntimeError(
                    f"session holds {self._banked_rounds} completed "
                    f"rounds (max_pending={self.max_pending}); drain "
                    f"with results()")
            self._tick(*self._take_round())
            return True
        if allow_partial and self._queued:
            self._tick(*self._take_round())
            self._waited = 0
            return True
        if self.in_flight_rounds:
            self._tick(None, 0)
            return True
        return False

    def sync(self) -> "Session":
        """Block until every dispatched tick has finished and the
        completion watcher has stamped it, so ``timers`` and
        ``report().timing`` hold every tick (ticks dispatch
        asynchronously — time steady-state throughput against this)."""
        self._watch.wait()
        for ts in self._tickets.values():
            if ts.chunks:
                jax.block_until_ready(ts.chunks[-1])
        return self

    def watch(self, outputs, done: Callable[[int], None]) -> None:
        """Call ``done(ready_ns)`` on the completion watcher's thread once
        ``outputs`` (arrays derived from delivered lanes) are ready on
        the device; ``ready_ns`` is a ``time.perf_counter_ns`` stamp.
        Outputs are waited on in the order they are handed over, after
        every tick dispatched before them. An exception from ``done`` is
        logged, and the outputs still count as completed."""
        self._watch.put(outputs, done=done)

    def scale(self, *, arrival_rate: float) -> "Session":
        """Serve-time autoscaling: re-pick the deployment for an observed
        ``arrival_rate`` (images/s) from the planning frontier.

        Returns ``self`` when the current deployment already is the
        cheapest candidate meeting the rate. Otherwise the session is
        flushed (outstanding tickets complete and stay collectable via
        ``results()`` here) and a NEW session on the chosen candidate's
        cached deployment is returned — submit new traffic there. The
        frontier is reused as-is: no DP, no search, and candidates the
        session scaled through before keep their compiled deployments.
        This session's ``round_batch`` carries over when the new
        placement's round width still divides it; otherwise the new
        session falls back to the candidate's own geometry default (an
        explicit round size cannot outlive the geometry it sized).
        """
        dep = self.deployment.reconcile(arrival_rate=arrival_rate)
        if dep is self.deployment:
            return self
        self.flush()
        try:
            dep.placement.serve_geometry(self.round_batch)
            round_batch = self.round_batch
        except ValueError:
            round_batch = None
        return dep.serve(self.params, round_batch=round_batch,
                         max_pending=self.max_pending,
                         max_wait_ticks=self.max_wait_ticks)

    def close(self) -> list[tuple[Ticket, jax.Array]]:
        """Flush, collect the final results, and end the session."""
        if self._closed:
            return []
        out = self.results()
        self._watch.close()
        self._closed = True
        self._state = None
        return out

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- reporting ----------------------------------------------------------

    @property
    def compile_count(self) -> int:
        """Lowerings behind this session — 1 however submit sizes mix
        (the retrace-count regression signal)."""
        if self._ring is not None:
            return self._ring.trace_count
        return self.deployment._serve_step(self.round_batch)[1]["lowerings"]

    @property
    def input_sharding(self):
        """Where submitted images should live before a tick consumes them:
        the serving ring's input sharding, or None (the default device)
        for a single-chip session."""
        return None if self._ring is None else self._ring.in_sharding

    @property
    def in_flight_rounds(self) -> int:
        """Rounds resident in the ring (dispatched, not yet delivered)."""
        return sum(1 for m in self._in_flight if m is not None)

    def serving_stats(self) -> ServingStats:
        """The queue-side state the async engine's metrics sample."""
        return ServingStats(
            pending_lanes=self._queued,
            in_flight_rounds=self.in_flight_rounds,
            rounds_served=self._rounds_served,
            flush_count=self._flushes,
            waited_ticks=self._waited_total)

    def report(self) -> TrafficReport:
        """The plan's per-image prediction with this session's measured
        transfers attached (masked padding lanes excluded from both
        ``measured_*`` and ``images``, so ``matches_prediction`` holds
        under any mix of submit sizes), the queue-side serving state as
        ``report.serving``, and the tick-timing window as
        ``report.timing``."""
        rep = self.deployment.plan.predicted.with_measured(
            self.counter, self._images)
        timing = None
        if self.timers.count:
            timing = {"tick_mean_s": self.timers.mean_s(),
                      "tick_count": self.timers.count,
                      "tick_busy_fraction": self.timers.busy_fraction()}
        return dataclasses.replace(rep, serving=self.serving_stats(),
                                   timing=timing)

    def describe(self) -> dict:
        """Machine-readable session state (benchmarks, logs)."""
        d = {
            "kind": self.deployment.kind,
            "round_batch": self.round_batch,
            "microbatch": self.microbatch,
            "ring_depth": self.ring_depth,
            "max_pending": self.max_pending,
            "max_wait_ticks": self.max_wait_ticks,
            "compile_count": self.compile_count,
            "images_entered": self._images,
            "tickets_open": len(self._tickets),
            "queued_images": self._queued,
            "pending_lanes": self._queued,
            "in_flight_rounds": self.in_flight_rounds,
            "rounds_served": self._rounds_served,
            "flush_count": self._flushes,
            "waited_ticks": self._waited_total,
        }
        if self._ring is not None:
            d["ring"] = self._ring.report()
        return d

    # -- internals ----------------------------------------------------------

    def _age_partial(self) -> None:
        """Sub-round latency budget (``max_wait_ticks``): age the queued
        partial round by one session tick (a ``submit`` or ``ready``
        call); once it has waited the budget out, auto-flush it through
        as a masked partial round."""
        if not self._queued:
            self._waited = 0
            return
        if self.max_wait_ticks is None:
            return
        self._waited += 1
        self._waited_total += 1
        if self._waited >= self.max_wait_ticks:
            self.flush()

    def _take_round(self):
        """Pop up to round_batch queued images -> (segments, images)."""
        segs, parts, n = [], [], 0
        while self._queue and n < self.round_batch:
            entry = self._queue[0]
            uid, xs, off = entry
            take = min(xs.shape[0] - off, self.round_batch - n)
            parts.append(xs[off:off + take])
            segs.append((uid, take))
            n += take
            if off + take == xs.shape[0]:
                self._queue.popleft()
            else:
                entry[2] = off + take
        self._queued -= n
        return segs, parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _tick(self, segs, xs) -> None:
        """Advance one round: account its valid lanes, run it, deliver
        the round leaving the ring to its tickets."""
        n_valid = 0 if segs is None else \
            sum(take for _uid, take in segs)
        if n_valid:
            self.counter.add_scaled(self._per_image, n_valid)
            self._images += n_valid
            self._rounds_served += 1
        if self._ring is None:
            self._deliver(segs, self._run_single(xs))
            return
        ring = self._ring
        if n_valid:
            in_round = ring.pack_round(xs)
            mask = np.zeros(ring.round_width, dtype=bool)
            mask[:-(-n_valid // self.microbatch)] = True
        else:
            in_round, mask = self._empty_round, \
                np.zeros(ring.round_width, dtype=bool)
        self._masks = [mask] + self._masks[:-1]
        self._state, lanes = ring.tick(self.params, self._state, in_round,
                                       np.stack(self._masks))
        self._watch.put((self._state, lanes), tick=True)
        if self.ring_depth > 1:
            self._in_flight.appendleft(segs if n_valid else None)
            exiting = self._in_flight.pop()
        else:
            exiting = segs if n_valid else None
        if exiting is not None:
            self._deliver(exiting, lanes)

    def _run_single(self, xs: jax.Array) -> jax.Array:
        step, _counts = self.deployment._serve_step(self.round_batch)
        pad = self.round_batch - xs.shape[0]
        if pad:
            xs = jnp.pad(xs, ((0, pad),) + ((0, 0),) * 3)
        y = step(self.params, xs)
        self._watch.put(y, tick=True)
        return y

    def _deliver(self, segs, lanes: jax.Array) -> None:
        off = 0
        for uid, take in segs:
            ts = self._tickets[uid]
            ts.chunks.append(lanes[off:off + take])
            ts.remaining -= take
            off += take
        self._banked_rounds += 1
