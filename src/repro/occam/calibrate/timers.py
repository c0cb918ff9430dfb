"""Wall-clock observability for the serving runtime.

Two complementary instruments:

* :class:`TickTimers` — a windowed, always-on record of each serving
  round's device time, fed by the session's completion watcher
  (``occam.deploy.Session``: a round's time runs from when the device
  could start it to when its outputs were ready); feeds the live
  ``utilization`` view in ``AsyncEngine.serving_stats()`` and the
  ``timing`` block of ``Deployment.report()``. Cheap: a bounded deque
  under a lock, written from the watcher's thread.
* :func:`measure_stage_seconds` / :func:`measure_hop_seconds` —
  isolated, synchronized micro-measurements (jit each stage body or
  boundary hop alone, ``block_until_ready``, best-of-N) used by
  ``occam.calibrate`` to fit a :class:`~repro.occam.calibrate
  .cost_model.CostModel`.

:class:`StageProfile` is the JSON-shippable join of both: per-stage
measured seconds, boundary-hop seconds, the analytic MACs/payloads they
correspond to, and the live tick window — everything frontier
re-scoring needs, exportable alongside a plan.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class TickTimers:
    """Windowed wall-clock accumulator for serving ticks.

    ``record(seconds)`` stamps one completed tick; events older than
    ``horizon_s`` roll off. ``busy_fraction()`` is the fraction of the
    observed window spent inside timed ticks — the duty cycle the
    utilization stats scale per-stage shares by. Safe to record from
    one thread while another reads."""

    horizon_s: float = 60.0
    clock: Callable[[], float] = time.monotonic
    events: collections.deque = dataclasses.field(
        default_factory=collections.deque)   # (t_end, duration_s)
    total_s: float = 0.0     # lifetime, never rolls off
    count: int = 0
    _lock: threading.RLock = dataclasses.field(
        default_factory=threading.RLock, repr=False, compare=False)

    def record(self, duration_s: float) -> None:
        with self._lock:
            now = self.clock()
            self.events.append((now, float(duration_s)))
            self.total_s += float(duration_s)
            self.count += 1
            self._roll(now)

    def _roll(self, now: float) -> None:
        while self.events and self.events[0][0] < now - self.horizon_s:
            self.events.popleft()

    def window(self, now: float | None = None) -> tuple[int, float]:
        """(ticks, busy seconds) inside the rolling horizon."""
        now = self.clock() if now is None else now
        with self._lock:
            self._roll(now)
            return len(self.events), sum(d for (_t, d) in self.events)

    def mean_s(self, now: float | None = None) -> float:
        n, busy = self.window(now)
        return busy / n if n else 0.0

    def busy_fraction(self, now: float | None = None) -> float:
        """Busy seconds / observed span, over the rolling window."""
        now = self.clock() if now is None else now
        with self._lock:
            n, busy = self.window(now)
            if not n:
                return 0.0
            start = self.events[0][0] - self.events[0][1]
        span = max(now - start, busy, 1e-12)
        return min(busy / span, 1.0)


# --------------------------------------------------------------------------
# Isolated micro-measurements (synchronized; calibration inputs)
# --------------------------------------------------------------------------

def measure_stage_seconds(net, partition, params, *, microbatch: int = 1,
                          iters: int = 3, out_rows: int = 1,
                          routes=None,
                          clock: Callable[[], float] = time.perf_counter
                          ) -> tuple[float, ...]:
    """Measured wall-clock seconds per stage body per microbatch slot.

    Each span stage's SPMD body is jitted standalone (no mesh, no
    collectives — exactly the compute a replica pays per owned slot),
    warmed once, then timed over ``iters`` synchronized runs. The result
    aligns with ``plan_span_stages(net, partition)`` and with the MAC
    model ``model_stage_times`` — the (analytic, measured) pairs
    ``fit_cost_model`` regresses."""
    from repro.runtime import stap_pipeline as sp
    stages = sp.plan_span_stages(net, partition, routes=routes)
    payload_width = max(max(st.in_spec.elems, st.out_spec.elems)
                        for st in stages)
    param_width = max((sp._span_param_elems(net, *st.span) for st in stages),
                      default=1) or 1
    times = []
    for st in stages:
        body = jax.jit(sp.make_stage_body(net, st, payload_width,
                                          out_rows=out_rows))
        p_flat = sp._flatten_span_params(params, net, *st.span,
                                         width=param_width)
        slot = jnp.zeros((microbatch, payload_width))
        jax.block_until_ready(body(p_flat, slot))   # compile + warm
        t0 = clock()
        for _ in range(max(1, iters)):
            y = body(p_flat, slot)
        jax.block_until_ready(y)
        times.append((clock() - t0) / max(1, iters))
    return tuple(times)


def measure_hop_seconds(ring, *, iters: int = 8,
                        clock: Callable[[], float] = time.perf_counter
                        ) -> float:
    """Measured seconds for one boundary hop of one payload slot.

    Times a jitted chain of ``iters`` slot-level ``ppermute`` hops over
    the ring's own mesh and routing (rect or packed) and divides out the
    chain length — the per-hop cost ``fit_cost_model`` turns into a
    link rate. Returns 0.0 for single-stage rings (no links)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.runtime import stap_pipeline as sp

    steady = ring.steady
    if steady.n_stages == 1:
        return 0.0
    if ring.packing == "sum":
        axes, spec = sp.CHIP_AXIS, P(sp.CHIP_AXIS)
        perm = ring.assignment.slot_perm(steady, 0)
    else:
        axes = (sp.STAGE_AXIS, sp.REPLICA_AXIS)
        spec = P((sp.STAGE_AXIS, sp.REPLICA_AXIS))
        perm = steady.slot_perm(0)
    n_rows = ring.init_state().shape[0] // ring.round_width

    def per_device(x):
        for _ in range(iters):
            x = lax.ppermute(x, axes, perm)
        return x

    fn = jax.jit(jax.shard_map(per_device, mesh=ring.mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
    x = jax.device_put(
        jnp.zeros((n_rows, ring.microbatch, ring.payload_width)),
        jax.sharding.NamedSharding(ring.mesh, spec))
    jax.block_until_ready(fn(x))    # compile + warm
    t0 = clock()
    jax.block_until_ready(fn(x))
    return (clock() - t0) / iters


# --------------------------------------------------------------------------
# The JSON-shippable join
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StageProfile:
    """Everything measured about a deployment's stages, exportable.

    ``stage_seconds`` come from the isolated stage bodies
    (:func:`measure_stage_seconds`); ``stage_macs`` / ``payload_elems``
    are the analytic quantities they calibrate; ``hop_seconds`` is the
    per-boundary link measurement; ``tick_*`` join the live serving
    window (:class:`TickTimers`) when the profile was taken from a
    running deployment."""

    spans: tuple[tuple[int, int], ...]
    replicas: tuple[int, ...]
    stage_macs: tuple[float, ...]
    stage_seconds: tuple[float, ...]
    payload_elems: tuple[int, ...]       # per interior boundary
    hop_seconds: float
    microbatch: int
    round_batch: int
    tick_mean_s: float = 0.0
    tick_count: int = 0
    tick_busy_fraction: float = 0.0

    def to_dict(self) -> dict:
        return {
            "spans": [list(s) for s in self.spans],
            "replicas": list(self.replicas),
            "stage_macs": list(self.stage_macs),
            "stage_seconds": list(self.stage_seconds),
            "payload_elems": list(self.payload_elems),
            "hop_seconds": self.hop_seconds,
            "microbatch": self.microbatch,
            "round_batch": self.round_batch,
            "tick_mean_s": self.tick_mean_s,
            "tick_count": self.tick_count,
            "tick_busy_fraction": self.tick_busy_fraction,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "StageProfile":
        return cls(
            spans=tuple(tuple(s) for s in d["spans"]),
            replicas=tuple(d["replicas"]),
            stage_macs=tuple(d["stage_macs"]),
            stage_seconds=tuple(d["stage_seconds"]),
            payload_elems=tuple(d["payload_elems"]),
            hop_seconds=float(d["hop_seconds"]),
            microbatch=int(d["microbatch"]),
            round_batch=int(d["round_batch"]),
            tick_mean_s=float(d.get("tick_mean_s", 0.0)),
            tick_count=int(d.get("tick_count", 0)),
            tick_busy_fraction=float(d.get("tick_busy_fraction", 0.0)),
        )
