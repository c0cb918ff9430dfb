"""Executable STAP runtime: staggered, replicated multi-chip span pipeline.

This is the paper's §III-E made runnable: a ``PartitionResult`` (the DP's
optimal spans) executes as a real SPMD pipeline over a ``(stage, replica)``
device mesh.

* Each stage holds *only its span's weights*, resident on its chips for the
  whole stream — Occam's full cross-image filter reuse (Eqn. 6) lifted to
  the multi-chip level.
* Mini-batch ``m`` is staggered onto replica ``m % r_i`` of stage ``i``
  following the :class:`~repro.core.stap.StapPlan`; the explicit lock-step
  tick schedule (ownership, fill/drain, routing) comes from
  :func:`~repro.core.stap.staggered_schedule`.
* Boundary activations (the span-boundary map plus every residual source
  crossing the cut — exactly the per-boundary quantity the DP minimized)
  move between stages by slot-level ``ppermute`` as the *only* inter-stage
  traffic: the replica that served a slot sends straight to the replica
  that will serve it next. There is no intra-stage collective until a
  single final ``psum`` assembles the last stage's outputs.
* Stage bodies dispatch through the engine registry
  (``EngineSpec.make_spmd_body``): kernel-routed spans run the fused
  Pallas span kernel directly under ``shard_map`` (interpret mode off
  TPU, the compiled kernel on real TPUs), scan-routed spans the jitted
  row-streaming twin, and oversized single layers the oracle, per
  ``repro.runtime.span_engine.plan_routes``.

Heterogeneous spans under one SPMD program: every boundary payload is
flattened to a fixed-width slot vector and every span's weights to a
fixed-width parameter vector, and the per-device program selects its span
body with ``lax.switch`` on the stage index — only the selected branch
executes at runtime, so a replica pays exactly its own span's FLOPs.

Input staging: the padded feed is *not* replicated to every device — it
is sharded over the stage axis (chip row i holds rounds [i*chunk,
(i+1)*chunk) of the stream) and an input conveyor of static stage-axis
``ppermute`` hops walks each round to stage 0 exactly when the schedule
consumes it, keeping per-chip input memory at O(stream/S).

Output staging is the same trick in reverse: no device banks the full
(rounds, width, slot) output buffer. The last stage injects each finished
round into an output conveyor that hops it along the cyclic stage ring to
its bank row, so every chip banks only ceil(rounds/S) rounds of output —
per-chip output memory O(stream/S), symmetric to the input side
(``collect_staged_outputs`` undoes the banking on the host).

Two executable forms share the span stages (whose bodies dispatch through
the engine registry — ``EngineSpec.make_spmd_body``):

* :class:`StapPipeline` — the fixed-round batch program: one ``lax.scan``
  over the whole staggered schedule, compiled per stream length.
* :class:`StapRing` — the serving form: ONE compiled fixed-shape SPMD
  tick (a ring of rounds, one per stage) iterated host-side, so a single
  lowering serves an unbounded stream of mixed submit sizes
  (``repro.occam.Deployment.serve`` builds sessions on it).

Runs on CPU CI via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(see ``tests/conftest.py``). Deployment entry: the staged API
(``repro.occam``: plan -> place -> compile -> run / serve); async
serving demo: ``examples/async_serve.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.graph import NetSpec
from repro.core.partition import PartitionResult
from repro.core.stap import (StaggeredSchedule, StapPlan, plan_replication,
                             staggered_schedule, steady_schedule)
from repro.models import cnn
from repro.occam import registry
from repro.runtime import span_engine

STAGE_AXIS = "stage"
REPLICA_AXIS = "replica"
CHIP_AXIS = "chip"
PACKINGS = ("rect", "sum")


# --------------------------------------------------------------------------
# Static planning: boundary payloads and per-span stages
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PayloadSpec:
    """What crosses a partition cut: the boundary map plus every residual
    source with an edge straddling the cut. ``elems`` is therefore exactly
    the per-boundary quantity the DP charges (one direction)."""

    cut: int
    keys: tuple[int, ...]   # [cut, *sorted crossing residual sources]
    elems: int              # per-image payload elements


def payload_spec(net: NetSpec, cut: int) -> PayloadSpec:
    extras = sorted({s for (s, t) in net.residual_edges if s < cut < t})
    keys = (cut, *extras)
    return PayloadSpec(cut, keys, sum(net.map_elems(k) for k in keys))


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One pipeline stage: a span, its engine route, and its payloads."""

    route: span_engine.SpanRoute
    in_spec: PayloadSpec
    out_spec: PayloadSpec
    spill: tuple[int, ...]     # interior maps this span must materialize
    src_keys: tuple[int, ...]  # upstream sources consumed from the payload

    @property
    def span(self) -> tuple[int, int]:
        return self.route.start, self.route.end


def plan_span_stages(net: NetSpec,
                     partition: PartitionResult | Sequence[int],
                     routes: Sequence[span_engine.SpanRoute] | None = None
                     ) -> tuple[StageSpec, ...]:
    """Pure function of net + partition: spans -> pipeline stages.

    ``routes`` overrides the registry's auto dispatch (forced backends
    from ``Placement.compile``); it must cover exactly the partition's
    spans."""
    boundaries = span_engine._boundaries_of(partition, net)
    if routes is None:
        routes = span_engine.plan_routes(net, partition)
    crossing = [(s, t) for (s, t) in net.residual_edges
                if any(s < p < t for p in boundaries)]
    spill_sources = {s for (s, _t) in crossing}
    stages = []
    for route in routes:
        a, b = route.start, route.end
        stages.append(StageSpec(
            route=route,
            in_spec=payload_spec(net, a),
            out_spec=payload_spec(net, b),
            spill=tuple(sorted(m for m in spill_sources if a < m < b)),
            src_keys=tuple(sorted({s for (s, t) in net.residual_edges
                                   if s < a < t <= b})),
        ))
    return tuple(stages)


def model_stage_times(net: NetSpec, stages: Sequence[StageSpec]
                      ) -> tuple[float, ...]:
    """Per-stage latency model for planning when no measured times exist:
    conv MACs plus pool window ops (arbitrary units — only ratios matter
    to ``plan_replication``)."""
    times = []
    for st in stages:
        a, b = st.span
        ops = 0
        for layer in net.layers[a:b]:
            ops += layer.macs if layer.kind == "conv" \
                else layer.out_elems * layer.k * layer.k
        times.append(float(max(ops, 1)))
    return tuple(times)


def default_stap_plan(stage_times: Sequence[float], *,
                      max_chips: int | None = None,
                      max_replicas: int | None = None,
                      target_period: float | None = None,
                      mesh: Mesh | None = None,
                      devices: Sequence | None = None,
                      harmonize: bool = False) -> StapPlan:
    """The replication-planning defaults shared by :class:`StapPipeline`
    and ``repro.occam.Plan.place``: cap replicas at what the available
    (stage, replica) mesh can physically hold, and treat a replica-capable
    mesh with no stated budget as a budget of the whole mesh."""
    n_stages = len(stage_times)
    if max_replicas is None:
        # cap replication at what the (stage, replica) mesh can
        # physically hold, so natural chip budgets plan meshes
        # that actually exist
        if mesh is not None:
            max_replicas = mesh.shape.get(REPLICA_AXIS, 1)
        else:
            n_dev = len(devices) if devices is not None \
                else jax.device_count()
            max_replicas = max(1, n_dev // n_stages)
    if mesh is not None and max_chips is None and target_period is None:
        # a replica-capable mesh with no stated budget means "use
        # it": water-fill up to the devices the mesh holds (the
        # schedule must match the mesh shape exactly)
        max_chips = n_stages * max_replicas
    return plan_replication(stage_times, target_period=target_period,
                            max_chips=max_chips, max_replicas=max_replicas,
                            harmonize=harmonize)


def _device_pool(devices: Sequence | None) -> list:
    """The caller's devices in the caller's order, else every device in
    physical ring order (``mesh_utils.create_device_mesh``): on a TPU
    slice consecutive devices are ICI neighbours, so each stage-to-stage
    hop crosses one link — on a v5e 2x2, ``jax.devices()`` order would
    put the second hop on a diagonal."""
    if devices is not None:
        return list(devices)
    return list(mesh_utils.create_device_mesh((jax.device_count(),)).flat)


def stap_mesh(n_stages: int, max_replicas: int,
              devices: Sequence | None = None) -> Mesh:
    """A (stage, replica) mesh over the first n_stages*max_replicas
    devices of the pool (``_device_pool``)."""
    devs = _device_pool(devices)
    need = n_stages * max_replicas
    if len(devs) < need:
        raise ValueError(
            f"STAP mesh needs {n_stages}x{max_replicas} = {need} devices, "
            f"have {len(devs)} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before jax "
            f"import to emulate them on CPU)")
    arr = np.array(devs[:need]).reshape(n_stages, max_replicas)
    return Mesh(arr, (STAGE_AXIS, REPLICA_AXIS))


def packed_mesh(n_chips: int, devices: Sequence | None = None) -> Mesh:
    """A flat 1-D chip mesh over the first ``n_chips`` devices of the
    pool (``_device_pool``) — the sum-of-replicas layout (§III-E): a
    4-3-2 plan occupies 9 chips, not a rectangular 3x4 = 12."""
    devs = _device_pool(devices)
    if len(devs) < n_chips:
        raise ValueError(
            f"packed STAP mesh needs {n_chips} devices, have {len(devs)} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_chips} before jax import to emulate them on CPU)")
    return Mesh(np.array(devs[:n_chips]), (CHIP_AXIS,))


# --------------------------------------------------------------------------
# Payload / parameter flattening (uniform SPMD buffers)
# --------------------------------------------------------------------------

def _pack(parts: dict[int, jax.Array], spec: PayloadSpec,
          width: int) -> jax.Array:
    """{map -> (mb, h, w, c)} -> (mb, width) zero-padded flat payload."""
    mb = parts[spec.keys[0]].shape[0]
    flat = jnp.concatenate([parts[k].reshape(mb, -1) for k in spec.keys],
                           axis=1)
    return jnp.pad(flat, ((0, 0), (0, width - flat.shape[1])))


def _unpack(payload: jax.Array, spec: PayloadSpec,
            net: NetSpec) -> dict[int, jax.Array]:
    parts, off = {}, 0
    for k in spec.keys:
        h, w, c = net.map_shape(k)
        n = h * w * c
        parts[k] = payload[:, off:off + n].reshape(-1, h, w, c)
        off += n
    return parts


def _span_param_elems(net: NetSpec, a: int, b: int) -> int:
    return sum(l.weight_elems + l.out_ch for l in net.layers[a:b]
               if l.kind == "conv")


def _flatten_span_params(params: Sequence[dict], net: NetSpec, a: int, b: int,
                         width: int) -> jax.Array:
    leaves = []
    for l in range(a, b):
        if net.layers[l].kind == "conv":
            leaves += [params[l]["w"].ravel(), params[l]["b"].ravel()]
    flat = jnp.concatenate(leaves) if leaves else jnp.zeros((0,), jnp.float32)
    return jnp.pad(flat, (0, width - flat.shape[0]))


def _unflatten_span_params(flat: jax.Array, net: NetSpec, a: int,
                           b: int) -> tuple[dict, ...]:
    out, off = [], 0
    for l in range(a, b):
        layer = net.layers[l]
        if layer.kind != "conv":
            out.append({})
            continue
        wsz = layer.weight_elems
        w = lax.slice_in_dim(flat, off, off + wsz).reshape(
            layer.k, layer.k, layer.in_ch, layer.out_ch)
        bv = lax.slice_in_dim(flat, off + wsz, off + wsz + layer.out_ch)
        out.append({"w": w, "b": bv})
        off += wsz + layer.out_ch
    return tuple(out)


# --------------------------------------------------------------------------
# The generic round executor (shared by heterogeneous spans and the
# homogeneous replicated transformer pipeline)
# --------------------------------------------------------------------------

def feed_chunk_rounds(n_rounds: int, n_stages: int) -> int:
    """Rounds of input feed resident per chip row: ceil(n_rounds / S)."""
    return -(-n_rounds // n_stages)


def out_chunk_rounds(n_rounds: int, n_stages: int) -> int:
    """Rounds of output banked per chip row — the same ceil(n_rounds / S)
    chunking as the input side (one rule, two conveyors)."""
    return feed_chunk_rounds(n_rounds, n_stages)


def output_bank_row(rg: int, n_rounds: int, n_stages: int) -> int:
    """Bank row of finished round ``rg`` under the output conveyor.

    Round rg finishes on the last stage row at tick rg + S - 1 and then
    hops cyclically (row S-1 -> 0 -> 1 -> ...) for h = (rounds-1-rg) mod S
    hops, landing on row (S-1+h) mod S. The reverse round-robin assignment
    is forced by finishing times: the *last* round finishes on the final
    tick and must bank with zero hops (row S-1), round rounds-2 gets at
    most one hop, and so on — so the conveyor drains within the schedule's
    existing ticks, with no extra drain ticks, while still spreading the
    rounds evenly (ceil(rounds/S) per row, round rg in bank slot rg // S).
    """
    return (n_rounds + n_stages - 2 - rg) % n_stages


def collect_staged_outputs(out: jax.Array, sched: StaggeredSchedule
                           ) -> jax.Array:
    """Undo the output conveyor's banking on the host: the staged
    (S * R * chunk, width, *slot) executable output -> (n_rounds, width,
    *slot) finished rounds in stream order, replica partials summed (each
    replica banked only its owned slots, zeros elsewhere — summed here
    instead of an inter-replica all-reduce of mostly-zero buffers)."""
    s, r, rounds = sched.n_stages, sched.max_replicas, sched.n_rounds
    chunk = out_chunk_rounds(rounds, s)
    arr = out.reshape((s, r, chunk) + out.shape[1:]).sum(axis=1)
    rg = np.arange(rounds)
    return arr[output_bank_row(rg, rounds, s), rg // s]


def stage_feed(feed: jax.Array, n_stages: int) -> jax.Array:
    """Pad a (n_rounds, ...) feed to (S * chunk, ...) for stage sharding.

    Chip row i initially holds rounds [i*chunk, (i+1)*chunk) — the input
    conveyor (see ``_round_executor``) walks them to stage 0 in time."""
    chunk = feed_chunk_rounds(feed.shape[0], n_stages)
    pad = n_stages * chunk - feed.shape[0]
    return jnp.pad(feed, ((0, pad),) + ((0, 0),) * (feed.ndim - 1))


def _round_executor(step, stage_params, feed: jax.Array, mesh: Mesh,
                    sched: StaggeredSchedule,
                    stage_axis: str = STAGE_AXIS,
                    replica_axis: str = REPLICA_AXIS) -> jax.Array:
    """Run the staggered lock-step schedule as one SPMD program.

    step(stage_idx, params_local, slot) -> slot', both of ``feed``'s
    trailing slot shape. ``feed``: (n_rounds, round_width, *slot) input —
    or its ``stage_feed`` padded form (S*chunk, round_width, *slot) when
    the caller already staged it onto devices. ``stage_params``: pytree
    with leading stage dim on every leaf. Returns the *staged* outputs —
    (S * R * chunk, round_width, *slot), each chip row banking
    ceil(n_rounds/S) finished rounds — which ``collect_staged_outputs``
    reassembles into (n_rounds, round_width, *slot) on the host.

    Input staging: the feed is *sharded over the stage axis* on its rounds
    dimension (chip row i holds rounds [i*chunk, (i+1)*chunk), replicated
    across the replica axis), never replicated whole — per-chip input
    memory is O(stream/S), not O(stream). Only stage 0 consumes rounds, so
    each tick every row forwards the round at its queue head one hop
    toward stage 0 (a static stage-axis ``ppermute`` — the input conveyor)
    and banks the round arriving from the row behind it in the freed slot.
    Row i's slot (t mod chunk) therefore holds round i*chunk + t at tick
    t, i.e. stage 0's head is exactly round t when it needs it.

    Output staging is the input conveyor in reverse: the last stage row
    injects each finished round into a one-slot transit buffer that hops
    along the *cyclic* stage ring (S-1 -> 0 -> 1 -> ...) once per tick;
    the row ``output_bank_row`` assigns to the round banks it when it
    arrives. Rounds enter transit one tick apart and move in lockstep, so
    at most one live round occupies any row's transit slot, and the
    reverse round-robin bank assignment drains the conveyor within the
    schedule's existing ticks (the last round banks with zero hops). No
    device ever materializes the full (rounds, width, *slot) buffer.

    Tick t: stage i serves round t - i; each replica runs only its owned
    *live* slots (``lax.cond`` — the skipped branch costs nothing at run
    time), then every slot's boundary payload ppermutes one hop down the
    pipe straight to the replica that will serve it next.
    """
    s_stages, r_max = sched.n_stages, sched.max_replicas
    got = (mesh.shape.get(stage_axis), mesh.shape.get(replica_axis))
    if got != (s_stages, r_max):
        # slot routing is computed over a (n_stages, max_replicas) grid; a
        # mismatched mesh would silently misroute every payload to zeros
        raise ValueError(
            f"mesh is {stage_axis}={got[0]}, {replica_axis}={got[1]} but "
            f"the schedule needs {s_stages}x{r_max} (replicas "
            f"{sched.replicas}); build it with stap_mesh({s_stages}, "
            f"{r_max})")
    width, rounds = sched.round_width, sched.n_rounds
    chunk = feed_chunk_rounds(rounds, s_stages)
    if feed.shape[0] == rounds:
        feed = stage_feed(feed, s_stages)
    if feed.shape[0] != s_stages * chunk:
        raise ValueError(f"feed has {feed.shape[0]} rounds; schedule needs "
                         f"{rounds} (staged: {s_stages * chunk})")
    out_chunk = out_chunk_rounds(rounds, s_stages)
    owner = jnp.asarray(np.array(sched.owner_table()))          # (S, R, W)
    live = jnp.asarray(np.array(sched.slot_live()))             # (G*W,)
    perms = [sched.slot_perm(w) for w in range(width)]
    conveyor = [(k, k - 1) for k in range(1, s_stages)]
    out_conveyor = [(k, (k + 1) % s_stages) for k in range(s_stages)]

    def per_device(params_local, queue0):
        i = lax.axis_index(stage_axis)
        j = lax.axis_index(replica_axis)
        p_here = jax.tree.map(lambda l: l[0], params_local)
        slot_shape = queue0.shape[2:]
        buf0 = jnp.zeros((width,) + slot_shape, queue0.dtype)
        outq0 = jnp.zeros((out_chunk, width) + slot_shape, queue0.dtype)
        transit0 = jnp.zeros((width,) + slot_shape, queue0.dtype)

        def tick(carry, t):
            buf, outq, transit, queue = carry
            rg = t - i
            active = jnp.logical_and(rg >= 0, rg < rounds)
            rgc = jnp.clip(rg, 0, rounds - 1)
            # input conveyor head: on row i this is round i*chunk + t, so
            # stage 0 reads exactly round t (its round for this tick)
            head = lax.dynamic_index_in_dim(queue, t % chunk, 0,
                                            keepdims=False)
            slot_in = jnp.where(i == 0, head, buf)
            # Double-buffered boundary slot: ``buf`` (the receive buffer,
            # carried from last tick) is only read here; each slot's
            # outgoing hop is issued immediately after its body produces
            # ``yw`` — a distinct send value, never aliasing ``buf`` — so
            # the collective-permute-start for slot w overlaps the bodies
            # of slots w+1.. instead of serializing behind the whole
            # tick's compute.
            ys, hops = [], []
            for w in range(width):
                pred = jnp.logical_and(
                    jnp.logical_and(active, owner[i, j, w]),
                    live[rgc * width + w])
                yw = lax.cond(
                    pred,
                    lambda x: step(i, p_here, x),
                    lambda x: jnp.zeros_like(x),
                    slot_in[w])
                ys.append(yw)
                if s_stages > 1:
                    # boundary activations: one slot-level hop down the
                    # pipe — the only other inter-stage traffic, exactly
                    # the DP's minimized quantity
                    hops.append(lax.ppermute(
                        yw, (stage_axis, replica_axis), perms[w]))
            y = jnp.stack(ys)
            # output conveyor: the last stage row injects its finished
            # round (inactive ticks injected zeros above); everyone else
            # passes along what arrived over the cyclic ring hop
            if s_stages > 1:
                incoming_out = lax.ppermute(transit, stage_axis,
                                            out_conveyor)
            else:
                incoming_out = transit
            arriving = jnp.where(i == s_stages - 1, y, incoming_out)
            # the round arriving at row i this tick (injected at tick
            # rg + S - 1, it reaches row i after (i + 1) mod S hops);
            # bank it here if output_bank_row — the single source of
            # truth shared with collect_staged_outputs — says so
            rg_o = t - (i + 1) % s_stages - (s_stages - 1)
            bank = jnp.logical_and(
                jnp.logical_and(rg_o >= 0, rg_o < rounds),
                output_bank_row(rg_o, rounds, s_stages) == i)
            deposited = lax.dynamic_update_index_in_dim(
                outq, arriving, jnp.clip(rg_o, 0, rounds - 1) // s_stages,
                0)
            outq = jnp.where(bank, deposited, outq)
            transit = arriving
            if s_stages > 1:
                # input conveyor: every row forwards its head one hop
                # toward stage 0 and banks the round from the row behind
                incoming = lax.ppermute(head, stage_axis, conveyor)
                queue = lax.dynamic_update_index_in_dim(
                    queue, incoming, t % chunk, 0)
                # next tick's receive buffer: the hops issued per slot
                # above (the send side of the double buffer)
                buf = jnp.stack(hops)
            return (buf, outq, transit, queue), None

        (_, outq, _, _), _ = lax.scan(tick, (buf0, outq0, transit0, queue0),
                                      jnp.arange(sched.n_ticks))
        return outq

    # each chip row banks only its ceil(rounds/S) conveyor-assigned rounds,
    # still replica-sharded (each replica banked only its owned slots,
    # zeros elsewhere) — collect_staged_outputs reassembles rounds and
    # sums the replica partials on the host instead of an inter-replica
    # all-reduce of the mostly-zero padded stream (the same zero-broadcast
    # this module's pipeline_forward fix removed)
    return jax.shard_map(per_device, mesh=mesh,
                         in_specs=(P(stage_axis), P(stage_axis)),
                         out_specs=P((stage_axis, replica_axis)),
                         check_vma=False)(stage_params, feed)


def replicated_forward(stage_fn, stage_params, microbatches: jax.Array,
                       mesh: Mesh, plan: StapPlan,
                       stage_axis: str = STAGE_AXIS,
                       replica_axis: str = REPLICA_AXIS) -> jax.Array:
    """Homogeneous replicated pipeline (the ``pipeline_forward``
    generalization): same-shape stages, microbatch m -> replica m % r_i.

    stage_fn(params_slice, x) -> y with y.shape == x.shape;
    stage_params leaves carry a leading stage dim; microbatches is
    (M, mb, ...) replicated. Returns the (M, mb, ...) last-stage outputs.
    """
    m = microbatches.shape[0]
    sched = staggered_schedule(plan, m)
    pad = sched.n_slots - m
    feed = jnp.pad(microbatches, ((0, pad),) + ((0, 0),) *
                   (microbatches.ndim - 1))
    feed = feed.reshape((sched.n_rounds, sched.round_width)
                        + microbatches.shape[1:])

    def step(_i, params_local, slot):
        return stage_fn(params_local, slot)

    staged = _round_executor(step, stage_params, feed, mesh, sched,
                             stage_axis=stage_axis,
                             replica_axis=replica_axis)
    outs = collect_staged_outputs(staged, sched)
    return outs.reshape((sched.n_slots,) + microbatches.shape[1:])[:m]


# --------------------------------------------------------------------------
# The span pipeline: heterogeneous Occam spans as switch-selected bodies
# --------------------------------------------------------------------------

def _payload_casts(policy):
    """(dequant, quant) boundary transforms for a policy: identity for
    None / the implicit fp32 policy; otherwise dequant lifts a payload
    into the policy's compute dtype at span entry and quant drops a span
    output back to the boundary dtype before it is packed for transport.
    """
    if policy is None or policy.is_default:
        ident = lambda arr: arr  # noqa: E731
        return ident, ident
    from repro.occam.quant import casting

    def dequant(q):
        return casting.dequantize(q, policy.boundary, policy.scale,
                                  compute=policy.compute)

    def quant(x):
        return casting.quantize(x, policy.boundary, policy.scale)

    return dequant, quant


def make_stage_body(net: NetSpec, stage: StageSpec, payload_width: int,
                    out_rows: int = 1, policy=None):
    """One stage's shard_map-traceable body: unflatten the span's
    parameter slice, unpack the boundary payload, run the span core the
    registry resolved for the route, and pack the outgoing payload
    (output map + spills + forwarded upstream sources).

    ``policy`` (an ``occam.quant.DtypePolicy``) makes the boundary
    genuinely quantized: the slot arrives in the boundary dtype,
    dequantizes at span entry (the span core computes in
    ``policy.compute``, always a float dtype), and the outgoing map /
    spills quantize back before packing. Forwarded upstream sources stay
    in their transport form — a map that rides several hops is quantized
    exactly once.

    Module-level because it is also a standalone jit target: the
    calibration timers (``repro.occam.calibrate.timers``) run each
    stage's body in isolation to measure per-stage wall-clock without a
    device mesh."""
    a, b = stage.span
    spec = registry.resolve_spmd_engine(stage.route.route)
    # per-stage effective tile height: a deep net's tail spans have
    # short output maps, so the planned out_rows clamps per span
    t = max(1, min(out_rows, net.map_shape(b)[0]))
    core = spec.make_spmd_body(net, a, b, stage.spill, stage.src_keys,
                               out_rows=t)
    dequant, quant = _payload_casts(policy)

    def body(p_flat, slot):
        span_params = _unflatten_span_params(p_flat, net, a, b)
        parts = _unpack(slot, stage.in_spec, net)
        x = dequant(parts[a])
        srcs = tuple(dequant(parts[s]) for s in stage.src_keys)
        out, spilled = core(span_params, x, srcs)
        out_parts = {}
        for s in stage.out_spec.keys:
            if s == b:
                out_parts[s] = quant(out)
            elif s in spilled:
                out_parts[s] = quant(spilled[s])
            elif s == a:
                # edge source == this span's input: forward the transport
                # form (already quantized), not the dequantized compute copy
                out_parts[s] = parts[s]
            else:
                out_parts[s] = parts[s]  # upstream source: forward it
        return _pack(out_parts, stage.out_spec, payload_width)

    return body


class _SpanProgram:
    """Shared static planning for the STAP executors: spans -> stages
    whose SPMD bodies dispatch through the engine registry
    (``EngineSpec.make_spmd_body``), flattened payload/parameter buffers,
    and the (stage, replica) mesh. :class:`StapPipeline` (fixed-round
    batch program) and :class:`StapRing` (single-tick serving step) both
    build on it."""

    def __init__(self, net: NetSpec,
                 partition: PartitionResult | Sequence[int],
                 microbatch: int = 1, *,
                 plan: StapPlan | None = None,
                 stage_times: Sequence[float] | None = None,
                 max_chips: int | None = None,
                 max_replicas: int | None = None,
                 target_period: float | None = None,
                 mesh: Mesh | None = None,
                 devices: Sequence | None = None,
                 routes: Sequence[span_engine.SpanRoute] | None = None,
                 out_rows: int = 1,
                 packing: str = "rect",
                 policy=None):
        if packing not in PACKINGS:
            raise ValueError(f"packing must be one of {PACKINGS}, "
                             f"got {packing!r}")
        # normalize the implicit fp32 policy to None so every downstream
        # hook has one no-quantization spelling
        if policy is not None and policy.is_default:
            policy = None
        self.policy = policy
        self.net = net
        self.boundaries = span_engine._boundaries_of(partition, net)
        self.stages = plan_span_stages(net, partition, routes=routes)
        n_stages = len(self.stages)
        self.microbatch = microbatch
        self.out_rows = out_rows
        self.packing = packing
        self.stage_times = tuple(stage_times) if stage_times is not None \
            else model_stage_times(net, self.stages)
        if plan is None:
            if packing == "sum":
                # sum packing exists to realize an *already chosen*
                # unbalanced replica vector on sum(replicas) chips; the
                # default planners reason in rectangular budgets
                raise ValueError("packing='sum' requires an explicit plan")
            plan = default_stap_plan(self.stage_times,
                                     target_period=target_period,
                                     max_chips=max_chips,
                                     max_replicas=max_replicas,
                                     mesh=mesh, devices=devices)
        if len(plan.replicas) != n_stages:
            raise ValueError(f"plan has {len(plan.replicas)} stages, "
                             f"partition has {n_stages}")
        self.plan = plan
        if packing == "sum":
            # lazy import: repro.occam's package init pulls this module in
            # via the deployment layer before occam.calibrate exists
            from repro.occam.calibrate.placement import pack_replicas
            self.assignment = pack_replicas(plan.replicas)
            if mesh is None:
                mesh = packed_mesh(self.assignment.n_chips, devices)
            elif mesh.shape.get(CHIP_AXIS) != self.assignment.n_chips:
                raise ValueError(
                    f"packed mesh is {CHIP_AXIS}="
                    f"{mesh.shape.get(CHIP_AXIS)} but the plan needs "
                    f"sum(replicas) = {self.assignment.n_chips} chips; "
                    f"build it with packed_mesh({self.assignment.n_chips})")
            self.mesh = mesh
        else:
            self.assignment = None
            self.mesh = mesh if mesh is not None else stap_mesh(
                n_stages, max(plan.replicas), devices)
        self.payload_width = max(max(st.in_spec.elems, st.out_spec.elems)
                                 for st in self.stages)
        self.param_width = max(
            (_span_param_elems(net, *st.span) for st in self.stages),
            default=1) or 1
        # the dtype every payload buffer (feed, ring state, ppermute
        # hops) is allocated and moved in — int8 boundaries really ship
        # a quarter of the fp32 bytes
        if self.policy is None:
            self._payload_dtype = jnp.float32
            self.payload_bytes_per_elem = 4.0
        else:
            from repro.occam.quant import casting
            self._payload_dtype = casting.jnp_dtype(self.policy.boundary)
            self.payload_bytes_per_elem = self.policy.boundary_bytes

    # -- static reporting ---------------------------------------------------

    @property
    def link_elems_per_image(self) -> int:
        """Boundary-payload elements moved per image: every interior
        boundary payload crosses its cut exactly once (per hop). This is
        the DP's minimized quantity; input delivery is accounted
        separately (:meth:`conveyor_elems_per_image`)."""
        return sum(st.out_spec.elems for st in self.stages[:-1])

    def executed_engine(self, stage: StageSpec) -> str:
        """The engine whose SPMD body the stage actually runs under
        shard_map, resolved through the registry: the route itself when it
        registered a ``make_spmd_body`` (pallas/scan/oracle all do —
        kernel-routed spans run the fused kernel, no scan substitution),
        else its declared ``spmd_fallback``."""
        return registry.resolve_spmd_engine(stage.route.route).name

    # -- SPMD program -------------------------------------------------------

    def _make_body(self, stage: StageSpec):
        return make_stage_body(self.net, stage, self.payload_width,
                               out_rows=self.out_rows, policy=self.policy)

    def _step(self):
        """step(stage_idx, p_flat, slot) -> slot' switching between the
        per-span bodies — only the selected branch executes at run time."""
        bodies = [self._make_body(st) for st in self.stages]

        def step(i_stage, p_flat, slot):
            return lax.switch(i_stage, bodies, p_flat, slot)

        return step

    def _param_rows(self) -> tuple[StageSpec, ...]:
        """One parameter row per mesh position: the stages themselves on
        the rectangular (stage, replica) mesh (replicas share a stage row
        via the replica axis), or per-chip stage copies on the packed
        chip axis (chip c holds exactly its assigned stage's span)."""
        if self.packing == "sum":
            return tuple(self.stages[i] for i in self.assignment.stage_ids())
        return self.stages

    def _stack_params(self, params: Sequence[dict]) -> jax.Array:
        # serving calls reuse the same weights; key the flatten/pad work on
        # the leaf buffers themselves (held by reference — an id() key
        # would go stale when the allocator recycles a freed array's
        # address) so steady-state run() skips it
        leaves = tuple(p[k] for p in params for k in sorted(p))
        cached = getattr(self, "_pstack_cache", None)
        if cached is not None and len(cached[0]) == len(leaves) and \
                all(a is b for a, b in zip(cached[0], leaves)):
            return cached[1]
        if self.policy is not None:
            from repro.occam.quant import casting
            params = casting.quantize_params(list(params), self.policy)
        stacked = jnp.stack([
            _flatten_span_params(params, self.net, *st.span,
                                 width=self.param_width)
            for st in self._param_rows()])
        self._pstack_cache = (leaves, stacked)
        return stacked


class StapPipeline(_SpanProgram):
    """A compiled STAP executor for one (net, partition, plan, batch) tuple.

    Build once, then ``run(params, xs)`` streams batches through the
    replicated span pipeline (the jit caches on the feed/param shapes, so
    repeated runs at one batch size pay no retrace). For mixed batch
    sizes from one compile, serve through :class:`StapRing`
    (``Deployment.serve``) instead.
    """

    def __init__(self, net: NetSpec,
                 partition: PartitionResult | Sequence[int],
                 batch: int, microbatch: int = 1, *,
                 plan: StapPlan | None = None,
                 stage_times: Sequence[float] | None = None,
                 max_chips: int | None = None,
                 max_replicas: int | None = None,
                 target_period: float | None = None,
                 mesh: Mesh | None = None,
                 devices: Sequence | None = None,
                 routes: Sequence[span_engine.SpanRoute] | None = None,
                 out_rows: int = 1, policy=None):
        super().__init__(net, partition, microbatch, plan=plan,
                         stage_times=stage_times, max_chips=max_chips,
                         max_replicas=max_replicas,
                         target_period=target_period, mesh=mesh,
                         devices=devices, routes=routes, out_rows=out_rows,
                         policy=policy)
        self.batch = batch
        self.n_microbatches = -(-batch // microbatch)
        self.schedule = staggered_schedule(self.plan, self.n_microbatches)
        self._fn = jax.jit(self._build())

    # -- static reporting ---------------------------------------------------

    @property
    def conveyor_elems_per_image(self) -> float:
        """Input-conveyor elements moved over stage links per image: each
        of the S-1 non-final rows forwards one (round_width, mb,
        payload_width) feed slot per tick, in every replica column (the
        queue is replicated over the replica axis; padding included — the
        ppermute moves the buffer regardless of content). This replaces
        the old whole-feed broadcast to every chip; on real hardware it
        is input streaming over ICI instead of S host-DRAM reads."""
        sched = self.schedule
        moved = (sched.n_ticks * (sched.n_stages - 1) * sched.max_replicas
                 * sched.round_width * self.microbatch * self.payload_width)
        return moved / self.batch

    @property
    def out_conveyor_elems_per_image(self) -> float:
        """Output-conveyor elements moved over stage links per image: the
        cyclic ring hop forwards every row's one-slot transit buffer each
        tick, in every replica column — the price of banking outputs at
        O(stream/S) per chip instead of every chip holding the full
        (rounds, width, slot) buffer."""
        sched = self.schedule
        if sched.n_stages == 1:
            return 0.0
        moved = (sched.n_ticks * sched.n_stages * sched.max_replicas
                 * sched.round_width * self.microbatch * self.payload_width)
        return moved / self.batch

    def report(self) -> dict:
        """Machine-readable run configuration (benchmarks / examples)."""
        return {
            "boundaries": list(self.boundaries),
            "spans": [list(st.span) for st in self.stages],
            "planned_routes": [st.route.route for st in self.stages],
            "engines": [self.executed_engine(st) for st in self.stages],
            "replicas": list(self.plan.replicas),
            "chips": self.plan.chips,
            "mesh_shape": [self.schedule.n_stages,
                           self.schedule.max_replicas],
            "round_width": self.schedule.round_width,
            "n_rounds": self.schedule.n_rounds,
            "n_ticks": self.schedule.n_ticks,
            "microbatch": self.microbatch,
            "n_microbatches": self.n_microbatches,
            "payload_elems": [st.out_spec.elems for st in self.stages[:-1]],
            "payload_width_padded": self.payload_width,
            "link_elems_per_image": self.link_elems_per_image,
            "conveyor_elems_per_image": self.conveyor_elems_per_image,
            "out_conveyor_elems_per_image": self.out_conveyor_elems_per_image,
            "dp_transfer_elems_per_image": cnn.predicted_transfers(
                self.net, list(self.boundaries)),
            # byte-denominated twins: the same quantities in the bytes
            # the wire actually carries (payloads move in the policy's
            # boundary dtype — 4.0 B/elem for the implicit fp32 policy)
            "payload_bytes_per_elem": self.payload_bytes_per_elem,
            "link_bytes_per_image":
                self.link_elems_per_image * self.payload_bytes_per_elem,
            "conveyor_bytes_per_image":
                self.conveyor_elems_per_image * self.payload_bytes_per_elem,
            "out_conveyor_bytes_per_image":
                self.out_conveyor_elems_per_image
                * self.payload_bytes_per_elem,
        }

    # -- SPMD program -------------------------------------------------------

    def _build(self):
        step = self._step()
        sched, mesh = self.schedule, self.mesh

        def fn(params_stacked, feed):
            return _round_executor(step, params_stacked, feed, mesh, sched)

        return fn

    # -- data movement ------------------------------------------------------

    def _pack_feed(self, xs: jax.Array) -> jax.Array:
        """Flatten + pad the stream, staged for the input conveyor: the
        rounds dimension is padded to S * chunk so ``run`` can shard it
        over the stage axis (chip row i holds rounds [i*chunk,
        (i+1)*chunk)) instead of replicating the whole feed to every
        device — per-chip input memory O(stream/S)."""
        mb, m = self.microbatch, self.n_microbatches
        if self.policy is not None:
            from repro.occam.quant import casting
            xs = casting.quantize(xs, self.policy.boundary,
                                  self.policy.scale)
        xs = jnp.pad(xs, ((0, m * mb - xs.shape[0]),) + ((0, 0),) * 3)
        flat = xs.reshape(m, mb, -1)
        flat = jnp.pad(flat, ((0, self.schedule.n_slots - m), (0, 0),
                              (0, self.payload_width - flat.shape[-1])))
        feed = flat.reshape(self.schedule.n_rounds,
                            self.schedule.round_width, mb,
                            self.payload_width)
        return stage_feed(feed, self.schedule.n_stages)

    def _stage_feed_sharding(self) -> jax.sharding.NamedSharding:
        """Rounds sharded over the stage axis, replicated over replicas."""
        return jax.sharding.NamedSharding(self.mesh, P(STAGE_AXIS))

    def run(self, params: Sequence[dict], xs: jax.Array,
            counter: cnn.TrafficCounter | None = None) -> jax.Array:
        """Stream ``xs`` ((B, H, W, C)) through the pipeline -> (B, ...).

        ``counter`` accumulates the model's off-chip transfers with the
        same engine-independent accounting as ``span_engine``
        (model == machine: totals equal ``predicted_transfers`` x batch).
        """
        if xs.ndim != 4:
            raise ValueError("stap pipeline streams batched (B, H, W, C)")
        if xs.shape[0] != self.batch:
            raise ValueError(f"pipeline compiled for batch {self.batch}, "
                             f"got {xs.shape[0]}")
        bpe = self.payload_bytes_per_elem
        for st in self.stages:
            a, b = st.span
            cnn.count_span_reads(counter, self.net, a, b, self.batch,
                                 bytes_per_elem=bpe)
            cnn.count_span_writes(counter, self.net, b, st.spill, self.batch,
                                  bytes_per_elem=bpe)
        # stage the input onto the mesh up front: each chip row receives
        # only its conveyor chunk of rounds (no whole-feed replication)
        feed = jax.device_put(self._pack_feed(xs), self._stage_feed_sharding())
        staged = self._fn(self._stack_params(params), feed)
        # the executable's output is conveyor-banked (each chip row holds
        # ceil(rounds/S) rounds); reassembly happens here, off the chips
        out = collect_staged_outputs(staged, self.schedule)
        h, w, c = self.net.map_shape(self.net.n_layers)
        flat = out.reshape(self.schedule.n_slots, self.microbatch,
                           self.payload_width)[:self.n_microbatches]
        y = flat[:, :, :h * w * c].reshape(-1, h, w, c)
        if self.policy is not None:
            # the last boundary crossed in the boundary dtype; hand the
            # caller fp32 images (replica-partial summation may have
            # widened an integer dtype — dequantize handles either form)
            from repro.occam.quant import casting
            y = casting.dequantize(y, self.policy.boundary,
                                   self.policy.scale)
        return y[:self.batch]


class StapRing(_SpanProgram):
    """The serving form of the STAP pipeline: ONE compiled fixed-shape
    SPMD tick, iterated host-side over an unbounded stream.

    Where :class:`StapPipeline` lowers a whole fixed-round program per
    stream length (the round count is baked into its ``lax.scan``), the
    ring compiles a single round-width tick: stage i serves the round
    that entered i ticks ago, then every slot's boundary payload hops one
    stage down the pipe — the carried *ring state*, one pending round per
    stage (``ring_depth`` rounds in flight). Every tick's shapes are
    fixed by (round_width, microbatch, payload_width), so one lowering
    serves every submit size; ragged traffic is packed into fixed rounds
    by ``repro.occam.Session`` with a per-stage slot-validity mask
    (masked slots skip their span body via ``lax.cond`` and are excluded
    from outputs and measured traffic by the session).

    Per-chip buffers are O(round_batch), independent of stream length:
    the tick consumes one round, holds one round of ring state, and
    emits one round — the serving limit of the batch pipeline's
    input/output conveyors.
    """

    def __init__(self, net: NetSpec,
                 partition: PartitionResult | Sequence[int],
                 microbatch: int = 1, *,
                 plan: StapPlan,
                 mesh: Mesh | None = None,
                 devices: Sequence | None = None,
                 routes: Sequence[span_engine.SpanRoute] | None = None,
                 out_rows: int = 1,
                 packing: str = "rect",
                 policy=None):
        super().__init__(net, partition, microbatch, plan=plan, mesh=mesh,
                         devices=devices, routes=routes, out_rows=out_rows,
                         packing=packing, policy=policy)
        self.steady = steady_schedule(self.plan)
        self.trace_count = 0   # tick lowerings; regression: stays at 1
        tick = self._build_tick_packed() if self.packing == "sum" \
            else self._build_tick()
        self._tick = jax.jit(tick)
        # windowed tick dispatch timer (occam.calibrate observability);
        # under steady load dispatch wall time converges to the device
        # tick time via XLA's dispatch backpressure
        from repro.occam.calibrate.timers import TickTimers
        self.timers = TickTimers()

    # -- geometry -----------------------------------------------------------

    @property
    def round_width(self) -> int:
        return self.steady.round_width

    @property
    def ring_depth(self) -> int:
        """Rounds in flight (= stages): submit-to-result latency in ticks."""
        return self.steady.ring_depth

    @property
    def round_batch(self) -> int:
        """Images per serving round: round_width slots x microbatch."""
        return self.steady.round_width * self.microbatch

    @property
    def in_sharding(self) -> NamedSharding:
        """Where a round's images belong on their way in: replicated over
        the ring's mesh, as the tick's ``in_round`` spec takes them."""
        return NamedSharding(self.mesh, P())

    def report(self) -> dict:
        """Machine-readable serving configuration."""
        return {
            "boundaries": list(self.boundaries),
            "spans": [list(st.span) for st in self.stages],
            "planned_routes": [st.route.route for st in self.stages],
            "engines": [self.executed_engine(st) for st in self.stages],
            "replicas": list(self.plan.replicas),
            "chips": self.plan.chips,
            "packing": self.packing,
            "mesh_shape": ([self.assignment.n_chips]
                           if self.packing == "sum" else
                           [self.steady.n_stages, self.steady.max_replicas]),
            "round_width": self.round_width,
            "round_batch": self.round_batch,
            "ring_depth": self.ring_depth,
            "microbatch": self.microbatch,
            "payload_width_padded": self.payload_width,
            "link_elems_per_image": self.link_elems_per_image,
            "payload_bytes_per_elem": self.payload_bytes_per_elem,
            "link_bytes_per_image":
                self.link_elems_per_image * self.payload_bytes_per_elem,
            "tick_lowerings": self.trace_count,
            "tick_count": self.timers.count,
            "tick_mean_s": self.timers.mean_s(),
            "tick_busy_fraction": self.timers.busy_fraction(),
        }

    # -- SPMD tick ----------------------------------------------------------

    def init_state(self) -> jax.Array:
        """A zeroed ring: each stage's pending-round payload slots,
        sharded over the (stage, replica) mesh — or over the flat chip
        axis under sum packing. Shape is fixed by the geometry —
        O(round_batch) per chip, stream-independent."""
        if self.packing == "sum":
            state = jnp.zeros((self.assignment.n_chips * self.round_width,
                               self.microbatch, self.payload_width),
                              self._payload_dtype)
            return jax.device_put(state, jax.sharding.NamedSharding(
                self.mesh, P(CHIP_AXIS)))
        s, r = self.steady.n_stages, self.steady.max_replicas
        state = jnp.zeros((s * r * self.round_width, self.microbatch,
                           self.payload_width), self._payload_dtype)
        return jax.device_put(state, jax.sharding.NamedSharding(
            self.mesh, P((STAGE_AXIS, REPLICA_AXIS))))

    def _build_tick(self):
        step = self._step()
        steady, mesh = self.steady, self.mesh
        s_stages, width = steady.n_stages, steady.round_width
        owner = jnp.asarray(np.array(steady.owner_table()))     # (S, R, W)
        perms = [steady.slot_perm(w) for w in range(width)]

        def per_device(params_local, state, in_round, masks):
            i = lax.axis_index(STAGE_AXIS)
            j = lax.axis_index(REPLICA_AXIS)
            p_here = jax.tree.map(lambda l: l[0], params_local)
            slot_in = jnp.where(i == 0, in_round, state)
            # Double-buffered boundary slot (as in ``_round_executor``):
            # ``state`` is the receive buffer, read-only this tick; each
            # slot's hop is issued right after its body so the transfer
            # overlaps the remaining slots' compute.
            ys, hops = [], []
            for w in range(width):
                # masks[i] is the validity of the round at stage i (the
                # session tracks what entered i ticks ago); a masked slot
                # skips its span body entirely
                pred = jnp.logical_and(owner[i, j, w], masks[i, w])
                yw = lax.cond(
                    pred,
                    lambda x: step(i, p_here, x),
                    lambda x: jnp.zeros_like(x),
                    slot_in[w])
                ys.append(yw)
                if s_stages > 1:
                    # boundary payloads hop one stage down the pipe — the
                    # ring state carried to the next tick
                    hops.append(lax.ppermute(
                        yw, (STAGE_AXIS, REPLICA_AXIS), perms[w]))
            y = jnp.stack(ys)
            out = jnp.where(i == s_stages - 1, y, jnp.zeros_like(y))
            state = jnp.stack(hops) if s_stages > 1 else jnp.zeros_like(y)
            return state, out

        mapped = jax.shard_map(per_device, mesh=mesh,
                               in_specs=(P(STAGE_AXIS),
                                         P((STAGE_AXIS, REPLICA_AXIS)),
                                         P(), P()),
                               out_specs=(P((STAGE_AXIS, REPLICA_AXIS)),
                                          P((STAGE_AXIS, REPLICA_AXIS))),
                               check_vma=False)
        r_max, mb = steady.max_replicas, self.microbatch
        h, w, c = self.net.map_shape(self.net.n_layers)
        out_cast = self._lane_cast()

        def fn(params_stacked, state, in_round, masks):
            # trace-time side effect: one increment per lowering, the
            # one-compile-across-submit-sizes regression signal
            self.trace_count += 1
            state, out = mapped(params_stacked, state, in_round, masks)
            # collect the exiting round inside the same dispatch: last
            # stage row only, replica partials summed (still never an
            # inter-replica all-reduce of the whole stream — this is one
            # round), payload lanes cut down to output images
            out = out[(s_stages - 1) * r_max * width:]
            out = out.reshape((r_max, width * mb, self.payload_width)) \
                .sum(axis=0)
            lanes = out_cast(out[:, :h * w * c].reshape(-1, h, w, c))
            return state, lanes

        return fn

    def _lane_cast(self):
        """Exit transform for the round leaving the last stage: the
        payload crossed in the boundary dtype (replica-partial summation
        may have widened an integer form); sessions get fp32 images."""
        if self.policy is None:
            return lambda lanes: lanes
        from repro.occam.quant import casting
        pol = self.policy
        return lambda lanes: casting.dequantize(lanes, pol.boundary,
                                                pol.scale)

    def _build_tick_packed(self):
        """The sum-of-replicas tick: same ring semantics as
        :meth:`_build_tick`, lowered over a flat ``sum(replicas)``-chip
        mesh instead of the rectangular (stage, replica) grid. Each chip
        knows its stage from the static :class:`ChipAssignment` tables;
        slot ownership and the per-slot boundary hops route over flat
        chip ids, so an unbalanced 4-3-2 plan really occupies 9 devices
        (paper §III-E) with no padded idle replicas."""
        step = self._step()
        steady, mesh, asg = self.steady, self.mesh, self.assignment
        s_stages, width = steady.n_stages, steady.round_width
        stage_ids = jnp.asarray(np.array(asg.stage_ids()))       # (C,)
        owner = jnp.asarray(np.array(asg.owner_table(steady)))   # (C, W)
        perms = [asg.slot_perm(steady, w) for w in range(width)]

        def per_device(params_local, state, in_round, masks):
            c = lax.axis_index(CHIP_AXIS)
            i = stage_ids[c]
            p_here = jax.tree.map(lambda l: l[0], params_local)
            slot_in = jnp.where(i == 0, in_round, state)
            # Double-buffered boundary slot (as in the rect tick): the
            # carried ``state`` is read-only this tick; each slot's hop
            # is issued right after its body.
            ys, hops = [], []
            for w in range(width):
                pred = jnp.logical_and(owner[c, w], masks[i, w])
                yw = lax.cond(
                    pred,
                    lambda x: step(i, p_here, x),
                    lambda x: jnp.zeros_like(x),
                    slot_in[w])
                ys.append(yw)
                if s_stages > 1:
                    hops.append(lax.ppermute(yw, CHIP_AXIS, perms[w]))
            y = jnp.stack(ys)
            out = jnp.where(i == s_stages - 1, y, jnp.zeros_like(y))
            state = jnp.stack(hops) if s_stages > 1 else jnp.zeros_like(y)
            return state, out

        mapped = jax.shard_map(per_device, mesh=mesh,
                               in_specs=(P(CHIP_AXIS), P(CHIP_AXIS), P(), P()),
                               out_specs=(P(CHIP_AXIS), P(CHIP_AXIS)),
                               check_vma=False)
        mb = self.microbatch
        h, w, c = self.net.map_shape(self.net.n_layers)
        last0 = asg.offsets[s_stages - 1]       # first last-stage chip
        r_last = asg.replicas[s_stages - 1]
        out_cast = self._lane_cast()

        def fn(params_stacked, state, in_round, masks):
            self.trace_count += 1
            state, out = mapped(params_stacked, state, in_round, masks)
            # collect the exiting round: last-stage chips only, replica
            # partials summed (each served only its owned slots)
            out = out[last0 * width:]
            out = out.reshape((r_last, width * mb, self.payload_width)) \
                .sum(axis=0)
            lanes = out_cast(out[:, :h * w * c].reshape(-1, h, w, c))
            return state, lanes

        return fn

    def tick(self, params: Sequence[dict], state: jax.Array,
             in_round: jax.Array, masks) -> tuple[jax.Array, jax.Array]:
        """Advance the ring one tick.

        ``in_round``: (round_width, mb, payload_width) packed round
        entering stage 0 (see :meth:`pack_round`). ``masks``: (S, W) bool
        — slot validity of the round resident at each stage this tick.
        Returns ``(state', lanes)`` where ``lanes`` (round_batch, h, w, c)
        is the round leaving the last stage (the one submitted
        ``ring_depth - 1`` ticks ago; replica partials combined inside
        the tick's dispatch — one round, never an all-reduce of a
        stream-sized buffer).
        """
        with self.timers.time():
            return self._tick(self._stack_params(params), state,
                              jnp.asarray(in_round),
                              jnp.asarray(masks, dtype=bool))

    # -- data movement ------------------------------------------------------

    def pack_round(self, xs: jax.Array) -> jax.Array:
        """(n <= round_batch, H, W, C) images -> (W, mb, payload_width)
        flat round, zero-padded on trailing lanes (mask them), placed at
        ``in_sharding`` — every tick then sees one input sharding, and
        the tick lowers once wherever the images came from."""
        xs = jnp.asarray(xs)
        pad = self.round_batch - xs.shape[0]
        if pad < 0:
            raise ValueError(f"round takes at most {self.round_batch} "
                             f"images, got {xs.shape[0]}")
        if self.policy is not None:
            from repro.occam.quant import casting
            xs = casting.quantize(xs, self.policy.boundary,
                                  self.policy.scale)
        xs = jnp.pad(xs, ((0, pad),) + ((0, 0),) * 3)
        flat = xs.reshape(self.round_width, self.microbatch, -1)
        flat = jnp.pad(flat, ((0, 0), (0, 0),
                              (0, self.payload_width - flat.shape[-1])))
        return jax.device_put(flat, self.in_sharding)



def stream(params: Sequence[dict], xs: jax.Array, net: NetSpec,
           partition: PartitionResult | Sequence[int], *,
           microbatch: int = 1, plan: StapPlan | None = None,
           stage_times: Sequence[float] | None = None,
           max_chips: int | None = None, max_replicas: int | None = None,
           target_period: float | None = None,
           mesh: Mesh | None = None, devices: Sequence | None = None,
           counter: cnn.TrafficCounter | None = None
           ) -> tuple[jax.Array, StapPipeline]:
    """One-shot convenience wrapper: build the pipeline and stream ``xs``.

    Returns ``(y, pipeline)`` — keep the pipeline object to stream more
    batches without retracing, or read ``pipeline.report()``.
    """
    pipe = StapPipeline(net, partition, xs.shape[0], microbatch, plan=plan,
                        stage_times=stage_times, max_chips=max_chips,
                        max_replicas=max_replicas,
                        target_period=target_period, mesh=mesh,
                        devices=devices)
    return pipe.run(params, xs, counter=counter), pipe
