"""Occam pipeline runtime: DP-optimal partitions as pipeline stages.

This is contribution C3+C4 made executable for transformers:

  1. ``plan_stages`` — run the paper's DP (repro.core.partition) over the
     layer chain with an HBM capacity model -> contiguous layer spans.
  2. ``plan_stap`` — stage latency model (FLOPs/chip-rate) -> replication
     counts for bottleneck stages (STAP; see repro.core.stap).
  3. ``pipeline_forward`` — an executable GPipe-style microbatch pipeline
     over a ``stage`` mesh axis using shard_map + ppermute: each stage
     holds only its span's weights (chip-residency: weights load once and
     stay — the paper's full cross-image filter reuse), microbatches
     stream through, boundary activations are the only inter-stage
     traffic (exactly the quantity the DP minimized).

The schedule runs S + M - 1 ticks for S stages x M microbatches. STAP
*staggering* (microbatch m -> replica m mod r_i) is executable too: pass a
``plan`` (or per-stage ``replicas``) and a (stage, replica) mesh and
``pipeline_forward`` delegates to the staggered round executor in
``repro.runtime.stap_pipeline`` (which also runs heterogeneous Occam span
stages; the discrete-event simulator in core.stap verifies the throughput
claims).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.partition import PartitionResult, partition_transformer
from repro.core.stap import StapPlan, plan_replication


@dataclasses.dataclass(frozen=True)
class StagePlan:
    partition: PartitionResult
    stage_spans: tuple[tuple[int, int], ...]
    stage_flops: tuple[float, ...]
    stap: StapPlan


def plan_stages(layer_weight_bytes: Sequence[float],
                layer_act_bytes: Sequence[float],
                layer_flops: Sequence[float],
                boundary_act_bytes: float,
                stage_capacity_bytes: float,
                chip_flops_per_s: float = 197e12,
                extra_chips: int = 0) -> StagePlan:
    """DP partition -> stages; STAP replication under a chip budget."""
    part = partition_transformer(layer_weight_bytes, layer_act_bytes,
                                 boundary_act_bytes, stage_capacity_bytes)
    spans = tuple((sp.start, sp.end) for sp in part.spans)
    flops = tuple(float(sum(layer_flops[a:b])) for a, b in spans)
    times = [f / chip_flops_per_s for f in flops]
    stap = plan_replication(times, max_chips=len(spans) + extra_chips)
    return StagePlan(part, spans, flops, stap)


def pipeline_forward(stage_fn: Callable, stage_params,
                     microbatches: jax.Array, mesh: Mesh,
                     axis: str = "stage",
                     plan: StapPlan | Sequence[int] | None = None
                     ) -> jax.Array:
    """Run M microbatches through S pipeline stages.

    stage_fn(stage_params_slice, x) -> y, same shape as x.
    stage_params: pytree with leading stage dim S on every leaf (stage s
        holds slice s — its Occam span's weights, resident for the whole
        stream).
    microbatches: (M, mb, ...) replicated input.
    plan: optional STAP replication — a :class:`StapPlan` or per-stage
        replica counts. Requires ``mesh`` to carry a second ("replica")
        axis of width max(replicas); microbatch m is staggered onto
        replica m mod r_i (paper §III-E) by the round executor in
        ``repro.runtime.stap_pipeline``.
    Returns (M, mb, ...) outputs (as produced by the last stage).
    """
    if plan is not None:
        from repro.runtime import stap_pipeline

        if not isinstance(plan, StapPlan):
            # synthesize a plan from bare replica counts; with unit stage
            # times the closed-form throughput min_i r_i/t_i is min(reps)
            reps = tuple(int(r) for r in plan)
            plan = StapPlan((1.0,) * len(reps), reps, float(min(reps)),
                            float(len(reps)), sum(reps))
        replica_axis = next(
            (a for a in mesh.axis_names if a != axis),
            stap_pipeline.REPLICA_AXIS)
        return stap_pipeline.replicated_forward(
            stage_fn, stage_params, microbatches, mesh, plan,
            stage_axis=axis, replica_axis=replica_axis)

    s_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    ticks = s_stages + m - 1

    def per_stage(params_local, mbs):
        # params_local leaves: (1, ...) — this stage's span weights.
        idx = lax.axis_index(axis)
        p_here = jax.tree.map(lambda l: l[0], params_local)
        buf = jnp.zeros_like(mbs[0])
        outs0 = jnp.zeros_like(mbs)

        def tick(carry, t):
            buf, outs = carry
            mb_id = t - idx
            active = jnp.logical_and(mb_id >= 0, mb_id < m)
            x_in = jnp.where(idx == 0,
                             mbs[jnp.clip(mb_id, 0, m - 1)], buf)
            y = stage_fn(p_here, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage deposits its finished microbatch
            is_last = idx == s_stages - 1
            outs = lax.cond(
                jnp.logical_and(active, is_last),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb_id, 0, m - 1), 0),
                lambda o: o, outs)
            # boundary activations move one hop down the chain (the only
            # inter-stage traffic — the DP's minimized quantity)
            nxt = lax.ppermute(
                y, axis, [(i, (i + 1) % s_stages) for i in range(s_stages)])
            return (nxt, outs), None

        (_, outs), _ = lax.scan(tick, (buf, outs0), jnp.arange(ticks))
        # finished microbatches stay on the last stage; the stage-sharded
        # output below is sliced, not psum-broadcast (a psum here would
        # move S x M x |act| zeros per call for one stage's payload)
        return outs

    out = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False,
    )(stage_params, microbatches)
    # the last stage's block is not a whole shard of the stage axis, and an
    # Explicit-axes mesh (jax.make_mesh) refuses an implicit resharding
    # slice — gather to replicated explicitly, then cut the block out
    out = jax.sharding.reshard(out, NamedSharding(mesh, P()))
    return out[(s_stages - 1) * m:]
