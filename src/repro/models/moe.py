"""Capacity-based top-k MoE with two execution paths.

``impl="ep_shard_map"`` (default under a mesh) — production path. Explicit
expert parallelism inside jax.shard_map:

  * tokens are data-sharded and *replicated over the model axis*; every
    model shard routes identically (router compute is negligible);
  * each model shard gathers only the tokens routed to its E/tp local
    experts into an (E/tp, C, D) dispatch buffer — a LOCAL gather, no
    GSPMD scatter involved;
  * local expert GEMMs; local scatter-add back to token space;
  * one psum over the model axis combines partial token outputs — the
    same activation-sized all-reduce a row-parallel dense FFN pays.

``impl="gspmd_scatter"`` — the pure-GSPMD formulation (index scatters with
capacity drop). Kept as the measured baseline: the SPMD partitioner
replicates the combine scatter's (B, S, D) operand on every device
(observed: 8 GiB/device fp32 buffers for olmoe train_4k), which is exactly
the kind of finding the roofline iteration log documents (EXPERIMENTS.md
§Perf).

Both paths share the routing math: per-group capacity C = ceil(T * k / E *
capacity_factor), position-in-expert by exclusive cumsum, over-capacity
drops, Switch load-balance + router z losses.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .sharding import current_ctx, shard


def init_moe(key, d_model: int, moe_cfg, dtype=jnp.bfloat16):
    e, f = moe_cfg.n_experts, moe_cfg.d_ff_expert
    ks = jax.random.split(key, 4)
    si, so = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(f)
    return {
        "router": jax.random.normal(ks[0], (d_model, e), jnp.float32) * si,
        "w1": jax.random.normal(ks[1], (e, d_model, f), dtype) * si,
        "w3": jax.random.normal(ks[2], (e, d_model, f), dtype) * si,
        "w2": jax.random.normal(ks[3], (e, f, d_model), dtype) * so,
    }


def capacity(tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    return max(1, math.ceil(tokens * top_k / n_experts * factor))


def _route(x, router, e, k):
    """Shared routing math. x: (T, D). Returns (w, idx, aux).

    The router contraction keeps x in bf16 with fp32 accumulation —
    materializing x.astype(f32) costs a full (T, D) fp32 copy (2 GiB/device
    at jamba scale, and it lands in the scan carry)."""
    logits = jnp.einsum("td,de->te", x, router.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)                     # (T, K)
    w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    counts = jnp.zeros((x.shape[0], e), jnp.int32)
    t_idx = jnp.broadcast_to(jnp.arange(x.shape[0])[:, None], idx.shape)
    counts = counts.at[t_idx, idx].add(1)
    ce = counts.astype(jnp.float32).mean(axis=0) / k
    lb_loss = e * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    # position-in-expert: exclusive cumsum over tokens (top-k experts of one
    # token are distinct, so no intra-token collision)
    base = jnp.cumsum(counts, axis=0) - counts           # (T, E)
    pos = jnp.take_along_axis(base, idx, axis=-1)        # (T, K)
    return w, idx, pos, (lb_loss, z_loss)


def _expert_ffn(xg, w1, w3, w2):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w1))
    h = h * jnp.einsum("ecd,edf->ecf", xg, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _local_moe(x2d, router, w1, w3, w2, *, e_total, k, cap_factor,
               e_start, sentinel_t):
    """Route local tokens, run the LOCAL experts, return partial outputs.

    x2d: (T, D); w1/w3/w2 hold e_local experts starting at e_start.
    Output is the partial token-space result covering local experts only.
    """
    t, d = x2d.shape
    e_local = w1.shape[0]
    c = capacity(t, e_total, k, cap_factor)
    w, idx, pos, aux = _route(x2d, router, e_total, k)
    # local expert slot maps (tokens routed elsewhere -> dropped locally).
    # NB: negative indices WRAP in jax scatters, so foreign experts must be
    # remapped to an out-of-bounds sentinel (e_local) for mode="drop".
    local = jnp.logical_and(idx >= e_start, idx < e_start + e_local)
    idx_loc = jnp.where(local, idx - e_start, e_local)
    t_idx = jnp.broadcast_to(jnp.arange(t)[:, None], idx.shape)
    src = jnp.full((e_local, c), sentinel_t, jnp.int32)
    src = src.at[idx_loc, pos].set(t_idx, mode="drop")   # OOB e/pos dropped
    wslot = jnp.zeros((e_local, c), jnp.float32)
    wslot = wslot.at[idx_loc, pos].set(w, mode="drop")
    xg = x2d[jnp.clip(src, 0, t - 1)]                    # (e_local, C, D)
    ye = _expert_ffn(xg, w1, w3, w2)
    ye = ye * wslot[..., None].astype(ye.dtype)
    y = jnp.zeros((t, d), ye.dtype)
    y = y.at[src.reshape(-1)].add(ye.reshape(-1, d), mode="drop")
    return y, aux


def moe_sublayer(p, x: jax.Array, moe_cfg, impl: str | None = None
                 ) -> tuple[jax.Array, dict]:
    """x: (B, S, D) -> (y, aux)."""
    ctx = current_ctx()
    if impl is None:
        impl = "ep_shard_map" if ctx is not None else "local"
    if impl == "gspmd_scatter":
        return _moe_gspmd_scatter(p, x, moe_cfg)

    b, s, d = x.shape
    e, k = moe_cfg.n_experts, moe_cfg.top_k

    if ctx is None:  # single-device (smoke tests): all experts local
        y2, aux = _local_moe(x.reshape(b * s, d), p["router"], p["w1"],
                             p["w3"], p["w2"], e_total=e, k=k,
                             cap_factor=moe_cfg.capacity_factor,
                             e_start=0, sentinel_t=b * s)
        return (y2.reshape(b, s, d),
                {"load_balance_loss": aux[0], "router_z_loss": aux[1]})

    mesh = ctx.mesh
    model = ctx.model_axis
    tp = mesh.shape[model]
    if e % tp:
        raise ValueError(f"n_experts={e} not divisible by tp={tp}")
    from jax.sharding import PartitionSpec as P

    dp = 1
    for a in ctx.data_axes:
        dp *= mesh.shape[a]
    if b % dp == 0:
        data_spec = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
    else:  # e.g. long_500k B=1: batch can't shard; replicate over data
        data_spec = None

    def shmap_fn(xl, router, w1, w3, w2):
        bl, sl, dl = xl.shape
        r = jax.lax.axis_index(model)
        y2, aux = _local_moe(
            xl.reshape(bl * sl, dl), router, w1, w3, w2,
            e_total=e, k=k, cap_factor=moe_cfg.capacity_factor,
            e_start=r * (e // tp), sentinel_t=bl * sl)
        y = jax.lax.psum(y2, model).reshape(bl, sl, dl)
        lb = aux[0]  # identical on every shard (same routing inputs)
        z = aux[1]
        return y, lb, z

    y, lb, z = jax.shard_map(
        shmap_fn, mesh=mesh,
        in_specs=(P(data_spec, None, None), P(None, None),
                  P(model, None, None), P(model, None, None),
                  P(model, None, None)),
        out_specs=(P(data_spec, None, None), P(), P()),
        check_vma=False,
    )(x, p["router"], p["w1"], p["w3"], p["w2"])
    return y, {"load_balance_loss": lb, "router_z_loss": z}


# --------------------------------------------------------------------------
# Pure-GSPMD baseline (kept for the §Perf before/after record)
# --------------------------------------------------------------------------

def _moe_gspmd_scatter(p, x: jax.Array, moe_cfg) -> tuple[jax.Array, dict]:
    b, s, d = x.shape
    e, k = moe_cfg.n_experts, moe_cfg.top_k
    c = capacity(s, e, k, moe_cfg.capacity_factor)

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(axis=(0, 1))
    onehot_e = jax.nn.one_hot(idx, e, dtype=jnp.int32)
    counts_tok = onehot_e.sum(2)
    ce = counts_tok.astype(jnp.float32).mean(axis=(0, 1)) / k
    lb_loss = e * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    base = jnp.cumsum(counts_tok, axis=1) - counts_tok
    pos = jnp.take_along_axis(base, idx, axis=-1)

    b_idx = jnp.broadcast_to(jnp.arange(b)[:, None, None], (b, s, k))
    s_idx = jnp.broadcast_to(jnp.arange(s)[None, :, None], (b, s, k))
    src = jnp.full((b, e, c), s, jnp.int32)
    src = src.at[b_idx, idx, pos].set(s_idx, mode="drop")
    wslot = jnp.zeros((b, e, c), jnp.float32)
    wslot = wslot.at[b_idx, idx, pos].set(w, mode="drop")

    xg = jnp.take_along_axis(
        x[:, :, None, :],
        jnp.clip(src, 0, s - 1).reshape(b, e * c)[:, :, None, None],
        axis=1).reshape(b, e, c, d)
    xg = shard(xg, "data", "model", None, None)

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xg, p["w1"]))
    h = h * jnp.einsum("becd,edf->becf", xg, p["w3"])
    ye = jnp.einsum("becf,efd->becd", h, p["w2"])
    ye = ye * wslot[..., None].astype(ye.dtype)

    be_idx = jnp.broadcast_to(jnp.arange(b)[:, None, None], (b, e, c))
    y = jnp.zeros((b, s, d), ye.dtype)
    y = y.at[be_idx, src].add(ye, mode="drop")
    y = shard(y, "data", None, None)
    return y, {"load_balance_loss": lb_loss, "router_z_loss": z_loss}
