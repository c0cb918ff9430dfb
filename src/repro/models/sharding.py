"""Sharding context: translate symbolic axes to mesh PartitionSpecs.

Model code calls ``shard(x, "data", None, "model")`` with *symbolic* axis
names; a ShardCtx (installed by the launcher / dry-run) maps them onto the
real mesh axes:

    "data"  -> ctx.data_axes   (("data",) single-pod, ("pod", "data") multi)
    "model" -> ctx.model_axis
    "both"  -> data_axes + (model_axis,)

Outside any context (CPU smoke tests) ``shard`` is the identity, so the
same model code runs unsharded.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterator

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    mesh: Mesh
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    # attention sharding strategy: "heads" (TP over heads; requires
    # divisibility) or "batch" (all-to-all to batch-sharded attention —
    # exact for any head count, used by qwen2-vl/minitron/qwen2.5)
    attn_strategy: str = "heads"
    # decode KV-cache layout: "heads" or "seq" (sequence-sharded cache,
    # flash-decoding style partial softmax; required when heads don't
    # divide or batch is tiny e.g. long_500k)
    decode_kv: str = "heads"
    # extra symbolic axes (e.g. cache_b/cache_s decode layouts); values are
    # raw PartitionSpec entries: a mesh-axis name, tuple of names, or None.
    symbols: tuple[tuple[str, object], ...] = ()


_CTX: ShardCtx | None = None


@contextlib.contextmanager
def use_shardings(ctx: ShardCtx | None) -> Iterator[None]:
    global _CTX
    prev, _CTX = _CTX, ctx
    try:
        yield
    finally:
        _CTX = prev


def current_ctx() -> ShardCtx | None:
    return _CTX


def resolve(*axes) -> P:
    """Symbolic axes -> PartitionSpec under the current context."""
    ctx = _CTX
    assert ctx is not None
    symbols = dict(ctx.symbols)
    out = []
    data = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
    defaults = {"act_seq": None, "cache_b": data, "cache_s": ctx.model_axis}
    for a in axes:
        if a is None:
            out.append(None)
        elif a in symbols:
            out.append(symbols[a])
        elif a == "data":
            out.append(data)
        elif a == "model":
            out.append(ctx.model_axis)
        elif a == "both":
            out.append(ctx.data_axes + (ctx.model_axis,))
        elif a in defaults:
            out.append(defaults[a])
        else:
            raise ValueError(f"unknown symbolic axis {a!r}")
    return P(*out)


def shard(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint under a ShardCtx; identity otherwise."""
    ctx = _CTX
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, resolve(*axes)))


def named(*axes) -> NamedSharding | None:
    ctx = _CTX
    if ctx is None:
        return None
    return NamedSharding(ctx.mesh, resolve(*axes))
