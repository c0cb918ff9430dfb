"""The benchmark's own yardstick for a CNN body: seeded weights, a plain
``jax.numpy`` forward pass, its lower-precision controls, and the
comparison that decides ``correct``.

Nothing here imports the system under test. The net is read from the
configuration file's ``net`` block (the chain of ``[kind, k, stride,
padding, out_ch]`` layers and the residual edges), so the geometry is
worked out here again, independently of ``repro.core.graph``.
"""
from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST

# float8_e4m3fn's largest finite value is 448; operands are scaled so that
# a tensor's largest magnitude lands on 256 before rounding.
_FP8_TARGET = 256.0


@dataclasses.dataclass(frozen=True)
class Layer:
    kind: str           # "conv" | "pool"
    k: int
    stride: int
    padding: int
    in_ch: int
    out_ch: int
    in_h: int
    in_w: int

    @property
    def out_h(self) -> int:
        return (self.in_h + 2 * self.padding - self.k) // self.stride + 1

    @property
    def out_w(self) -> int:
        return (self.in_w + 2 * self.padding - self.k) // self.stride + 1


def layers(net: dict) -> list[Layer]:
    """The configuration's chain with every map's geometry threaded."""
    out, h, w, c = [], net["in_h"], net["in_w"], net["in_ch"]
    for kind, k, stride, padding, out_ch in net["layers"]:
        if kind not in ("conv", "pool"):
            raise ValueError(f"unknown layer kind {kind!r}")
        lay = Layer(kind, k, stride, padding, c,
                    out_ch if kind == "conv" else c, h, w)
        out.append(lay)
        h, w, c = lay.out_h, lay.out_w, lay.out_ch
    return out


def map_shape(net: dict, i: int) -> tuple[int, int, int]:
    """(h, w, c) of feature map i (0 = the input)."""
    if i == 0:
        return net["in_h"], net["in_w"], net["in_ch"]
    lay = layers(net)[i - 1]
    return lay.out_h, lay.out_w, lay.out_ch


def conv_macs(net: dict, a: int = 0, b: int | None = None) -> int:
    """Multiply-accumulates of the conv layers a..b-1 for one image."""
    lays = layers(net)
    return sum(l.out_h * l.out_w * l.out_ch * l.k * l.k * l.in_ch
               for l in lays[a:b] if l.kind == "conv")


# --------------------------------------------------------------------------
# Weights and inputs, from the seed
# --------------------------------------------------------------------------

def key_from_seed(seed: int) -> jax.Array:
    """JAX's key for a seed of any size (two 31-bit words of it)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.key(int(words[0]) & 0x7FFFFFFF),
                              int(words[1]) & 0x7FFFFFFF)


def make_params(net: dict, key: jax.Array) -> list[dict]:
    """He-normal conv weights (HWIO) and small biases, made on the device
    in one jitted call, in the ``[{"w", "b"} or {}]`` per-layer layout the
    serving path takes."""
    lays = layers(net)

    @jax.jit
    def init(key):
        params = []
        for lay in lays:
            if lay.kind != "conv":
                params.append({})
                continue
            key, kw, kb = jax.random.split(key, 3)
            fan_in = lay.k * lay.k * lay.in_ch
            w = jax.random.normal(kw, (lay.k, lay.k, lay.in_ch, lay.out_ch),
                                  jnp.float32) * np.float32(
                                      np.sqrt(2.0 / fan_in))
            b = jax.random.normal(kb, (lay.out_ch,), jnp.float32) * \
                np.float32(0.01)
            params.append({"w": w, "b": b})
        return params

    return init(key)


def make_pool(net: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` distinct standard-normal images on the host, as a client's
    images arrive: (n, H, W, C) float32."""
    return rng.standard_normal((n,) + map_shape(net, 0), dtype=np.float32)


# --------------------------------------------------------------------------
# The plain forward pass and its controls
# --------------------------------------------------------------------------

def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _round_fp8(x):
    scale = _FP8_TARGET / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


# precision -> (conv operand rounding, stored-activation rounding)
PRECISIONS = {
    "highest": (None, None),
    "bf16": (_round_bf16, _round_bf16),
    "fp8": (_round_fp8, None),
}


def _shortcut(src, h_t: int, w_t: int, c_t: int):
    """Residual 'option A' as the configuration states it: strided
    subsample of the source map, channels zero-padded or cut."""
    h_s, w_s, c_s = src.shape
    sh, sw = max(h_s // h_t, 1), max(w_s // w_t, 1)
    y = src[::sh, ::sw, :][:h_t, :w_t, :]
    if c_t > c_s:
        y = jnp.pad(y, ((0, 0), (0, 0), (0, c_t - c_s)))
    return y[:, :, :c_t]


def forward(params: list[dict], x, net: dict, precision: str = "highest"):
    """One image (H, W, C) through the conv body. Convolutions are fp32
    products at ``Precision.HIGHEST`` of operands rounded as
    ``precision`` says; ``"highest"`` rounds nothing."""
    rnd_op, rnd_act = PRECISIONS[precision]
    maps = [x]
    for i, lay in enumerate(layers(net)):
        h = maps[-1]
        if lay.kind == "conv":
            w = params[i]["w"]
            if rnd_op is not None:
                h, w = rnd_op(h), rnd_op(w)
            p = lay.padding
            y = lax.conv_general_dilated(
                h[None], w, (lay.stride, lay.stride), ((p, p), (p, p)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=HIGHEST)[0]
            y = jax.nn.relu(y + params[i]["b"])
        else:
            p = lay.padding
            y = lax.reduce_window(
                h, -jnp.inf, lax.max, (lay.k, lay.k, 1),
                (lay.stride, lay.stride, 1), ((p, p), (p, p), (0, 0)))
        for s, t in net.get("residual_edges", ()):
            if t == i + 1:
                y = y + _shortcut(maps[s], *y.shape)
        if rnd_act is not None:
            y = rnd_act(y)
        maps.append(y)
    return maps[-1]


def reference_outputs(params, pool: np.ndarray, net: dict,
                      precision: str = "highest", block: int = 8
                      ) -> np.ndarray:
    """The forward pass over every pool image, ``block`` images at a
    time, back on the host as float64."""
    fn = jax.jit(jax.vmap(lambda p, x: forward(p, x, net, precision),
                          in_axes=(None, 0)))
    outs = [np.asarray(fn(params, jnp.asarray(pool[i:i + block])),
                       np.float64)
            for i in range(0, len(pool), block)]
    return np.concatenate(outs)


# --------------------------------------------------------------------------
# The comparison
# --------------------------------------------------------------------------

class Envelope:
    """Elementwise min and max of every output served for each pool
    image. The worst gap of any served output from the reference is
    then read from the two envelopes alone, so every output is compared
    while only two arrays per pool image are kept. Thread-safe: outputs
    are folded in from the materialising threads."""

    def __init__(self, n_pool: int, out_shape: tuple[int, ...]):
        self.lo = np.full((n_pool,) + tuple(out_shape), np.inf, np.float32)
        self.hi = np.full((n_pool,) + tuple(out_shape), -np.inf, np.float32)
        self.served = np.zeros(n_pool, np.int64)
        self.bad_shapes = 0
        self._lock = threading.Lock()

    def fold(self, idx, ys: np.ndarray) -> None:
        if ys.shape[1:] != self.lo.shape[1:] or len(ys) != len(idx):
            with self._lock:
                self.bad_shapes += 1
            return
        with self._lock:
            for i, y in zip(idx, ys):
                np.minimum(self.lo[i], y, out=self.lo[i])
                np.maximum(self.hi[i], y, out=self.hi[i])
                self.served[i] += 1

    def worst_rel_err(self, ref: np.ndarray) -> float:
        """max over served pool images of max|y - ref| / max|ref|. A NaN
        anywhere reads as infinity."""
        worst = 0.0
        for i in np.nonzero(self.served)[0]:
            gap = np.maximum(np.abs(self.hi[i] - ref[i]),
                             np.abs(self.lo[i] - ref[i]))
            if not np.all(np.isfinite(gap)):
                return float("inf")
            worst = max(worst, float(gap.max() / np.abs(ref[i]).max()))
        return worst


def rel_err(ys: np.ndarray, ref: np.ndarray) -> float:
    """The same number for outputs held whole: max over images of
    max|y - ref| / max|ref|."""
    gap = np.abs(np.asarray(ys, np.float64) - ref)
    if not np.all(np.isfinite(gap)):
        return float("inf")
    per = gap.reshape(len(ref), -1).max(1) / \
        np.abs(ref).reshape(len(ref), -1).max(1)
    return float(per.max())
