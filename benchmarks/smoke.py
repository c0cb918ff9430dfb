"""Smoke pass over every executable benchmark family at its smallest
config: one tiny net through the span engine (residual case and out_rows
sweep included), the STAP pipeline, the serving session, the async
continuous-batching engine, the autoplan frontier, and the calibrated
re-scoring pass. A regression gate, not a measurement — each family
must still build, compile and produce sane numbers, in seconds.

Writes nothing under results/ (the tracked BENCH_*.json artifacts come
from the real configs). Re-executes itself with the emulated-device XLA
flags so the pipeline/serving families get a mesh, exactly as
``benchmarks.occam_stap`` does:

    PYTHONPATH=src python -m benchmarks.smoke     # == make bench-smoke
"""
from __future__ import annotations

import tempfile
import time


def _tiny_case():
    import jax

    from repro.core.graph import chain
    from repro.models import cnn

    C, P = "conv", "pool"
    specs = [(C, 3, 1, 1, 8), (C, 3, 1, 1, 8), (P, 2, 2, 0, 0),
             (C, 3, 1, 1, 16)]
    net = chain("smoke_vgg", specs, in_h=12, in_w=12, in_ch=3)
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    xs = jax.random.normal(jax.random.PRNGKey(1), (4, 12, 12, 3))
    return net, params, xs


def smoke_span_engine() -> float:
    from benchmarks import tables

    with tempfile.NamedTemporaryFile(suffix=".json") as tmp:
        rows, derived = tables.occam_span_engine(hw=16, reps=1,
                                                 out_json=tmp.name)
    assert rows and derived > 0
    return derived


def smoke_stap() -> float:
    import jax

    from repro import occam

    net, params, xs = _tiny_case()
    plan = occam.plan(net, 2500, batch=1)
    assert plan.n_spans >= 2
    dep = plan.place(pipeline=True, microbatch=1).compile()
    pipe = dep.pipeline(xs.shape[0])
    y = jax.block_until_ready(pipe.run(params, xs))
    assert y.shape[0] == xs.shape[0]
    return float(plan.n_spans)


def smoke_serve() -> float:
    import numpy as np

    from repro import occam

    net, params, xs = _tiny_case()
    dep = occam.plan(net, 2500, batch=1).place(pipeline=True,
                                               microbatch=1).compile()
    sess = dep.serve(params)
    sess.submit(xs)
    (_t, ys), = sess.results()
    assert np.asarray(ys).shape[0] == xs.shape[0]
    return float(xs.shape[0])


def smoke_autoplan() -> float:
    from repro import occam

    net, params, xs = _tiny_case()
    fr = occam.autoplan(net, occam.Fleet(chips=4, vmem_elems=2500),
                        out_rows="auto")
    assert len(fr.candidates) > 0
    assert all(c.plan.out_rows >= 1 for c in fr)
    return float(len(fr.candidates))


def smoke_async() -> float:
    import asyncio

    import numpy as np

    from repro import occam

    net, params, xs = _tiny_case()
    dep = occam.plan(net, 2500, batch=1).place(pipeline=True,
                                               microbatch=1).compile()

    async def drive() -> int:
        async with occam.AsyncEngine(dep, params, max_wait_ms=20.0) as eng:
            t1 = await eng.submit(xs, tenant="a")
            t2 = await eng.submit(xs[:1], tenant="b")   # aged partial round
            y1, y2 = await t1, await t2
            assert np.asarray(y1).shape[0] == xs.shape[0]
            assert np.asarray(y2).shape[0] == 1
            assert eng.compile_count == 1
            return eng.metrics.snapshot()["total_completions"]

    return float(asyncio.run(drive()))


def smoke_calibrate() -> float:
    from repro import occam

    net, params, xs = _tiny_case()
    fr = occam.autoplan(net, occam.Fleet(chips=4, vmem_elems=2500))
    dep = fr.best().deploy()
    cm = occam.calibrate(dep, params, rounds=1)
    assert cm.macs_per_s > 0 and cm.samples >= 1
    rescored = fr.rescore(cm)
    assert len(rescored) >= 1
    assert rescored.best().plan.calibration is cm
    return float(cm.compute_overhead_factor)


def smoke_quant() -> float:
    import jax.numpy as jnp

    from repro import occam

    net, params, xs = _tiny_case()
    plans = {pol: occam.plan(net, 2500, batch=xs.shape[0], dtype_policy=pol)
             for pol in ("fp32", "int8")}
    assert plans["int8"].predicted.offchip_bytes < \
        plans["fp32"].predicted.offchip_bytes
    dep = plans["int8"].place().compile(interpret=True)
    y = dep.run(params, xs)
    ref = plans["fp32"].place().compile(interpret=True).run(params, xs)
    assert dep.report().matches_prediction_bytes
    return float(jnp.max(jnp.abs(y - ref)))


SMOKES = [
    ("span_engine", smoke_span_engine),
    ("stap_pipeline", smoke_stap),
    ("serve_session", smoke_serve),
    ("async_engine", smoke_async),
    ("autoplan", smoke_autoplan),
    ("calibrate", smoke_calibrate),
    ("quant", smoke_quant),
]


def main() -> None:
    print("smoke,seconds,derived")
    for name, fn in SMOKES:
        t0 = time.perf_counter()
        derived = fn()
        print(f"{name},{time.perf_counter() - t0:.1f},{derived:.4g}")
    print("bench-smoke OK")


if __name__ == "__main__":
    from benchmarks.emulated import launch

    launch("benchmarks.smoke", main)
