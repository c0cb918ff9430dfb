"""The serving path's own instruments: the span recorder
(``occam.serve.trace``) and the session's completion watcher.

A single-chip deployment of a tiny net in interpret mode serves through
``AsyncEngine``; spans link each request to the rounds that carried it,
tickets resolve only once the device has finished their round, and the
tick timers hold device round times once ``Session.sync`` returns."""
import asyncio
import gc
import sys
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro import occam
from repro.core.graph import chain
from repro.models import cnn
from repro.occam.calibrate import TickTimers
from repro.occam.serve import trace
from repro.occam.serve.trace import SpanRecorder

C = "conv"


@pytest.fixture(scope="module")
def tiny():
    net = chain("t", [(C, 3, 1, 1, 4), (C, 3, 2, 1, 8)], in_h=10, in_w=10,
                in_ch=3)
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    dep = occam.plan(net, 10**6).place().compile(interpret=True)
    return net, params, dep


def _images(net, n, seed):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (n,) + net.map_shape(0))


def _serve(dep, params, xs_list, *, record=False, **kw):
    """Submit every request in order, drain, await every ticket; returns
    the outputs and the engine."""
    async def drive():
        eng = occam.AsyncEngine(dep, params, round_batch=4, **kw)
        if record:
            eng.spans.enable()
        async with eng:
            tickets = [await eng.submit(xs) for xs in xs_list]
            await eng.drain()
            ys = [await t for t in tickets]
        eng.spans.disable()
        return ys, eng

    return asyncio.run(drive())


# --------------------------------------------------------------------------
# The recorder
# --------------------------------------------------------------------------

def test_recorder_records_nothing_while_off(tiny, monkeypatch):
    """Off, no site reads the clock and nothing is kept."""
    net, params, dep = tiny

    def no_clock():
        raise AssertionError("a recording site read the clock while off")

    monkeypatch.setattr(trace, "now", no_clock)
    ys, eng = _serve(dep, params, [_images(net, 3, 1), _images(net, 5, 2)])
    assert [y.shape[0] for y in ys] == [3, 5]
    assert not eng.spans.on
    assert eng.spans.drain() == [] and eng.spans.dropped == 0


def test_spans_link_each_request_to_its_rounds(tiny):
    """Three requests of 3, 3 and 2 images in rounds of 4: the second
    request straddles both rounds. Every span names its cause, and all
    spans of one request or one round share its uid."""
    net, params, dep = tiny
    sizes = [3, 3, 2]
    xs = [_images(net, n, 10 + i) for i, n in enumerate(sizes)]
    _ys, eng = _serve(dep, params, xs, record=True)
    spans = eng.spans.drain()
    assert eng.spans.dropped == 0

    def only(name, uid):
        hits = [s for s in spans if s.name == name and s.id == uid]
        assert len(hits) == 1, (name, uid, hits)
        return hits[0]

    stages = sorted((s for s in spans if s.name == "occam.stage"),
                    key=lambda s: s.id)
    assert [s.requests for s in stages] == [(0, 1), (1, 2)]
    assert [s.images for s in stages] == [4, 4]
    for st in stages:
        r = st.id
        assert st.parent == -1 and st.round == r
        dispatch = only("occam.dispatch", r)
        device = only("occam.device", r)
        deliver = only("occam.deliver", r)
        for s in (dispatch, device, deliver):
            assert s.parent == r and s.round == r and s.images == 4
        assert st.end_ns <= dispatch.start_ns < dispatch.end_ns
        assert device.start_ns == dispatch.end_ns < device.end_ns
    last_round = {0: 0, 1: 1, 2: 1}
    for uid, n in enumerate(sizes):
        req = only("occam.request", uid)
        sub = only("occam.submit", uid)
        queued = only("occam.queued", uid)
        assert req.parent == -1 and req.images == n
        assert sub.parent == queued.parent == uid
        assert req.round == last_round[uid]
        assert req.start_ns <= sub.start_ns < sub.end_ns <= req.end_ns
        assert sub.end_ns <= queued.end_ns
        # the wait ends inside the stage that took the last lane, and the
        # request ends after that round's outputs were ready
        st = stages[req.round]
        assert st.start_ns <= queued.end_ns <= st.end_ns
        assert only("occam.device", req.round).end_ns <= req.end_ns
    assert len(spans) == 3 * len(sizes) + 4 * len(stages)


def test_dropped_counts_spans_past_capacity(monkeypatch):
    monkeypatch.setattr(trace, "CAPACITY", 3)
    rec = SpanRecorder()
    rec.enable()
    for i in range(5):
        rec.record("x", i, i + 1, i)
    assert rec.dropped == 2
    assert [s.id for s in rec.drain()] == [0, 1, 2]
    assert rec.drain() == []
    rec.enable()                    # a new record starts from nothing
    assert rec.dropped == 0


def test_drain_gives_epoch_nanoseconds():
    rec = SpanRecorder()
    rec.enable()
    before = time.time_ns()
    t0 = trace.now()
    time.sleep(0.01)
    t1 = trace.now()
    after = time.time_ns()
    rec.record("x", t0, t1, 0)
    (s,) = rec.drain()
    slack = 1_000_000
    assert before - slack <= s.start_ns < s.end_ns <= after + slack
    assert s.end_ns - s.start_ns == t1 - t0


# --------------------------------------------------------------------------
# The completion watcher
# --------------------------------------------------------------------------

def _slow_step(out_shape, iters=400):
    """A stand-in serving step that keeps the device busy for a while
    before its outputs are ready."""
    def step(params, xs):
        m = jnp.full((256, 256), 1e-3)
        m = lax.fori_loop(0, iters, lambda _i, m: jnp.tanh(m @ m), m)
        return jnp.zeros((xs.shape[0],) + out_shape) + m[0, 0]

    return jax.jit(step), {"lowerings": 0}


def test_ticket_resolves_at_device_completion(tiny, monkeypatch):
    """A ticket resolves only once its outputs are ready, and its latency
    sample spans at least its round's time on the device."""
    net, params, dep = tiny
    out_shape = net.map_shape(net.n_layers)
    step = _slow_step(out_shape)
    monkeypatch.setattr(dep, "_serve_step", lambda round_batch: step)
    xs = _images(net, 4, 3)
    jax.block_until_ready(step[0](params, xs))     # compiled before

    async def drive():
        eng = occam.AsyncEngine(dep, params, round_batch=4)
        eng.spans.enable()
        async with eng:
            ticket = await eng.submit(xs)
            y = await ticket
            ready_when_resolved = y.is_ready()
        return y, eng, ready_when_resolved

    y, eng, ready = asyncio.run(drive())
    assert ready
    assert y.shape == (4,) + out_shape
    (device,) = [s for s in eng.spans.drain() if s.name == "occam.device"]
    device_s = (device.end_ns - device.start_ns) / 1e9
    assert device_s > 0.005         # the stand-in step really was slow
    latency = eng.metrics.snapshot()["latency_p50_s"]
    assert latency >= device_s


def test_sync_waits_for_the_watcher(tiny):
    """After ``sync`` the timers hold one device time per tick, and
    together they fit inside the wall time the ticks took."""
    net, params, dep = tiny
    sess = dep.serve(params, round_batch=4)
    sess.submit(_images(net, 4, 4))     # compile outside the timed part
    sess.results()
    sess.sync()
    before = sess.timers.count
    t0 = time.perf_counter()
    for i in range(3):
        sess.submit(_images(net, 4, 5 + i))
    sess.sync()
    wall = time.perf_counter() - t0
    assert sess.timers.count - before == 3
    newest = [d for _t, d in list(sess.timers.events)[-3:]]
    assert all(d > 0 for d in newest) and sum(newest) <= wall
    assert sess.report().timing["tick_count"] == sess.timers.count
    sess.close()


def test_watcher_outlives_a_failing_callback(tiny, caplog):
    """A callback that raises is logged and its outputs still count as
    completed: ``sync`` returns and later ticks are still timed."""
    net, params, dep = tiny
    sess = dep.serve(params, round_batch=4)
    sess.submit(_images(net, 4, 20))
    (_t, lanes), = sess.results()

    def fail(_ready_ns):
        raise KeyError("callback bug")

    sess.watch(lanes, fail)
    waiter = threading.Thread(target=sess.sync, daemon=True)
    waiter.start()
    waiter.join(timeout=60)
    assert not waiter.is_alive(), "sync never saw the outputs complete"
    assert "completion callback failed" in caplog.text
    before = sess.timers.count
    sess.submit(_images(net, 4, 21))
    sess.sync()
    assert sess.timers.count == before + 1
    sess.close()


def test_engine_dropped_unstopped_lets_its_watcher_end(tiny):
    """An engine that is never stopped, once dropped and collected,
    leaves no watcher thread behind holding it and its weights."""
    net, params, dep = tiny

    async def drive():
        eng = occam.AsyncEngine(dep, params, round_batch=4)
        await (await eng.submit(_images(net, 4, 30)))
        return eng._session._watch._thread, weakref.ref(eng)

    thread, eng = asyncio.run(drive())
    assert thread.is_alive()
    deadline = time.monotonic() + 30
    while (eng() is not None or thread.is_alive()) \
            and time.monotonic() < deadline:
        gc.collect()
        thread.join(timeout=0.05)
    assert eng() is None
    assert not thread.is_alive()


def test_tick_timers_under_concurrent_record_and_read():
    """The watcher records while the event loop reads: no update is
    lost and no reader sees a deque mid-change."""
    timers = TickTimers(horizon_s=1e9)
    writers, each = 8, 2000
    errors = []

    def write():
        for _ in range(each):
            timers.record(1e-6)

    def read():
        try:
            for _ in range(2000):
                timers.busy_fraction()
                timers.mean_s()
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write) for _ in range(writers)]
        threads += [threading.Thread(target=read) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors
    assert timers.count == writers * each == len(timers.events)
    assert timers.total_s == pytest.approx(writers * each * 1e-6)
