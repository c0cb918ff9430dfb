"""The trace reduction, on a small recorded trace and on a profiler trace
recorded here."""
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))

# Two chips, 100 ns window [1000, 1100): chip 0 runs a kernel, a copy and
# a collective half hidden behind the copy; chip 1 idles in a gap while
# the harness submits.
SYNTHETIC = {
    "devices": {
        "/device:TPU:0": [["_span_pallas.1", 990, 1040],
                          ["copy.1", 1040, 1060],
                          ["collective-permute-done.2", 1050, 1070],
                          ["_span_pallas.1", 1080, 1120]],
        "/device:TPU:1": [["fusion.3", 1000, 1020],
                          ["all-reduce.4", 1060, 1070]],
    },
    "host": [["bench.window", 1000, 1100], ["bench.submit", 1025, 1055],
             ["bench.materialise", 1070, 1090]],
}


def test_window_busy_and_kernel_time():
    t = SYNTHETIC
    lo, hi = tracereduce.window(t)
    assert (lo, hi) == (1000, 1100)
    chip0 = t["devices"]["/device:TPU:0"]
    # [1000,1070) + [1080,1100)
    assert tracereduce.busy_ns(chip0, lo, hi) == 90
    assert tracereduce.matching_ns(chip0, "_span_pallas", lo, hi) == (60, 2)
    assert tracereduce.busy_ns(t["devices"]["/device:TPU:1"], lo, hi) == 30


def test_top_ops_and_idle_gaps():
    t = SYNTHETIC
    top = tracereduce.top_ops(t, 1000, 1100)
    assert top[0] == ["_span_pallas.1", pytest.approx(60 / 2 / 1e9)]
    gaps = tracereduce.idle_gaps(t, 1000, 1100)
    # chip 1 idles [1020,1060) with the submit span open at its midpoint
    assert gaps[0] == ["bench.submit", pytest.approx(40e-9)]
    assert ["bench.materialise", pytest.approx(30e-9)] in gaps
    assert ["bench.materialise", pytest.approx(10e-9)] in gaps
    quiet = dict(t, host=[["bench.window", 1000, 1100]])
    assert tracereduce.idle_gaps(quiet, 1000, 1100)[0][0] == \
        "no harness span"


def test_subtract_and_union():
    assert tracereduce.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tracereduce.subtract([(0, 10)], [(2, 3), (5, 12)]) == \
        [(0, 2), (3, 5)]
    assert tracereduce.subtract([(0, 2), (4, 6)], []) == [(0, 2), (4, 6)]


RECORDED = os.path.join(HERE, "testdata", "alexnet_sat_trace.json")


def test_recorded_chip_trace():
    """40 ms from the middle of a traced ``alexnet.sat`` window on one
    TPU v5 lite (Python tracing on, so the host was slowed)."""
    with open(RECORDED) as f:
        t = json.load(f)
    lo, hi = tracereduce.window(t)
    assert hi - lo == 40_000_000
    (ops,) = t["devices"].values()
    busy = tracereduce.busy_ns(ops, lo, hi)
    kernel, calls = tracereduce.matching_ns(ops, "_span_pallas", lo, hi)
    assert calls == 4
    assert 3.0e6 < kernel / calls < 4.5e6       # ns per round of 8
    assert kernel < busy < hi - lo
    top = tracereduce.top_ops(t, lo, hi)
    assert [name for name, _ in top[:3]] == ["_span_pallas.1", "pad.2",
                                             "copy"]
    gaps = tracereduce.idle_gaps(t, lo, hi, n=len(ops) + 1)
    assert sum(g for _, g in gaps) == pytest.approx((hi - lo - busy) / 1e9)


def test_load_reads_a_profiler_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tracereduce.find_xplane(str(tmp_path))
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    t = tracereduce.load(path)
    assert t["host"] == [] and t["start_ns"] > 0
    assert "plane" in tracereduce.describe(path)


def test_host_spans_land_on_the_trace_clock(tmp_path):
    """The harness's spans, taken with ``time.time_ns``, fall where the
    profiler itself puts an annotation made between the same readings."""
    jax.profiler.start_trace(str(tmp_path))
    before = time.time_ns()
    with jax.profiler.TraceAnnotation("probe"):
        time.sleep(0.01)
    after = time.time_ns()
    jax.profiler.stop_trace()
    path = tracereduce.find_xplane(str(tmp_path))
    t = tracereduce.load(path)
    tracereduce.add_host_spans(t, [("bench.window", before, after)])
    (lo, hi), = [(s, e) for n, s, e in t["host"] if n == "bench.window"]
    probe = [e for p in jax.profiler.ProfileData.from_file(path).planes
             for line in p.lines for e in line.events if e.name == "probe"]
    assert len(probe) == 1
    assert lo <= probe[0].start_ns <= probe[0].end_ns <= hi
