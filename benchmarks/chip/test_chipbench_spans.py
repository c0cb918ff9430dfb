"""The program's own spans on the profiler's clock, and the reader of the
span kernels' stable names (``top_span_roofline``)."""
import json
import os
import time
import types

import jax
import pytest

import benchspec
import devicepeaks
import spanrun
import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = devicepeaks.PEAKS["TPU v5 lite"]
TOP = benchspec.metric_reader("top_span_roofline")
ALL_SPANS = benchspec.metric_reader("span_kernel_roofline")


def _net(name):
    bench = benchspec.load_benchmark()
    (cfg,) = [c for c in bench["configs"] if c["name"] == name]
    with open(os.path.join(benchspec.ROOT, cfg["file"])) as f:
        return json.load(f)["net"]


def _run(ops, kernels, net, window=(1000, 2000)):
    """What the readers take of a ``servecell.Run``, on one chip."""
    plane = "/device:TPU:0"
    return types.SimpleNamespace(
        trace={"devices": {plane: ops}, "host": []}, trace_window=window,
        kernels={plane: kernels}, net=net, peaks=PEAKS)


def _least(net, a, b, batch=8):
    import opcount

    return opcount.least_time_s(opcount.span_flops(net, a, b, batch),
                                opcount.span_bytes(net, a, b, batch),
                                PEAKS)[0]


def test_top_span_is_found_by_its_name():
    """Of vgg19's first two spans the plan predicts (6,11) costliest (more
    operations than (0,6)); only its calls count, clipped to the window,
    though (0,6) takes more device time."""
    net = _net("vgg19")
    kernels = [(0, 6, 8, ()), (6, 11, 8, ())]
    assert _least(net, 6, 11) > _least(net, 0, 6)
    ops = [["_span_pallas_6_11.2", 900, 1300],     # 300 ns inside
           ["_span_pallas_0_6.1", 1300, 1800],
           ["copy", 1800, 1900],
           ["_span_pallas_6_11.2", 1900, 2100]]    # 100 ns inside
    got = TOP(_run(ops, kernels, net))
    assert got == pytest.approx(100.0 * 2 * _least(net, 6, 11) / 400e-9)


def test_top_span_stays_put_when_kernels_get_faster():
    """The span read is the plan's, not the trace's: making (0,6) four
    times faster leaves the reading alone, and making (6,11) twice as
    fast doubles it."""
    net = _net("vgg19")
    kernels = [(0, 6, 8, ()), (6, 11, 8, ())]

    def read(t06, t611):
        ops = [["_span_pallas_0_6.1", 1000, 1000 + t06],
               ["_span_pallas_6_11.2", 1000 + t06, 1000 + t06 + t611]]
        return TOP(_run(ops, kernels, net, window=(0, 10_000)))

    before = read(3500, 880)
    assert read(875, 880) == pytest.approx(before)
    assert read(3500, 440) == pytest.approx(2 * before)


def test_top_span_equals_the_kernel_share_with_one_span():
    net = _net("alexnet")
    ops = [["_span_pallas_0_8.1", 1000, 1400], ["pad.2", 1400, 1500],
           ["_span_pallas_0_8.1", 1500, 1950]]
    run = _run(ops, [(0, 8, 8, ())], net)
    assert TOP(run) == pytest.approx(ALL_SPANS(run))


@pytest.mark.parametrize("case", ["unnamed_kernels", "no_trace",
                                  "no_device", "no_peaks", "no_kernels"])
def test_top_span_reads_nothing_where_there_is_nothing(case):
    """A program whose kernels carry no span in their name (all of them
    ``_span_pallas.<n>``), a run with no trace, a trace with no device
    plane (the CPU), an unknown device and a plan with no span kernel
    give no value."""
    net = _net("alexnet")
    run = _run([["_span_pallas.1", 1000, 1900]], [(0, 8, 8, ())], net)
    if case == "no_trace":
        run.trace = None
    elif case == "no_device":
        run.trace["devices"] = {}
    elif case == "no_peaks":
        run.peaks = None
    elif case == "no_kernels":
        run.kernels = {plane: [] for plane in run.kernels}
        run.trace["devices"] = {"/device:TPU:0": [["_span_pallas_0_8.1",
                                                    1000, 1900]]}
    assert TOP(run) is None
    if case == "unnamed_kernels":
        assert ALL_SPANS(run) is not None


def test_program_spans_land_on_the_trace_clock(tmp_path):
    """Spans the program records, drained as ``time.time_ns`` nanoseconds
    and placed on a profile by its start time, hold an annotation the
    profiler recorded inside them."""
    from repro.occam.serve import trace as occam_trace

    rec = occam_trace.SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    rec.enable()
    t0 = occam_trace.now()
    with jax.profiler.TraceAnnotation("probe"):
        time.sleep(0.01)
    rec.record("occam.probe", t0, occam_trace.now(), 0)
    rec.disable()
    jax.profiler.stop_trace()
    path = tracereduce.find_xplane(str(tmp_path))
    t = tracereduce.load(path)
    tracereduce.add_host_spans(t, [(s.name, s.start_ns, s.end_ns)
                                   for s in rec.drain()])
    (lo, hi), = [(s, e) for n, s, e in t["host"] if n == "occam.probe"]
    probe = [e for p in jax.profiler.ProfileData.from_file(path).planes
             for line in p.lines for e in line.events if e.name == "probe"]
    assert len(probe) == 1
    assert lo <= probe[0].start_ns <= probe[0].end_ns <= hi


RECORDED = os.path.join(HERE, "testdata", "alexnet_sat_spans.json")


@pytest.fixture(scope="module")
def recorded():
    """30 ms from 5 s into a traced ``alexnet.sat`` window on one TPU v5
    lite, device operations only, with the engine's span recorder on
    around the window and its spans added to the harness's (cut as
    ``spanrun.cut`` cuts, before it kept the program's spans and ids)."""
    with open(RECORDED) as f:
        t = json.load(f)
    lo, hi = tracereduce.window(t)
    assert hi - lo == 30_000_000
    return t, lo, hi


def test_recorded_kernel_is_named_for_its_span(recorded):
    t, lo, hi = recorded
    top = tracereduce.top_ops(t, lo, hi)
    assert [name for name, _ in top[:3]] == ["_span_pallas_0_8.1", "pad.2",
                                             "copy"]
    (ops,) = t["devices"].values()
    run = _run(ops, [(0, 8, 8, ())], _net("alexnet"), window=(lo, hi))
    assert TOP(run) == pytest.approx(ALL_SPANS(run))
    # over whole calls only, as over a whole window: 1.46% on the chip
    calls = [(s, e) for name, s, e in ops
             if name.startswith("_span_pallas") and lo <= s and e <= hi]
    run.trace_window = (calls[0][0], calls[-1][1])
    assert 1.3 < TOP(run) < 1.6


def test_recorded_idle_gaps_carry_program_spans(recorded):
    """Every gap in a busy window falls while rounds are on the device:
    the engine's own spans name it."""
    t, lo, hi = recorded
    (ops,) = t["devices"].values()
    gaps = tracereduce.idle_gaps(t, lo, hi, n=len(ops))
    assert gaps and all("occam.device" in label for label, _ in gaps)


def test_recorded_rounds_are_stamped_at_device_completion(recorded):
    """Each round's completion stamp lands within 1 ms of the end of the
    last device operation finished by then, and the rounds' device
    times, ready(i) - max(dispatch_end(i), ready(i-1)), add up to the
    device's busy time between the first and the last stamp."""
    t, lo, hi = recorded
    (ops,) = t["devices"].values()
    device = sorted((s, e) for name, s, e in t["host"]
                    if name == "occam.device")
    device.sort(key=lambda se: se[1])
    stamps = [e for _s, e in device if lo <= e <= hi]
    assert len(stamps) >= 5
    for stamp in stamps:
        last = max(e for _n, _s, e in ops if e <= stamp)
        assert 0 <= stamp - last < 1_000_000
    rounds = [e1 - max(s1, e0) for (_s0, e0), (s1, e1)
              in zip(device, device[1:]) if lo <= e0 and e1 <= hi]
    busy = tracereduce.busy_ns(ops, stamps[0], stamps[-1])
    assert sum(rounds) == pytest.approx(busy, rel=0.05)
    assert all(4.0e6 < r < 5.5e6 for r in rounds)    # 4.70 ms a round


MS = 1_000_000


def _program_trace():
    """Four rounds of one request each, in ms on the trace's clock; the
    window is [5, 100] ms. Round r: (stage, dispatch, ready, deliver)."""
    rounds = [((0, 1), (1, 2), 10, (11, 12)),
              ((2, 3), (3, 4), 25, (26, 27)),
              ((4, 5), (5, 6), 45, (46, 48)),
              ((49, 50), (50, 52), 60, (61, 62))]
    prog = []
    for r, (st, dp, ready, dl) in enumerate(rounds):
        prog += [["occam.submit", st[0] - 0.5, st[0] - 0.25, r, r, 8, -1, []],
                 ["occam.queued", st[0] - 0.25, st[1], r, r, 8, -1, []],
                 ["occam.stage", *st, r, -1, 8, r, [r]],
                 ["occam.dispatch", *dp, r, r, 8, r, []],
                 ["occam.device", dp[1], ready, r, r, 8, r, []],
                 ["occam.deliver", *dl, r, r, 8, r, []]]
    prog = [[n, round(s * MS), round(e * MS), *rest]
            for n, s, e, *rest in prog]
    ops = [["_span_pallas_0_8.1", round(s * MS), round(e * MS)]
           for s, e in ((3, 9.5), (11, 24.8), (26, 44.9), (53, 59.5))]
    return {"devices": {"/device:TPU:0": ops},
            "host": [[tracereduce.WINDOW_SPAN, 5 * MS, 100 * MS]],
            "program": prog, "program_dropped": 0, "start_ns": 0}


def test_summarize_reads_rounds_from_the_program_spans():
    got = spanrun.summarize(_program_trace())
    assert got["spans"] == 24 and got["dropped"] == 0
    assert got["rounds_in_window"] == 4
    # ready(i) - max(dispatch_end(i), ready(i-1)) of rounds 1-3: 15, 20, 8;
    # round 0 has no recorded predecessor
    assert got["round_device_ms"] == pytest.approx(15.0)
    assert got["busy_ms_per_round"] == pytest.approx(
        (4.5 + 13.8 + 18.9 + 6.5) / 4)
    # stage + dispatch + deliver + the request's submit: 3.25, 3.25,
    # 4.25, 4.25 ms
    assert got["host_round_ms"] == pytest.approx(3.75)
    # only rounds 1-3's waits end inside the window: 1.25, 1.25, 1.25 ms
    assert got["admission_wait_ms"] == pytest.approx(1.25)
    assert got["stamp_lag_ms"]["median"] == pytest.approx(0.5)
    assert got["stamp_lag_ms"]["max"] == pytest.approx(0.5)


def test_cut_keeps_what_overlaps_its_window_and_whole_rounds():
    t = _program_trace()
    part = spanrun.cut(t, at_ns=20 * MS, length_ns=10 * MS)
    assert tracereduce.window(part) == (25 * MS, 35 * MS)
    assert [o[1:] for o in part["devices"]["/device:TPU:0"]] == [
        [26 * MS, round(44.9 * MS)]]
    kept = {(n, i) for n, _s, _e, i, *_ in part["program"]}
    # round 1 was ready at 25 ms, inside: it keeps every span, and so does
    # its request; of round 2 only the device span overlaps
    assert kept == {("occam.device", 2), ("occam.stage", 1),
                    ("occam.dispatch", 1), ("occam.device", 1),
                    ("occam.deliver", 1), ("occam.submit", 1),
                    ("occam.queued", 1)}


PROGRAM = os.path.join(HERE, "testdata", "alexnet_sat_program.json")


def test_recorded_program_spans_summarize_as_on_the_chip():
    """30 ms of another traced ``alexnet.sat`` window on one TPU v5 lite,
    cut by ``spanrun.py --slice`` with the program's spans and their ids:
    the rounds completed in the cut read one round's device time each,
    the host's work per round is below it, and requests hardly queue."""
    with open(PROGRAM) as f:
        t = json.load(f)
    got = spanrun.summarize(t)
    assert got["dropped"] == 0
    assert got["rounds_in_window"] == 7
    assert 4.0 < got["round_device_ms"] < 5.5        # 4.70 ms a round
    assert 0 < got["host_round_ms"] < got["round_device_ms"]
    assert 0 < got["admission_wait_ms"] < 1
    lo, hi = tracereduce.window(t)
    assert all(label.startswith("occam.") for label, _ in
               tracereduce.idle_gaps(t, lo, hi))
