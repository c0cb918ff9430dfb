#!/usr/bin/env python3
"""A traced run of one cell with the program's span recorder on, and what
the program's spans say about it.

    python3 benchmarks/chip/spanrun.py --workload alexnet.sat --seed 7 \
        --seconds 30 --trace-dir <dir>
    python3 benchmarks/chip/spanrun.py --slice <dir>/reduced.json <out.json>

The first form is ``run.py --trace 1`` with ``AsyncEngine.spans``
enabled right after the profiler starts and disabled right before it
stops, so only the traced window records. The drained spans join the
harness's own in ``trace["host"]`` (so ``idle_gaps`` carry ``occam.*``
labels) and, whole, in ``trace["program"]`` of ``<dir>/reduced.json``.
After run.py's result line it prints one more line,
``{"program": summarize(trace)}``. The second form cuts 30 ms from 5 s
into such a trace's window, the size kept as test data.

Not part of a benchmark run: no cell runs it. run.py takes no hook for
the recorder, so this script sets three: the engine ``servecell.build``
makes, the profiler's start and stop, and ``tracereduce.add_host_spans``.
"""
import argparse
import bisect
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

SLICE_AT_NS = 5_000_000_000     # into the traced window
SLICE_NS = 30_000_000


def install() -> dict:
    """Turn the recorder on around the traced window; returns where the
    drained spans are kept."""
    import jax

    import servecell
    import tracereduce

    state = {}
    build, start, stop = (servecell.build, jax.profiler.start_trace,
                          jax.profiler.stop_trace)
    add = tracereduce.add_host_spans

    def build_(cell, seed):
        params, pool, engine = build(cell, seed)
        state["engine"] = engine
        return params, pool, engine

    def start_(*a, **kw):
        start(*a, **kw)
        state["engine"].spans.enable()

    def stop_():
        spans = state.pop("engine").spans
        spans.disable()
        state["dropped"] = spans.dropped
        state["spans"] = spans.drain()
        stop()

    def add_(trace, host):
        prog = state.get("spans", [])
        add(trace, list(host) + [(s.name, s.start_ns, s.end_ns)
                                 for s in prog])
        base = trace["start_ns"]
        trace["program"] = [[s.name, s.start_ns - base, s.end_ns - base,
                             s.id, s.parent, s.images, s.round,
                             list(s.requests)] for s in prog]
        trace["program_dropped"] = state.get("dropped", 0)

    servecell.build = build_
    jax.profiler.start_trace, jax.profiler.stop_trace = start_, stop_
    tracereduce.add_host_spans = add_
    return state


def _by_name(trace) -> dict:
    """{span name: {id: [start, end, images, requests]}} of the program's
    spans, on the trace's clock."""
    out: dict = {}
    for name, s, e, i, _parent, images, _round, reqs in trace["program"]:
        out.setdefault(name, {})[i] = [s, e, images, reqs]
    return out


def _pct(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else None


def _median(xs):
    return statistics.median(xs) if xs else None


def summarize(trace: dict) -> dict:
    """What the program's spans say about the traced window [lo, hi].

    Rounds are those whose outputs were ready inside the window
    (``occam.device`` ends there). Per round:

    * ``round_device_ms``: ready(i) - max(dispatch_end(i), ready(i-1)),
      the device time the session's timers hold (median; a round whose
      predecessor was not recorded is left out);
    * ``busy_ms_per_round``: the device trace's busy time in the window
      over the rounds, what ``round_device_ms`` should match;
    * ``host_round_ms``: ``occam.stage`` + ``occam.dispatch`` +
      ``occam.deliver`` of the round plus ``occam.submit`` of the
      requests it carries (median). These run one after another on the
      event loop, so their sum is their self time;
    * ``stamp_lag_ms``: the completion stamp less the end of the last
      device operation finished by then (median, p95, max).

    ``admission_wait_ms`` is the median ``occam.queued`` of the requests
    whose wait ended in the window."""
    import tracereduce

    lo, hi = tracereduce.window(trace)
    by = _by_name(trace)
    disp, dev = by.get("occam.dispatch", {}), by.get("occam.device", {})
    stage, deliver = by.get("occam.stage", {}), by.get("occam.deliver", {})
    submit = by.get("occam.submit", {})
    order = sorted(r for r in dev if r in disp)
    rounds = [r for r in order if lo <= dev[r][1] <= hi]
    device_ms = []
    for prev, r in zip(order, order[1:]):
        if lo <= dev[r][1] <= hi and prev == r - 1:
            device_ms.append(
                (dev[r][1] - max(disp[r][1], dev[prev][1])) / 1e6)
    host_ms = []
    for r in rounds:
        if r in stage and r in deliver:
            t = sum(e - s for s, e, *_ in (stage[r], disp[r], deliver[r]))
            t += sum(submit[u][1] - submit[u][0] for u in stage[r][3]
                     if u in submit)
            host_ms.append(t / 1e6)
    queued = [(e - s) / 1e6 for s, e, *_ in by.get("occam.queued",
                                                   {}).values()
              if lo <= e <= hi]
    busy = [tracereduce.busy_ns(ops, lo, hi)
            for ops in trace["devices"].values()]
    lag = []
    for ops in trace["devices"].values():
        ends = sorted(e for _n, _s, e in ops)
        for r in rounds:
            k = bisect.bisect_right(ends, dev[r][1])
            if k:
                lag.append((dev[r][1] - ends[k - 1]) / 1e6)
    return {
        "spans": len(trace["program"]),
        "dropped": trace.get("program_dropped"),
        "rounds_in_window": len(rounds),
        "round_device_ms": _median(device_ms),
        "busy_ms_per_round": (statistics.fmean(busy) / 1e6 / len(rounds)
                              if rounds and busy else None),
        "host_round_ms": _median(host_ms),
        "admission_wait_ms": _median(queued),
        "stamp_lag_ms": {"median": _pct(lag, 0.5), "p95": _pct(lag, 0.95),
                         "max": max(lag, default=None)},
    }


ROUND_SPANS = ("occam.stage", "occam.dispatch", "occam.device",
               "occam.deliver")


def cut(trace: dict, at_ns: int = SLICE_AT_NS,
        length_ns: int = SLICE_NS) -> dict:
    """``length_ns`` of a reduced trace from ``at_ns`` into its window:
    every device operation, harness span and program span that overlaps
    it, and the cut as the new window. A round whose outputs were ready
    inside the cut keeps all its program spans, and so do the requests
    it carries, so ``summarize`` reads the cut as it reads a window."""
    import tracereduce

    lo, _hi = tracereduce.window(trace)
    a = lo + at_ns
    b = a + length_ns

    def keep(s, e):
        return e > a and s < b

    rounds = {i for name, _s, e, i, *_ in trace["program"]
              if name == "occam.device" and a <= e <= b}
    requests = {u for name, _s, _e, i, *_rest, reqs in trace["program"]
                if name == "occam.stage" and i in rounds for u in reqs}

    def kept(p):
        name, s, e, i = p[:4]
        return keep(s, e) or (i in rounds if name in ROUND_SPANS
                              else i in requests)

    host = [h for h in trace["host"]
            if h[0] != tracereduce.WINDOW_SPAN and keep(h[1], h[2])]
    host.append([tracereduce.WINDOW_SPAN, a, b])
    return {"devices": {p: [o for o in ops if keep(o[1], o[2])]
                        for p, ops in trace["devices"].items()},
            "host": sorted(host, key=lambda h: h[1]),
            "program": [p for p in trace["program"] if kept(p)],
            "program_dropped": trace["program_dropped"],
            "start_ns": trace["start_ns"]}


def main(argv=None) -> int:
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "--slice":
        ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
        ap.add_argument("--slice", nargs=2, metavar=("REDUCED", "OUT"),
                        required=True)
        src, out = ap.parse_args(argv).slice
        with open(src) as f:
            part = cut(json.load(f))
        with open(out, "w") as f:
            json.dump(part, f, separators=(",", ":"))
        return 0
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace-dir", required=True)
    known, _rest = ap.parse_known_args(argv)
    import run      # first: its set-up clock starts at its import

    install()
    rc = run.main(argv + ["--trace", "1"])
    if rc:
        return rc
    with open(os.path.join(known.trace_dir, "reduced.json")) as f:
        trace = json.load(f)
    print(json.dumps({"program": summarize(trace)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
