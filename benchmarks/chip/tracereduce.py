"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` a traced run writes, with nothing but
JAX's ``ProfileData``, into plain tuples: the device operations of each
chip. The trace records device operations only: host tracing logs the
client's host-to-device transposes by the million and slows the host
threefold. What the harness was doing comes from its own record of its
spans on the host clock (``host``), moved onto the trace's clock by the
profile's start time. Everything after that works on those tuples, so
it is checked on a small recorded trace with no chip.

Times are nanoseconds after the profile's start, on which host and
device events are aligned. The window is the harness's own
``bench.window`` span.
"""
from __future__ import annotations

import glob
import json
import os
import re

WINDOW_SPAN = "bench.window"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> dict:
    """``{"devices": {plane: [[name, start, end], ...]}, "host": [],
    "start_ns": <profile start, ns since the epoch>}``. Device operations
    come from each TPU plane's ``XLA Ops`` line."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, start_ns = {}, None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[op_name(e.name), int(e.start_ns),
                             int(e.end_ns)] for e in line.events]
            devices[plane.name] = sorted(ops, key=lambda e: e[1])
        for key, value in plane.stats:
            if key == "profile_start_time":
                start_ns = int(value)
    if start_ns is None:
        raise ValueError(f"{path} records no profile_start_time")
    return {"devices": devices, "host": [], "start_ns": start_ns}


def add_host_spans(trace: dict, spans) -> None:
    """The harness's spans, ``(name, start, end)`` in ``time.time_ns``
    nanoseconds, onto the trace's clock."""
    base = trace["start_ns"]
    trace["host"] = sorted(([name, s - base, e - base]
                            for name, s, e in spans), key=lambda e: e[1])


def op_name(event_name: str) -> str:
    """The HLO instruction's name, from an ``XLA Ops`` event that carries
    the whole instruction ("%_span_pallas.1 = f32[...] custom-call(...)")."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def save(trace: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)


def window(trace: dict) -> tuple[int, int]:
    spans = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    return spans[0][1], spans[0][2]


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the (disjoint, sorted) intervals ``a`` covered by none of
    the (disjoint, sorted) intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(ops, lo: int, hi: int) -> int:
    """Time in [lo, hi) in which some operation ran on the device."""
    return total(union(_clip([(s, e) for _n, s, e in ops], lo, hi)))


def matching_ns(ops, pattern: str, lo: int, hi: int) -> tuple[int, int]:
    """(summed duration, count) of the operations whose name contains
    ``pattern``, clipped to the window."""
    hit = [(s, e) for n, s, e in ops if pattern in n]
    return total(_clip(hit, lo, hi)), sum(
        1 for s, e in hit if min(e, hi) > max(s, lo))


def top_ops(trace: dict, lo: int, hi: int, n: int = 10) -> list:
    """The device operations that took most time, summed over chips and
    averaged per chip, as ``[name, seconds]``."""
    sums: dict[str, int] = {}
    for ops in trace["devices"].values():
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                sums[name] = sums.get(name, 0) + d
    chips = max(len(trace["devices"]), 1)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / chips / 1e9] for name, ns in ranked]


def idle_gaps(trace: dict, lo: int, hi: int, n: int = 10) -> list:
    """The longest gaps with no operation on a chip, each labelled with
    the harness spans open on the host at its midpoint."""
    gaps = []
    for ops in trace["devices"].values():
        busy = union(_clip([(s, e) for _n, s, e in ops], lo, hi))
        gaps += subtract([(lo, hi)], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) // 2
        open_spans = sorted({name for name, hs, he in trace["host"]
                             if hs <= mid < he and name != WINDOW_SPAN})
        label = "+".join(open_spans) if open_spans else "no harness span"
        out.append([label, (e - s) / 1e9])
    return out


def describe(path: str, top: int = 25) -> str:
    """A trace's planes and lines, with each line's most frequent event
    names: what to look at before writing a reduction against it."""
    import collections

    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            names = collections.Counter(e.name for e in events)
            dur = collections.Counter()
            for e in events:
                dur[e.name] += e.duration_ns
            for name, n in names.most_common(top):
                out.append(f"    {n:7d} x {dur[name] / 1e6:10.3f} ms  {name}")
            shown = [e for e in events if re.search(
                "custom|pallas|span|kernel|permute|reduce", e.name)][:3]
            for e in (shown or events)[:3]:
                stats = {k: str(v)[:100] for k, v in e.stats}
                out.append(f"    {e.name}: {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(find_xplane(sys.argv[1])))
