"""The span kernels' share of their roofline, in percent (device trace).

Numerator: the least time of every kernel call in the traced window,
max(operations / peak, bytes / HBM bandwidth) per call, with operations
and bytes counted from shapes by ``opcount``. Denominator: the summed
device time of the kernel's events. Every span kernel carries the same
name, so a chip's calls are attributed to the spans it runs in plan
order, each span once per round.
"""
import opcount
import tracereduce

# the HLO name of every span kernel's custom call (the jitted
# ``_span_pallas`` of kernels/fused_span/kernel.py), numbered per call site
KERNEL = "_span_pallas"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    lo, hi = run.trace_window
    least = spent = 0.0
    for plane, calls in run.kernels.items():
        if not calls:
            continue
        ns, count = tracereduce.matching_ns(
            run.trace["devices"].get(plane, []), KERNEL, lo, hi)
        if not count:
            continue
        per_round = sum(opcount.least_time_s(
            opcount.span_flops(run.net, a, b, batch),
            opcount.span_bytes(run.net, a, b, batch, spill),
            run.peaks)[0] for a, b, batch, spill in calls)
        least += per_round * count / len(calls)
        spent += ns / 1e9
    return 100.0 * least / spent if spent else None
