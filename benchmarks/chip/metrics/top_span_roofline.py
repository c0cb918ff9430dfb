"""The roofline share of the span kernel the plan predicts costliest, in
percent (device trace).

The span is chosen from the plan, not from the trace: of the plan's
span kernels, the one whose call has the largest least time,
max(operations / peak, bytes / HBM bandwidth), counted from shapes by
``opcount``. So every run of a configuration reads the same kernel,
however fast each one runs. Each span kernel is named for its span
(``_span_pallas_<a>_<b>``, with XLA's ``.<n>`` suffix), so that span's
calls are found by name and attributed to no other span. Numerator: the
least time of each of its calls in the traced window; denominator: the
summed device time of those calls. A trace whose kernels carry no span
in their name gives nothing.
"""
import re

import opcount

NAME = re.compile(r"_span_pallas_(\d+)_(\d+)(?:\.\d+)?")


def top_span(run):
    """(a, b) and the least time of one call of the plan's costliest span
    kernel, or None when the plan has none."""
    least = {}
    for calls in run.kernels.values():
        for a, b, batch, spill in calls:
            least[(a, b)] = opcount.least_time_s(
                opcount.span_flops(run.net, a, b, batch),
                opcount.span_bytes(run.net, a, b, batch, spill),
                run.peaks)[0]
    if not least:
        return None
    span = max(least, key=least.get)
    return span, least[span]


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    top = top_span(run)
    if top is None:
        return None
    (a, b), per_call = top
    lo, hi = run.trace_window
    spent_ns, calls = 0, 0
    for ops in run.trace["devices"].values():
        for name, s, e in ops:
            m = NAME.fullmatch(name)
            d = min(e, hi) - max(s, lo)
            if m and (int(m[1]), int(m[2])) == (a, b) and d > 0:
                spent_ns += d
                calls += 1
    if not calls:
        return None
    return 100.0 * per_call * calls / (spent_ns / 1e9)
