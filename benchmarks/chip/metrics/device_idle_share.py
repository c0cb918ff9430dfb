"""1 - (union of device operation intervals / traced window), averaged
over the cell's chips, in percent (device trace)."""
import tracereduce


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    lo, hi = run.trace_window
    shares = [1.0 - tracereduce.busy_ns(ops, lo, hi) / (hi - lo)
              for ops in run.trace["devices"].values()]
    return 100.0 * sum(shares) / len(shares)
