"""Useful operations of the images completed in the measured window (two
per conv multiply-accumulate of the whole net) over the window times
chips times the chip's peak, in percent (host clock; taken in the
untraced window of a --trace 1 run)."""
import netref


def read(run):
    if run.peaks is None:
        return None
    images = sum(r.n for r in run.completed_in_window())
    flops = 2.0 * netref.conv_macs(run.net) * images
    return 100.0 * flops / (run.seconds * run.chips
                            * run.peaks["flops_per_s"])
