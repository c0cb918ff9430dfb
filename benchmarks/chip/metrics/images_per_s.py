"""Images whose outputs reached the host inside the window, per second
of the window (host clock)."""


def read(run):
    return sum(r.n for r in run.completed_in_window()) / run.seconds
