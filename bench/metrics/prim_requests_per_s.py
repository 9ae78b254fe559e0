"""Requests completed inside the window, over the window's seconds.  Host
clock, client side."""


def read(run):
    if not run.latencies_s or not run.window_s:
        return None
    return run.completed_in_window / run.window_s
