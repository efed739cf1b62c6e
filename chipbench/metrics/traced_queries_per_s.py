"""Queries answered per second over the traced window: the served rate
of a cell whose seed-to-seed spread is too wide for an end-to-end bound,
read under the profiler for the mix's ``trace_seconds``."""


def read(run):
    loop = run.loop
    if not loop.recs or loop.window_s <= 0:
        return None
    return loop.queries / loop.window_s
