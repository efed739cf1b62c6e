"""Device busy time of the traced window per query answered in it."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or run.loop.queries == 0:
        return None
    return 1e3 * t.busy_s / run.loop.queries
