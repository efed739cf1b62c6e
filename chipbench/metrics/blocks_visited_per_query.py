"""Blocks the walk refined per query (the program's
``SearchStats.blocks_visited``, summed over the window's answers)."""


def read(run):
    recs = run.loop.recs
    if not recs or "blocks_visited" not in recs[0].counters:
        return None
    return sum(r.counters["blocks_visited"] for r in recs) / run.loop.queries
