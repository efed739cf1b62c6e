"""Blocks read from disk per query (the program's
``IOStats.blocks_fetched``, summed over the window's answers)."""


def read(run):
    recs = run.loop.recs
    if not recs or "disk_blocks_read" not in recs[0].counters:
        return None
    return sum(r.counters["disk_blocks_read"] for r in recs) / run.loop.queries
