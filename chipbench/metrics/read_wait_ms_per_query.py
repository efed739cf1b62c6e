"""Time the host walk waited for block reads per query answered in the
traced window: the program's ``cache.wait`` spans (a read in flight or a
demand miss; a cache hit records none), summed."""
import program_spans


def read(run):
    t = run.trace
    if (t is None or run.loop.queries == 0
            or not program_spans.has_client_spans(t)):
        return None
    waits = program_spans.of(t, "cache.wait")
    return 1e-6 * sum(s for _, s in waits) / run.loop.queries
