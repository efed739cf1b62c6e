"""Mean host time of one in-memory search call's enqueue (the program's
``engine.dispatch`` span around ``engine.run`` in ``core.search``)."""
import program_spans


def read(run):
    return None if run.trace is None else program_spans.mean_ms(
        run.trace, "engine.dispatch")
