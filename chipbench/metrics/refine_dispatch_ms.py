"""Mean host time of one refine dispatch of the disk walk: the program's
``walk.dispatch`` span (the block's slices, its fetch, the enqueue of the
refine step) less the ``cache.wait`` nested in it."""
import program_spans


def read(run):
    return None if run.trace is None else program_spans.mean_ms(
        run.trace, "walk.dispatch")
