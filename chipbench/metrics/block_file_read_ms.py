"""Mean time of one block's read out of the series file into host memory
(the program's ``cache.read_file`` span, on a reader thread)."""
import program_spans


def read(run):
    return None if run.trace is None else program_spans.mean_ms(
        run.trace, "cache.read_file")
