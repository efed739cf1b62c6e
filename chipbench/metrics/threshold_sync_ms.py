"""Mean time of one threshold pull of the disk walk (the program's
``walk.sync`` span: the bound's op, the wait for the device, the copy)."""
import program_spans


def read(run):
    return None if run.trace is None else program_spans.mean_ms(
        run.trace, "walk.sync")
