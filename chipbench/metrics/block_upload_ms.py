"""Mean time of one block's ``jax.device_put`` on a reader thread (the
program's ``cache.upload`` span; where the put returns before the
transfer lands, the landing shows in the client's wait or sync)."""
import program_spans


def read(run):
    return None if run.trace is None else program_spans.mean_ms(
        run.trace, "cache.upload")
