"""Share of the device's idle time in the traced window that no program
span names: the idle gaps whose middle falls in a bare ``request`` or
outside every host span (``program_spans.gap_names``)."""
import program_spans


def read(run):
    t = run.trace
    if t is None or not program_spans.has_client_spans(t):
        return None
    gaps = program_spans.gap_names(t)
    idle = sum(s for _, s in gaps)
    if idle <= 0:
        return None
    bare = sum(s for name, s in gaps
               if name in ("request", program_spans.OUTSIDE))
    return 100.0 * bare / idle
