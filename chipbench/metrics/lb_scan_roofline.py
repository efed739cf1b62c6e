"""Share of its roofline that ``kernels/lb_scan.py`` reaches in the
traced window (``kernel_cost.lb_scan``; the query rows are the cell's
batch, the series those of the call's (w, M) bounds)."""
import kernel_cost
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    batch, w = run.traffic["batch"], run.cfg["w"]

    def cost(ev):
        shapes = [s for s in trace_reduce.operand_shapes(ev) if len(s) == 2]
        if len(shapes) < 2 or shapes[1][0] != w:
            return None
        return kernel_cost.lb_scan(min(shapes[0][0], batch), shapes[1][1], w)

    return kernel_cost.roofline_share(run.trace.kernel_events("lb_scan"),
                                      cost, run.peaks)
