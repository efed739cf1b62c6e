"""Share of its roofline that ``kernels/block_topk.py`` reaches in the
traced window (``kernel_cost.block_topk``; the query rows are the
cell's batch, the lanes those of the call's distance panel)."""
import kernel_cost
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    batch, k = run.traffic["batch"], run.traffic["k"]

    def cost(ev):
        shapes = trace_reduce.operand_shapes(ev)
        if not shapes or len(shapes[0]) != 2:
            return None
        q, c = shapes[0]
        return kernel_cost.block_topk(min(q, batch), c, k)

    return kernel_cost.roofline_share(run.trace.kernel_events("block_topk"),
                                      cost, run.peaks)
