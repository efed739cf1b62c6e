"""Share of its roofline that ``kernels/fused_refine.py`` reaches in the
traced window (``kernel_cost.fused_panel_topk``; the query rows are the
cell's batch, not the kernel's 8-row padded tile)."""
import kernel_cost
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    cfg, batch, k = run.cfg, run.traffic["batch"], run.traffic["k"]

    def cost(ev):
        shapes = [s for s in trace_reduce.operand_shapes(ev) if len(s) == 2]
        if len(shapes) < 4:
            return None
        c = shapes[3][0]                       # the (C, n) block operand
        return kernel_cost.fused_panel_topk(batch, c, cfg["length"],
                                            cfg["w"], k)

    return kernel_cost.roofline_share(
        run.trace.kernel_events("fused_panel_topk"), cost, run.peaks)
