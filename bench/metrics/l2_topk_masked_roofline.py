"""l2_topk_masked_roofline: least time of every l2_topk_masked launch of
the window (costs.py at its launch shape, peaks.json) over the device
time of its compiled program (``jit_l2_topk_masked``)."""
import costs


def read(ctx):
    tr, peak = ctx["trace"], ctx["peaks"]
    launches = ctx["launches"]["l2_topk_masked"]
    if tr is None or peak is None or not launches:
        return None
    t_dev = tr.module_s("jit_l2_topk_masked")
    if t_dev <= 0:
        return None
    least = sum(costs.least_time(*costs.l2_topk_masked(*shape), peak)[0]
                for shape in launches)
    return 100.0 * least / t_dev
