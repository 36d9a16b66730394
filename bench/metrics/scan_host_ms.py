"""scan_host_ms: host time per batch inside ``ScanStage.topk`` and
``ScanStage.adc_select`` (pool padding, LUTs, cover selection, copies),
less the device time of the scan kernels' programs inside them."""

KERNEL_IN = (("jit_l2_topk_masked", "ScanStage.topk"),
             ("jit_pq_adc_masked", "ScanStage.adc_select"))


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.n_batches:
        return None
    host = sum(tr.span_s(span) for _, span in KERNEL_IN)
    if host <= 0:
        return None
    dev = sum(tr.module_s_within(mod, span) for mod, span in KERNEL_IN)
    return (host - dev) * 1e3 / tr.n_batches
