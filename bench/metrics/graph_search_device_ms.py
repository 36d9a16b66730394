"""graph_search_device_ms: device time of the graph phase's compiled
program (``jit_greedy_search`` in the trace's XLA Modules) per batch."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.n_batches:
        return None
    t = tr.module_s("jit_greedy_search")
    return t * 1e3 / tr.n_batches if t > 0 else None
