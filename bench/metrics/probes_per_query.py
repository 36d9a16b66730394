"""probes_per_query: partitions probed per query in the window
(SearchStats.n_probes of every batch, via AnnsFrontend.last_stats)."""


def read(ctx):
    win = ctx["window"]
    return win.probes / len(win.q_idx)
