"""gets_per_query: object-store GETs in the window (ObjectStore.n_gets
delta) over the queries of the window."""


def read(ctx):
    win = ctx["window"]
    return win.gets / len(win.q_idx)
