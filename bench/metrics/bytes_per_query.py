"""bytes_per_query: bytes the object store served in the window
(ObjectStore.bytes_fetched delta) over the queries of the window."""


def read(ctx):
    win = ctx["window"]
    return win.bytes / len(win.q_idx)
