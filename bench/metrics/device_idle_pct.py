"""device_idle_pct: share of the traced window in which no operation ran
on the chip (union of the trace's XLA Ops intervals)."""


def read(ctx):
    tr = ctx["trace"]
    busy = tr.busy_s() if tr is not None else None
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / tr.window_s)
