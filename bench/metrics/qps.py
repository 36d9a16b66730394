"""qps: queries answered in the window over the window's wall time
(host clock; the window ends when its last batch's answers are on the
host)."""


def read(ctx):
    win = ctx["window"]
    return len(win.q_idx) / win.wall_s
