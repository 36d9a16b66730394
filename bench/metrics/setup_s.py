"""setup_s: seconds from the process's start to the first query of the
window (data, index build, partition write, warm-up; host clock)."""


def read(ctx):
    return ctx["setup_s"]
