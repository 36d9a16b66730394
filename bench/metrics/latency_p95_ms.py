"""latency_p95_ms: 95th percentile over every query of the window of
the wall time from its submit to its answer on the host."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx["window"].latency_s, 95)) * 1e3
