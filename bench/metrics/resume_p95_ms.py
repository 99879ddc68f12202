"""95th percentile of every page-resume request of the window, in ms."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_s, 95) * 1e3) if ctx.requests else None
