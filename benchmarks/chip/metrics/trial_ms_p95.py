"""95th percentile, over every trial of the window, of the time from a
trial's dispatch to its records on the host (closed loop only)."""

import numpy as np


def read(ctx):
    window = ctx["window"]
    if window["loop"] != "closed" or not window["latencies_s"]:
        return None
    return float(np.percentile(np.asarray(window["latencies_s"]) * 1e3, 95))
