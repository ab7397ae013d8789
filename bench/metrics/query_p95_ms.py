"""95th percentile (linear between order statistics) of the client-side
latencies of every query completed in the window, in ms."""

import numpy as np


def read(run):
    lat = [r.latency_s for r in run.records]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
