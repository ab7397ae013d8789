"""Median client-side latency, submit to the answer as host values, over
every query completed in the window, in ms."""

import numpy as np


def read(run):
    lat = [r.latency_s for r in run.records]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
