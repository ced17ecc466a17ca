"""Median ticket latency, submit to the return of its drain, over every
ticket of the window."""

import numpy as np


def read(run):
    lat = [t.latency_s for t in run.tickets]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
