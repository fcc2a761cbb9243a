"""Median time from when a request was due until its tokens were on the
host, over every request due in the window (a failed one never meets a
limit)."""
import numpy as np


def read(v):
    lat = v.latencies_ms()
    return float(np.percentile(lat, 50)) if len(lat) else None
