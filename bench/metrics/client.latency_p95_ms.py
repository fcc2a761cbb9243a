"""95th percentile of due-to-tokens time over every request due in the
window (all of them, not a median of chunks)."""
import numpy as np


def read(v):
    lat = v.latencies_ms()
    return float(np.percentile(lat, 95)) if len(lat) else None
