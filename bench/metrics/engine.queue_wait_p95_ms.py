"""95th percentile of due -> executor start over the window's requests
that reached the executor (harness stamps)."""
import numpy as np


def read(v):
    w = [r.start_ms - r.due_ms for r in v.requests
         if not np.isnan(r.start_ms)]
    return float(np.percentile(w, 95)) if w else None
