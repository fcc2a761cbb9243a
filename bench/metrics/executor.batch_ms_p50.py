"""Median wall time of one executor call (prefill and every decode step
of a batch, tokens on the host)."""
import numpy as np


def read(v):
    ms = [b.t1_ms - b.t0_ms for b in v.batches]
    return float(np.median(ms)) if ms else None
