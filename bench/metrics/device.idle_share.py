"""Share of the traced sub-window in which no operation ran on the
device."""
from harness import trace as T


def read(v):
    if v.trace is None or not v.trace["ops"]:
        return None
    t0, t1 = v.trace_bounds()
    return 100.0 * (1.0 - T.busy_ns(v.trace["ops"], t0, t1) / (t1 - t0))
