"""Share of the traced sub-window in which the device ran no operation
while the serving loop was inside one of its ``engine.advance`` spans:
idle time the program's own host work caused, as opposed to waiting for
traffic.  The program stamps its spans on ``perf_counter``; they are put
on the trace's timeline by the median offset between the trace's
``execute <app> #<i>`` annotations and the same batches' host stamps."""
import statistics

from harness import trace as T


def read(v):
    tracer = getattr(v.session.srv, "tracer", None)
    if tracer is None or v.trace is None or not v.trace["ops"]:
        return None
    held = v.traced_batches()
    if not held:
        return None
    # trace ns = harness ms * 1e6 + offset; harness ms is perf_counter
    # less the session's origin.
    offset = statistics.median(s - b.t0_ms * 1e6 for b, s, _ in held)
    base = offset - v.session.origin * 1e9
    advance = [s for s in tracer.spans() if s.name == "engine.advance"]
    if not advance:
        return None
    t0, t1 = v.trace_bounds()
    loop = T.union((max(s.t0_ns + base, t0), min(s.t1_ns + base, t1))
                   for s in advance
                   if s.t0_ns + base < t1 and s.t1_ns + base > t0)
    idle = T.idle_gaps(v.trace["ops"], t0, t1)
    both, i, j = 0.0, 0, 0
    while i < len(idle) and j < len(loop):
        lo = max(idle[i][0], loop[j][0])
        hi = min(idle[i][1], loop[j][1])
        both += max(0.0, hi - lo)
        if idle[i][1] < loop[j][1]:
            i += 1
        else:
            j += 1
    return 100.0 * both / (t1 - t0)
