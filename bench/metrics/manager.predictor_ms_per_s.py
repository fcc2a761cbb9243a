"""Serving-loop time spent in the arrival predictors, per second of the
window: the union of the loop thread's ``predictor.*`` spans (forward
passes with any compile they trigger, fit submissions) that start in the
window.  Read from the program's own spans (``srv.tracer``); a program
that records none gives nothing."""


def read(v):
    tracer = getattr(v.session.srv, "tracer", None)
    spans = tracer.spans() if tracer is not None else []
    loop = {s.thread for s in spans if s.name == "engine.advance"}
    if not loop:
        return None
    origin_ns = v.session.origin * 1e9
    ivals = sorted((s.t0_ns, s.t1_ns) for s in spans
                   if s.name.startswith("predictor.") and s.thread in loop
                   and v.in_window((s.t0_ns - origin_ns) / 1e6))
    total, end = 0, None
    for a, b in ivals:  # nested or overlapping spans count once
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total / 1e6 / v.window_s
