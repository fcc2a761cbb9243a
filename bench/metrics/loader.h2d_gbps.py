"""Host-to-device weight copy rate over the window's moves: the bytes of
every ``loader.h2d`` span that starts in the window over their summed
time, from ``set_variant``'s dispatch until the new buffers are ready
(program spans, ``srv.tracer``).  Nothing to read in a run whose window
holds no move."""


def read(v):
    tracer = getattr(v.session.srv, "tracer", None)
    if tracer is None:
        return None
    origin_ns = v.session.origin * 1e9
    moves = [s for s in tracer.spans() if s.name == "loader.h2d"
             and v.in_window((s.t0_ns - origin_ns) / 1e6)]
    secs = sum(s.t1_ns - s.t0_ns for s in moves) / 1e9
    if secs <= 0:
        return None
    return sum(s.attrs["bytes"] for s in moves) / secs / 1e9
