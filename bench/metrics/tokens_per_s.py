"""Output tokens of the requests whose tokens reached the host inside the
window, over the window's seconds."""


def read(v):
    toks = sum(r.max_new for r in v.session.requests
               if not r.failed and v.in_window(r.done_ms))
    return toks / v.window_s
