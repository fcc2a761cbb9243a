"""Process start to window start: build (weights and zoo), the warm-up
compiles, and the replay that gives the predictors their history."""


def read(v):
    s = v.session
    return s.t_window0 - s.t_process0
