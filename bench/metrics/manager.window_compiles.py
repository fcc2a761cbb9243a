"""Backend compiles inside the window by the arrival predictors: those
on their fit worker and those inside ``predict_and_preload`` (eager
forward passes).  Booked by where they ran, not by function name."""


def read(v):
    return float(sum(1 for c in v.compiles if c[3] == "predictor"))
