"""Backend compiles inside the window anywhere but the arrival
predictors: the serving program, the loader, the engine, and any other
call on the serving path (the warm-up should have left none)."""


def read(v):
    return float(sum(1 for c in v.compiles if c[3] != "predictor"))
