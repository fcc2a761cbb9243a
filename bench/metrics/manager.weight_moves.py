"""Weight moves in the window: every change of a tenant's resident
variant (load, upgrade, downgrade, eviction) as the loader enacted it."""


def read(v):
    return float(len(v.moves))
