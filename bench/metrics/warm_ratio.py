"""Share of the requests due in the window whose batch the manager
admitted warm (the engine's ``RequestResult.warm``); a request that never
ran is not warm."""


def read(v):
    reqs = v.requests
    if not reqs:
        return None
    return sum(bool(v.warm_by_rid.get(r.rid)) for r in reqs) / len(reqs)
