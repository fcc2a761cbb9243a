"""Mean requests per batch the engine executed in the window."""


def read(v):
    bs = v.batches
    return sum(len(b.rids) for b in bs) / len(bs) if bs else None
