"""Model operations of the batches in the traced sub-window over the
device time of their serving programs times the chip's bf16 peak."""
from harness import counts
from harness import trace as T

PROGRAM = "_generate_tokens"


def read(v):
    flops = secs = 0.0
    for b, s, e in v.traced_batches():
        runs = T.within(v.trace["modules"], s, e, PROGRAM,
                        v.starts("modules"))
        if not runs:
            continue
        flops += counts.model_flops(v.models[b.app], *b.prompts.shape,
                                    b.max_new)
        secs += sum(d for _, _, d in runs) / 1e9
    if secs <= 0:
        return None
    return 100.0 * flops / (secs * v.peaks["bf16_flops_per_s"])
