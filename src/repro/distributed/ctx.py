"""Activation-sharding context: lets pure layer code emit
``with_sharding_constraint`` hints without threading mesh objects through
every call.  The launcher (steps.build_cell) installs the context; on a
bare CPU (tests, smoke) it stays disabled and hints are no-ops.

Why this exists: XLA's sharding propagation picks the wrong dim after
head-split reshapes — e.g. (B,S,KV·hd)→(B,S,KV,hd) can land the model
axis on ``hd``, making every attention einsum a partial-sum all-reduce of
score-sized tensors.  A handful of explicit hints on q/k/v, FFN hidden,
and SSM internals pins the intended TP layout (measured effect recorded
in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


@dataclass
class ShardCtx:
    dp_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    model_size: int = 1
    dp_size: int = 1
    enabled: bool = False

    @property
    def dp_spec(self):
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]


_CTX = ShardCtx()


def set_ctx(ctx: Optional[ShardCtx]) -> None:
    global _CTX
    _CTX = ctx if ctx is not None else ShardCtx()


def get_ctx() -> ShardCtx:
    return _CTX


def hint(x, *dims: Optional[str]):
    """Constrain ``x``: each entry is 'dp', 'model', or None per dim.

    'dp' requires exact divisibility (batch semantics).  'model' also
    accepts *uneven* sharding (XLA GSPMD pads the last shards) whenever
    the dim is at least model_size/4 — e.g. llama4's 40 heads or hymba's
    25 heads shard 16-way with ≤2× padding waste, versus 16× redundant
    compute+memory if left replicated (measured: a 36 GB/device score
    arena on llama4 train_4k)."""
    ctx = _CTX
    if not ctx.enabled:
        return x
    spec = []
    for d, want in zip(x.shape, dims):
        if want == "model" and ctx.model_size > 1 and (
                d % ctx.model_size == 0 or d * 4 >= ctx.model_size):
            spec.append(ctx.model_axis)
        elif want == "dp" and ctx.dp_size > 1 and d % ctx.dp_size == 0:
            spec.append(ctx.dp_spec)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
