"""Model-zoo builders: produce int8/int4 (and bf16) variants of real
parameter trees — the paper's per-application "precision levels" realized
on actual LM weights.

Representation: a quantized weight is ``{"q": int8 (..., K, N),
"s": f32 (..., K//group, N)}``; dense layers route through the fused
dequant Pallas matmul (``ops.quant_matmul``) at serve time, so the smaller
variant also means proportionally less HBM traffic (the TPU analogue of
the paper's Table I load/inference asymmetry).

1-D parameters (norms, biases, A_log, …) and embedding tables stay in the
base dtype: they are a negligible fraction of bytes and quantizing them
hurts fidelity disproportionately.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.kernels import ops

PyTree = Any

# Tree paths containing these substrings are never quantized.  Depthwise
# conv taps are W×C (a few KB) — not worth the fidelity cost.
_EXCLUDE = ("embed", "meta", "final_norm", "conv")


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _quantize_leaf(w: jnp.ndarray, bits: int, group: int):
    """Quantize trailing-2D slices of an >=2-D weight."""
    *lead, K, N = w.shape
    w2 = w.reshape(-1, K, N)
    qs, ss = [], []
    for i in range(w2.shape[0]):
        q, s = ops.quantize_weights(w2[i], bits=bits, group=group)
        qs.append(q)
        ss.append(s)
    q = jnp.stack(qs).reshape(*lead, K, N)
    s = jnp.stack(ss).reshape(*lead, ss[0].shape[0], N)
    return {"q": q, "s": s}


def dequantize_leaf(leaf) -> jnp.ndarray:
    if not is_quantized(leaf):
        return leaf
    q, s = leaf["q"], leaf["s"]
    *lead, K, N = q.shape
    G = s.shape[-2]
    group = K // G
    w = q.astype(jnp.float32).reshape(*lead, G, group, N) * s[..., None, :]
    return w.reshape(*lead, K, N)


def is_quantized(leaf) -> bool:
    return isinstance(leaf, dict) and set(leaf) == {"q", "s"}


def quantize_params(params: PyTree, *, bits: int = 8,
                    group: int = 128) -> PyTree:
    """Return the ``bits``-precision zoo variant of a parameter tree."""
    if bits >= 16:
        dtype = jnp.bfloat16 if bits == 16 else jnp.float32
        return jax.tree.map(
            lambda w: w.astype(dtype) if w.ndim >= 2 else w, params)

    def visit(path, w):
        ps = _path_str(path)
        if any(e in ps for e in _EXCLUDE):
            return w
        # Leaves under layers/ carry a stacked leading L dim: true weight
        # matrices there are ndim>=3; elsewhere (head) ndim>=2.
        min_ndim = 3 if ps.startswith("layers") else 2
        if w.ndim < min_ndim:
            return w
        K = w.shape[-2]
        g = group if K % group == 0 else K
        return _quantize_leaf(w, bits, g)

    return jax.tree_util.tree_map_with_path(visit, params)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class PlacedQuant:
    """A quantized weight placed on a device mesh: the ``q``/``s`` pair
    plus the mesh and the ``PartitionSpec`` of its two matrix dims
    (static pytree data, so it survives the layer scan's slicing).  The
    Pallas matmul is a Mosaic call that the SPMD partitioner cannot
    split, so ``ops.quant_matmul`` runs it per shard from this spec."""
    q: Any
    s: Any
    mesh: Any
    spec: PartitionSpec

    def tree_flatten(self):
        return (self.q, self.s), (self.mesh, self.spec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def place_params(params: PyTree, specs: PyTree, mesh) -> PyTree:
    """Put ``params`` on ``mesh`` with the ``PartitionSpec`` tree
    ``specs``.  Each quantized leaf comes back as a :class:`PlacedQuant`
    carrying the spec of its two matrix dims, so every path that runs the
    weights (fused or eager) runs the Pallas matmul per shard.  Leaves
    already placed are re-placed, as after a mesh change."""

    def put(leaf, spec):
        if isinstance(leaf, PlacedQuant):
            leaf = {"q": leaf.q, "s": leaf.s}
        if not is_quantized(leaf):
            return jax.device_put(leaf, NamedSharding(mesh, spec))
        nd = leaf["q"].ndim
        ks = (tuple(spec["q"]) + (None,) * nd)[:nd]
        return PlacedQuant(
            jax.device_put(leaf["q"], NamedSharding(mesh, spec["q"])),
            jax.device_put(leaf["s"], NamedSharding(mesh, spec["s"])),
            mesh, PartitionSpec(*ks[-2:]))

    return jax.tree.map(
        put, params, specs,
        is_leaf=lambda l: isinstance(l, PlacedQuant) or is_quantized(l))


def dequantize_params(qparams: PyTree) -> PyTree:
    return jax.tree.map(dequantize_leaf, qparams, is_leaf=is_quantized)


def params_nbytes(params: PyTree) -> int:
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total


# ---------------------------------------------------------------------------
# Fidelity: the accuracy proxy for LM-arch zoos (DESIGN.md §2).
# ---------------------------------------------------------------------------
def fidelity(cfg, params_ref: PyTree, qparams: PyTree, batch: dict,
             forward_fn) -> Dict[str, float]:
    """Top-1 agreement and logit MSE of quantized vs reference forward."""
    ref_logits = forward_fn(cfg, params_ref, batch)
    deq = dequantize_params(qparams)
    q_logits = forward_fn(cfg, deq, batch)
    ref_ids = jnp.argmax(ref_logits, -1)
    q_ids = jnp.argmax(q_logits, -1)
    agree = float(jnp.mean((ref_ids == q_ids).astype(jnp.float32)))
    mse = float(jnp.mean((ref_logits - q_logits) ** 2))
    return {"top1_agreement": agree * 100.0, "logit_mse": mse}
