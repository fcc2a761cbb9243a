"""Dispatching wrappers around the perf-critical kernels.

``impl`` resolution:
  * "pallas"    — the Pallas TPU kernels (compiled on TPU; ``interpret=True``
                  execution on CPU for validation).
  * "reference" — the pure-jnp oracles in :mod:`repro.kernels.ref`.
  * "auto"      — pallas on TPU backends, reference elsewhere.  The dry-run /
                  roofline path always lowers the reference graph (Pallas TPU
                  kernels cannot lower on the CPU backend), which is
                  mathematically identical.

``set_impl`` forces one globally for tests; nothing outside the process
(no environment variable) can send a TPU run to the reference.

Models call these entry points only; nothing below this layer leaks upward.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import ref

_FORCED: Optional[str] = None


def set_impl(impl: Optional[str]) -> None:
    """Force "pallas" / "reference" globally (None restores auto)."""
    global _FORCED
    _FORCED = impl


def resolve_impl(impl: str = "auto") -> str:
    if _FORCED is not None:
        return _FORCED
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0, scale=0.0,
                    q_offset=0, prefix=0, impl="auto"):
    if resolve_impl(impl) == "pallas":
        from . import flash_attention as fa

        return fa.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, q_offset=q_offset, prefix=prefix,
            interpret=_interpret())
    return ref.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        q_offset=q_offset, prefix=prefix)


def decode_attention(q, k_cache, v_cache, lengths, *, window=0, softcap=0.0,
                     scale=0.0, prefix=0, impl="auto"):
    if resolve_impl(impl) == "pallas":
        from . import decode_attention as da

        return da.decode_attention(
            q, k_cache, v_cache, lengths, window=window, softcap=softcap,
            scale=scale, prefix=prefix, interpret=_interpret())
    return ref.decode_attention(
        q, k_cache, v_cache, lengths, window=window, softcap=softcap,
        scale=scale, prefix=prefix)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           window=0, softcap=0.0, scale=0.0, prefix=0,
                           impl="auto"):
    if resolve_impl(impl) == "pallas":
        from . import decode_attention as da

        return da.paged_decode_attention(
            q, k_pages, v_pages, page_table, lengths, window=window,
            softcap=softcap, scale=scale, prefix=prefix,
            interpret=_interpret())
    return ref.paged_decode_attention(
        q, k_pages, v_pages, page_table, lengths, window=window,
        softcap=softcap, scale=scale, prefix=prefix)


def quant_matmul(x, w_q, scales, *, out_dtype=None, impl="auto",
                 mesh=None, spec=None):
    """``x @ dequant(w_q, scales)``.  ``mesh``/``spec`` give the weight's
    placement (``spec`` over its (K, N) dims): the Pallas kernel then runs
    per shard under ``shard_map``, since the partitioner cannot split a
    Mosaic call — a K-sharded weight sums the partial products across
    its axis.  The reference path is plain jnp and needs neither."""
    if resolve_impl(impl) != "pallas":
        return ref.quant_matmul(x, w_q, scales, out_dtype=out_dtype)
    from . import quant_matmul as qm

    out_dtype = out_dtype or x.dtype
    if mesh is None:
        return qm.quant_matmul(
            x, w_q, scales, out_dtype=out_dtype, interpret=_interpret())
    k_ax, n_ax = spec
    if k_ax is not None and scales.shape[0] % math.prod(
            mesh.shape[a] for a in (k_ax if isinstance(k_ax, tuple)
                                    else (k_ax,))):
        raise ValueError(
            f"quant_matmul: {scales.shape[0]} scale groups do not divide "
            f"over mesh axis {k_ax!r}; a K-sharded weight needs whole "
            "groups on every shard")
    lead = (None,) * (x.ndim - 1)

    def local(x, w_q, scales):
        if k_ax is None:
            return qm.quant_matmul(x, w_q, scales, out_dtype=out_dtype,
                                   interpret=_interpret())
        part = qm.quant_matmul(x, w_q, scales, out_dtype=jnp.float32,
                               interpret=_interpret())
        return jax.lax.psum(part, k_ax).astype(out_dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(*lead, k_ax), P(k_ax, n_ax), P(k_ax, n_ax)),
        out_specs=P(*lead, n_ax), check_vma=False)(x, w_q, scales)


def ssd_scan(x, dt, A, Bm, Cm, D, *, init_state=None, return_state=False,
             chunk=256, impl="auto"):
    if resolve_impl(impl) == "pallas":
        from . import ssd_scan as ssd

        return ssd.ssd_scan(
            x, dt, A, Bm, Cm, D, init_state=init_state,
            return_state=return_state, chunk=chunk, interpret=_interpret())
    return ref.ssd_scan_chunked(
        x, dt, A, Bm, Cm, D, init_state=init_state,
        return_state=return_state, chunk=chunk)


# Thin passthroughs (no kernel needed; kept here so models never import ref).
ssd_step = ref.ssd_step
causal_conv1d = ref.causal_conv1d
causal_conv1d_step = ref.causal_conv1d_step
quantize_weights = ref.quantize_weights
