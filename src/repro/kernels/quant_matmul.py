"""Pallas TPU quantized matmul: bf16 activations × int8/int4 weights with
per-(K-group, N-column) symmetric scales, dequantized on the fly in VMEM.

This is the compute core of the paper's model-zoo idea on TPU: the low
precision variants are *served through this kernel*, so the ~2–4× weight
footprint saving (which is what the Edge-MultiAI manager trades on) comes
with HBM-bandwidth savings rather than a dequantize-to-HBM round trip.

The same int8-payload-plus-per-group-scales layout is the serving
stack's *wire format* too: ``LoaderSpec(compress="int8")`` stages loads
in it (``repro.distributed.compression.wire_compression_ratio`` prices
the transfer), and a ``Downgrade(in_place=True)`` in the residency IR
requantizes resident leaves into it on-chip — a variant switch that
moves zero bytes over the host link, because the weights this kernel
serves are exactly what :func:`quantize_params` derives from the wider
resident copy.

TPU mapping
-----------
* Grid ``(nM, nN, nK)``, K innermost; an f32 accumulator tile persists in
  VMEM scratch across the K loop and is flushed once per (M, N) tile.
* The weight tile is loaded as int8 (half/quarter the HBM bytes of bf16 —
  the whole point), upcast in-register, scaled by the per-group scale row,
  and fed to the MXU via ``dot_general`` with f32 accumulation.
* Block sizes default to (256, 256, 512); K blocks are chosen to divide
  the quantization group so each K tile sees exactly one scale row
  (``block_k = lcm(group, 128)`` handled by the wrapper).
* VMEM at defaults: x 256×512×2B + w 512×256×1B + acc 256×256×4B ≈ 0.6 MB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)  # (bm, bk)
    w = w_ref[...].astype(jnp.float32)  # (bk, bn) — dequant below
    s = s_ref[...].astype(jnp.float32)  # (gk, bn) scale rows for this K tile
    gk = s.shape[0]
    bk = w.shape[0]
    group = bk // gk
    w = w.reshape(gk, group, -1) * s[:, None, :]
    w = w.reshape(bk, -1)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def quant_matmul(
    x: jnp.ndarray,  # (..., K) bf16/f32
    w_q: jnp.ndarray,  # (K, N) int8 (int4 values in int8 storage)
    scales: jnp.ndarray,  # (K // group, N) f32
    *,
    out_dtype=None,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    out_dtype = out_dtype or x.dtype
    K, N = w_q.shape
    G = scales.shape[0]
    group = K // G
    lead = x.shape[:-1]
    M = math.prod(lead)
    x2 = x.reshape(M, K)

    bm = min(block_m, max(8, M))
    bn = min(block_n, N)
    # K blocks must hold an integer number of scale groups.
    bk = min(block_k, K)
    bk = max(group, (bk // group) * group)
    Mp = math.ceil(M / bm) * bm
    Np = math.ceil(N / bn) * bn
    Kp = math.ceil(K / bk) * bk
    if Mp != M:
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    if Kp != K or Np != N:
        x2 = jnp.pad(x2, ((0, 0), (0, Kp - K)))
        w_q = jnp.pad(w_q, ((0, Kp - K), (0, Np - N)))
        scales = jnp.pad(scales, ((0, (Kp - K) // group), (0, Np - N)))
    nm, nn, nk = Mp // bm, Np // bn, Kp // bk
    gk = bk // group  # scale rows per K tile

    kernel = functools.partial(_qmm_kernel, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((gk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="quant_matmul",
    )(x2, w_q, scales)
    return out[:M, :N].reshape(*lead, N)
