"""Pallas TPU Mamba-2 SSD scan (chunked state-space duality).

The paper's SSM tenants (mamba2, hymba) spend their FLOPs here.  The SSD
trick converts the elementwise recurrence into MXU-shaped work: a
quadratic *intra-chunk* block (attention-like (Q,Q)·(Q,P) matmuls) plus a
linear *inter-chunk* state recurrence — this kernel fuses both so the
(H, P, N) state never round-trips to HBM between chunks.

TPU mapping
-----------
* Grid ``(B, H, nc)`` with the chunk index innermost; the per-(b, h) SSM
  state lives in VMEM scratch across the whole chunk loop, stored
  transposed as (N, P) so every matmul in the body is a plain
  (non-transposed) MXU product.
* The per-head skip scalar D[h] arrives via SMEM scalar prefetch.
* The intra-chunk cumulative decay ``a_cum`` is a cumsum computed by the
  wrapper (the same op the jnp reference uses) and enters the kernel both
  as a row (1, Q) and as a column (Q, 1), so the (Q, Q) decay matrix is a
  broadcast difference with no in-kernel transpose.
* Every operand is laid out ``(..., nc, rows, cols)`` and blocked one
  whole chunk at a time, so each block's last two dims equal the array's
  and Mosaic's (8, 128) tiling rule holds at any chunk or prompt length.
* Tiles at (Q, P, N) = (256, 64, 128): x 256·64·4B + B/C 2·256·128·4B +
  decay matrix 256·256·4B + state 128·64·4B ≈ 0.7 MB VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(d_ref, x_ref, dt_ref, acr_ref, acc_ref, bt_ref,
                c_ref, init_ref, y_ref, state_ref, state_scr, *, nc, Q):
    h, ic = pl.program_id(1), pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0, 0]  # (1, Q)
    a_row = acr_ref[0, 0, 0]  # (1, Q) inclusive cumulative log-decay
    a_col = acc_ref[0, 0, 0]  # (Q, 1) the same values as a column
    Bt = bt_ref[0, 0, 0].astype(jnp.float32)  # (N, Q)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)
    Dk = d_ref[h]

    # Intra-chunk (attention-like) term.
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(ii >= jj, jnp.exp(a_col - a_row), 0.0)  # (Qi, Qj)
    cb = jnp.dot(Cm, Bt, preferred_element_type=jnp.float32)  # (Q, Q)
    M = cb * L * dt  # dt at the key position
    y = jnp.dot(M, x, preferred_element_type=jnp.float32)  # (Q, P)

    # Inter-chunk contribution from the carried state (held as (N, P)).
    state = state_scr[...]
    y += jnp.exp(a_col) * jnp.dot(Cm, state,
                                  preferred_element_type=jnp.float32)

    # State update: decay to chunk end + new outer products.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
    a_end = jnp.sum(jnp.where(lane == Q - 1, a_row, 0.0), axis=1,
                    keepdims=True)  # (1, 1)
    w = jnp.exp(a_end - a_row) * dt  # (1, Q)
    state_scr[...] = jnp.exp(a_end) * state + jnp.dot(
        Bt * w, x, preferred_element_type=jnp.float32)  # (N, P)

    y_ref[0, 0, 0] = (y + x * Dk).astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _finish():
        state_ref[0, 0] = state_scr[...]


def ssd_scan(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H)
    A: jnp.ndarray,  # (H,)
    Bm: jnp.ndarray,  # (B, S, G, N)
    Cm: jnp.ndarray,  # (B, S, G, N)
    D: jnp.ndarray,  # (H,)
    *,
    init_state: Optional[jnp.ndarray] = None,
    return_state: bool = False,
    chunk: int = 256,
    interpret: bool = False,
):
    Bb, S0, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Q = min(chunk, S0)
    pad = (Q - S0 % Q) % Q
    if pad:
        # dt=0 padding is exact: decay 1, zero contribution.
        tail = ((0, 0), (0, pad), (0, 0), (0, 0))
        x, Bm, Cm = jnp.pad(x, tail), jnp.pad(Bm, tail), jnp.pad(Cm, tail)
        dt = jnp.pad(dt, tail[:3])
    S = S0 + pad
    nc = S // Q
    # Chunk-major layouts: (B, heads, nc, rows, cols).
    xt = jnp.transpose(x.reshape(Bb, nc, Q, H, P), (0, 3, 1, 2, 4))
    ct = jnp.transpose(Cm.reshape(Bb, nc, Q, G, N), (0, 3, 1, 2, 4))
    btt = jnp.transpose(Bm.reshape(Bb, nc, Q, G, N), (0, 3, 1, 4, 2))
    dtf = jnp.transpose(dt.astype(jnp.float32).reshape(Bb, nc, Q, H),
                        (0, 3, 1, 2))  # (B, H, nc, Q)
    a_cum = jnp.cumsum(dtf * A.astype(jnp.float32)[None, :, None, None],
                       axis=-1)
    if init_state is None:
        init_state = jnp.zeros((Bb, H, P, N), jnp.float32)
    init_t = jnp.swapaxes(init_state.astype(jnp.float32), -1, -2)

    def head(b, h, c, *_):
        return (b, h, c, 0, 0)

    def group(b, h, c, *_):
        return (b, h // rep, c, 0, 0)

    def state_blk(b, h, c, *_):
        return (b, h, 0, 0)

    kernel = functools.partial(_ssd_kernel, nc=nc, Q=Q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), head),
            pl.BlockSpec((1, 1, 1, 1, Q), head),
            pl.BlockSpec((1, 1, 1, 1, Q), head),
            pl.BlockSpec((1, 1, 1, Q, 1), head),
            pl.BlockSpec((1, 1, 1, N, Q), group),
            pl.BlockSpec((1, 1, 1, Q, N), group),
            pl.BlockSpec((1, 1, N, P), state_blk),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), head),
            pl.BlockSpec((1, 1, N, P), state_blk),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
    )
    y, state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, nc, Q, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, N, P), jnp.float32),
        ],
        interpret=interpret,
        name="ssd_scan",
    )(D.astype(jnp.float32), xt,
      dtf[:, :, :, None, :], a_cum[:, :, :, None, :], a_cum[..., None],
      btt, ct, init_t)
    y = jnp.transpose(y, (0, 2, 3, 1, 4)).reshape(Bb, S, H, P)[:, :S0]
    if return_state:
        return y, jnp.swapaxes(state, -1, -2)
    return y
