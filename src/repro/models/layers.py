"""Building-block layers shared by all 10 architecture families.

Everything is a pure function over explicit parameter pytrees (no module
framework).  Per-layer parameters arrive stacked with a leading ``L`` dim and
are consumed one slice at a time inside the layer scan in
:mod:`repro.models.transformer`.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from repro.distributed.ctx import hint
from repro.models.config import ModelConfig
from repro.quant.quantize import PlacedQuant


# ---------------------------------------------------------------------------
# Weight application — transparently serves quantized zoo variants through
# the fused dequant matmul kernel (the paper's low-precision serving path).
# ---------------------------------------------------------------------------
def _is_q(w) -> bool:
    return isinstance(w, dict) and set(w) == {"q", "s"}


def mm(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w for dense or quantized ({"q","s"} or mesh-placed) 2-D
    weights."""
    if isinstance(w, PlacedQuant):
        return ops.quant_matmul(x, w.q, w.s, out_dtype=x.dtype,
                                mesh=w.mesh, spec=w.spec)
    if _is_q(w):
        return ops.quant_matmul(x, w["q"], w["s"], out_dtype=x.dtype)
    return x @ w


def dense_w(w) -> jnp.ndarray:
    """Materialize a (possibly quantized) weight densely — used for >2-D
    expert tensors and embedding-style contractions where the fused kernel
    doesn't apply."""
    if isinstance(w, PlacedQuant):
        w = {"q": w.q, "s": w.s}
    if _is_q(w):
        from repro.quant.quantize import dequantize_leaf

        return dequantize_leaf(w)
    return w


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    # Variance accumulates in f32 via the dot's accumulator — no f32 copy
    # of x ever materializes (XLA CPU hoists such converts of the whole
    # remat stack into a 3.75 GB/device buffer on the biggest tenant).
    var = jnp.einsum("...d,...d->...", x, x,
                     preferred_element_type=jnp.float32) / x.shape[-1]
    scale = lax.rsqrt(var + eps)[..., None]
    wf = (1.0 + w.astype(jnp.float32))
    return (x * scale.astype(x.dtype)) * wf.astype(x.dtype)


def act_fn(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D) with positions (S,) or (B, S)."""
    B, S, H, D = x.shape
    half = D // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions.astype(jnp.float32)[:, :, None] * freq[None, None, :]
    cos = jnp.cos(ang)[:, :, None, :]  # (B, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention branch (full-sequence prefill/train and single-token decode)
# ---------------------------------------------------------------------------
def attention_prefill(
    cfg: ModelConfig,
    lp: dict,
    x: jnp.ndarray,  # (B, S, D) — already input-normed
    positions: jnp.ndarray,  # (S,) or (B, S)
    window: jnp.ndarray,  # scalar int32, 0 = full
    prefix: int = 0,  # positions < prefix always visible (hymba meta tokens)
):
    """Returns (attn_out (B,S,H*hd), k, v) so the caller can build caches."""
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = hint(mm(x, lp["wq"]).reshape(B, S, H, hd),
             "dp", None, "model", None)
    k = hint(mm(x, lp["wk"]).reshape(B, S, KV, hd),
             "dp", None, "model", None)
    v = hint(mm(x, lp["wv"]).reshape(B, S, KV, hd),
             "dp", None, "model", None)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    out = _masked_attention(
        q, k, v,
        window=window,
        softcap_v=cfg.attn_logit_softcap,
        scale=cfg.attn_scale,
        prefix=prefix,
    )
    return out.reshape(B, S, H * hd), k, v


ATTN_BLOCK_Q = 512  # q-chunk size for the blocked jnp attention path


def _masked_attention(q, k, v, *, window, softcap_v, scale, prefix):
    """Blocked-softmax reference attention with dynamic (traced) window.

    KV heads are repeated up to H *before* the score matmul so the head
    dim shards cleanly on the TP axis (a grouped (KV, G) reshape would
    split one mesh axis across two tensor dims, which SPMD cannot
    express).  Queries stream in ``ATTN_BLOCK_Q`` chunks via the layer
    ``_scan`` (so score tensors never exceed B×H×bq×T — this is what
    keeps the lowered train graphs inside HBM; the Pallas flash kernel is
    the VMEM-resident production analogue).  ``window`` is a traced
    scalar so one scanned layer body serves local and global layers.
    """
    from repro.models.transformer import _scan

    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    sc = scale if scale else D ** -0.5
    if G > 1:
        k = hint(jnp.repeat(k, G, axis=2), "dp", None, "model", None)
        v = hint(jnp.repeat(v, G, axis=2), "dp", None, "model", None)
    kv_pos = jnp.arange(S)[None, :]  # (1, T)

    def attend_block(qb, pos0):
        """qb: (B, bq, H, D), absolute positions pos0 + arange(bq)."""
        bq = qb.shape[1]
        qs_ = (qb.astype(jnp.float32) * sc).astype(qb.dtype)
        # f32 accumulation inside the dots; k/v stay in storage dtype so
        # no full-size f32 copies materialize.
        s = hint(jnp.einsum("bqhd,bthd->bhqt", qs_, k,
                            preferred_element_type=jnp.float32),
                 "dp", "model", None, None)
        if softcap_v:
            s = softcap(s, softcap_v)
        q_pos = pos0 + jnp.arange(bq)[:, None]  # (bq, 1)
        mask = kv_pos <= q_pos
        in_w = (window == 0) | (kv_pos > q_pos - window) | (kv_pos < prefix)
        mask = mask & in_w
        s = jnp.where(mask[None, None], s, -2.3819763e38)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqt,bthd->bqhd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32).astype(q.dtype)
        return hint(o, "dp", None, "model", None)

    bq = ATTN_BLOCK_Q
    if S <= bq:
        return attend_block(q, 0)
    nq, rem = divmod(S, bq)
    # Scan stacks/emissions must stay head-sharded or the bwd cotangent
    # stack materializes fully gathered (measured: +17 GB/device).
    qs = hint(jnp.moveaxis(
        q[:, :nq * bq].reshape(B, nq, bq, H, D), 1, 0),
        None, "dp", None, "model", None)  # (nq, B, bq, H, D)
    offs = jnp.arange(nq) * bq

    def body(_, inp):
        qb, off = inp
        return (), attend_block(qb, off)

    # Recompute scores in the backward pass instead of saving the full
    # (nq, B, H, bq, T) stacks (~10 GB/device on hymba under DP-only) —
    # the same trade flash attention makes on TPU.
    body = jax.checkpoint(body, prevent_cse=False)
    _, blocks = _scan(body, (), (qs, offs))  # (nq, B, bq, H, D)
    blocks = hint(blocks, None, "dp", None, "model", None)
    out = jnp.moveaxis(blocks, 0, 1).reshape(B, nq * bq, H, D)
    if rem:
        out = jnp.concatenate(
            [out, attend_block(q[:, nq * bq:], nq * bq)], axis=1)
    return out


def attention_decode(
    cfg: ModelConfig,
    lp: dict,
    x: jnp.ndarray,  # (B, 1, D) input-normed single token
    k_cache: jnp.ndarray,  # (B, T, KV, hd)
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,  # (B,) current valid length (new token index)
    window: jnp.ndarray,  # scalar int32
    prefix: int = 0,
    uniform_pos: bool = False,
):
    """Returns (attn_out (B, 1, H*hd), new_k_cache, new_v_cache).

    ``uniform_pos=True`` writes the cache with one dynamic_update_slice
    (all rows at the same decode position — true for the lowered
    serve_step's synchronized batches).  The per-row scatter path exists
    for ragged serving batches, but XLA:CPU lowers bf16 scatters via an
    f32 upcast of the *whole* cache stack (measured 6 GB/device), and the
    dry-run must reflect the TPU behaviour, not that artifact."""
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    xq = x[:, 0, :]
    q = mm(xq, lp["wq"]).reshape(B, 1, H, hd)
    k = mm(xq, lp["wk"]).reshape(B, 1, KV, hd)
    v = mm(xq, lp["wv"]).reshape(B, 1, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    pos = lengths[:, None]  # (B, 1) absolute positions
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    if uniform_pos:
        # Deferred-write path: attend over the cache + the fresh token
        # directly; the caller stacks the per-layer (B, KV, hd) new k/v and
        # writes them into the big cache with ONE dynamic_update_slice
        # after the layer scan.  This removes L whole-cache copies per
        # decode step from the scan emission (and the f32 upcast XLA:CPU
        # applies to them).
        out = _decode_attention_deferred(
            q[:, 0], k[:, 0], v[:, 0], k_cache, v_cache, lengths,
            window=window, softcap_v=cfg.attn_logit_softcap,
            scale=cfg.attn_scale, prefix=prefix)
        return (out.reshape(B, 1, H * hd),
                k[:, 0].astype(k_cache.dtype),
                v[:, 0].astype(v_cache.dtype))
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, lengths].set(
        k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, lengths].set(
        v[:, 0].astype(v_cache.dtype))
    out = _decode_attention_windowed(
        q[:, 0], k_cache, v_cache, lengths + 1,
        window=window,
        softcap_v=cfg.attn_logit_softcap,
        scale=cfg.attn_scale,
        prefix=prefix,
    )
    return out.reshape(B, 1, H * hd), k_cache, v_cache


def quantize_kv(x: jnp.ndarray):
    """Per-(token, kv-head) symmetric int8 quantization of k/v rows.
    x: (..., KV, hd) -> (int8 values, f32 scales (..., KV))."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scales = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scales[..., None]),
                 -128, 127).astype(jnp.int8)
    return q, scales.astype(jnp.float32)


def _decode_attention_deferred_q(q, k_new, v_new, kq, ks, vq, vs, lengths,
                                 *, window, softcap_v, scale, prefix):
    """int8-KV-cache decode attention (§Perf C3): the cache streams at
    half the bytes; dequantization folds into the score/output scaling
    (one multiply per (token, head) — never a dequantized cache copy).

    kq/vq: (B, T, KV, hd) int8;  ks/vs: (B, T, KV) f32.
    """
    B, H, D = q.shape
    T, KV = kq.shape[1], kq.shape[2]
    G = H // KV
    sc = scale if scale else D ** -0.5
    qf = (q.astype(jnp.float32) * sc).astype(q.dtype).reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, kq.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    s = s * jnp.moveaxis(ks, 1, 2)[:, :, None, :]  # fold in k scales
    s_self = jnp.einsum("bkgd,bkd->bkg", qf, k_new,
                        preferred_element_type=jnp.float32)[..., None]
    if softcap_v:
        s = softcap(s, softcap_v)
        s_self = softcap(s_self, softcap_v)
    kv_pos = jnp.arange(T)[None, :]
    valid = kv_pos < lengths[:, None]
    in_w = (window == 0) | (kv_pos >= lengths[:, None] + 1 - window) | (
        kv_pos < prefix)
    valid = valid & in_w
    s = jnp.where(valid[:, None, None, :], s, -2.3819763e38)
    # Self token combined via log-sum-exp, NOT concat: concatenating onto
    # the T dim breaks its sharding and XLA all-gathers the whole cache
    # (measured 1 GB/layer on llama4 decode).
    m = jnp.maximum(jnp.max(s, -1, keepdims=True), s_self)
    e = jnp.exp(s - m)
    e_self = jnp.exp(s_self - m)
    denom = jnp.sum(e, -1, keepdims=True) + e_self
    # fold v scales into the weights (e_t · s_t) before the int8 pv
    ec = (e * jnp.moveaxis(vs, 1, 2)[:, :, None, :]).astype(q.dtype)
    o = jnp.einsum("bkgt,btkd->bkgd", ec, vq.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    o = (o + e_self * v_new.astype(jnp.float32)[:, :, None, :]) / denom
    return o.reshape(B, H, D).astype(q.dtype)


def attention_decode_q(cfg, lp, x, kq, ks, vq, vs, lengths, window,
                       prefix=0):
    """Quantized-cache decode step (deferred write).  Returns
    (attn_out, k_new_q, k_new_s, v_new_q, v_new_s)."""
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    xq = x[:, 0, :]
    q = mm(xq, lp["wq"]).reshape(B, 1, H, hd)
    k = mm(xq, lp["wk"]).reshape(B, 1, KV, hd)
    v = mm(xq, lp["wv"]).reshape(B, 1, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    pos = lengths[:, None]
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    out = _decode_attention_deferred_q(
        q[:, 0], k[:, 0], v[:, 0], kq, ks, vq, vs, lengths,
        window=window, softcap_v=cfg.attn_logit_softcap,
        scale=cfg.attn_scale, prefix=prefix)
    knq, kns = quantize_kv(k[:, 0])
    vnq, vns = quantize_kv(v[:, 0])
    return out.reshape(B, 1, H * hd), knq, kns, vnq, vns


def _decode_attention_deferred(q, k_new, v_new, k_cache, v_cache, lengths,
                               *, window, softcap_v, scale, prefix):
    """Decode attention where the fresh token's k/v ride alongside the
    (not-yet-updated) cache: scores over [cache, self]."""
    B, H, D = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    sc = scale if scale else D ** -0.5
    qf = (q.astype(jnp.float32) * sc).astype(q.dtype).reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, k_cache,
                   preferred_element_type=jnp.float32)
    s_self = jnp.einsum("bkgd,bkd->bkg", qf, k_new,
                        preferred_element_type=jnp.float32)[..., None]
    if softcap_v:
        s = softcap(s, softcap_v)
        s_self = softcap(s_self, softcap_v)
    kv_pos = jnp.arange(T)[None, :]
    valid = kv_pos < lengths[:, None]
    in_w = (window == 0) | (kv_pos >= lengths[:, None] + 1 - window) | (
        kv_pos < prefix)
    valid = valid & in_w
    s = jnp.where(valid[:, None, None, :], s, -2.3819763e38)
    # log-sum-exp combine (see the quantized variant for why not concat)
    m = jnp.maximum(jnp.max(s, -1, keepdims=True), s_self)
    e = jnp.exp(s - m)
    e_self = jnp.exp(s_self - m)
    denom = jnp.sum(e, -1, keepdims=True) + e_self
    o = jnp.einsum("bkgt,btkd->bkgd", e.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = (o + e_self * v_new.astype(jnp.float32)[:, :, None, :]) / denom
    return o.reshape(B, H, D).astype(q.dtype)


def _decode_attention_windowed(q, k_cache, v_cache, lengths, *, window,
                               softcap_v, scale, prefix):
    B, H, D = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    sc = scale if scale else D ** -0.5
    # The cache stays in its storage dtype: upcasting it would materialize
    # an f32 copy of the ENTIRE stacked KV cache (measured 6 GB/device on
    # musicgen decode — XLA hoists the convert out of the layer scan).
    # f32 accumulation happens inside the dots instead.
    qf = (q.astype(jnp.float32) * sc).astype(q.dtype).reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, k_cache,
                   preferred_element_type=jnp.float32)
    if softcap_v:
        s = softcap(s, softcap_v)
    kv_pos = jnp.arange(T)[None, :]
    valid = kv_pos < lengths[:, None]
    in_window = (window == 0) | (kv_pos >= lengths[:, None] - window) | (
        kv_pos < prefix)
    valid = valid & in_window
    s = jnp.where(valid[:, None, None, :], s, -2.3819763e38)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------
def mlp(cfg: ModelConfig, x: jnp.ndarray, wg, wu, wd) -> jnp.ndarray:
    h = act_fn(mm(x, wg), cfg.act) * mm(x, wu)
    h = hint(h, *(["dp"] + [None] * (h.ndim - 2) + ["model"]))
    return mm(h, wd)


# ---------------------------------------------------------------------------
# Mixture-of-Experts FFN
# ---------------------------------------------------------------------------
def moe_ffn(cfg: ModelConfig, lp: dict, x: jnp.ndarray,
            impl: str = "dense") -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D).

    ``impl="dense"`` is the paper-faithful baseline formulation: every expert
    processes every token and the one-hot gates zero the rest.  It is simple
    and shards cleanly (experts over the ``model`` axis), at the cost of
    E/K× redundant FLOPs — visible in the roofline's useful-flops ratio and
    attacked in the §Perf hillclimb via the "ragged" implementation.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    xt = x.reshape(B * S, D)
    logits = mm(xt, lp["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, K)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    gates = jnp.sum(
        jax.nn.one_hot(topi, E, dtype=jnp.float32) * topv[..., None], axis=1
    )  # (T, E)
    if impl == "ragged":
        y = _moe_ragged(cfg, lp, xt, topi, topv)
        if cfg.num_shared_experts:
            y = y + mlp(cfg, xt, lp["ws_g"], lp["ws_u"], lp["ws_d"])
    elif impl == "local":
        # shared expert computed inside the shard_map: its partial sums
        # ride the SAME model-axis psum as the routed experts (one AR
        # instead of two per layer, fwd and bwd — §Perf A3).
        y = _moe_local(cfg, lp, xt, topi, topv)
    else:
        y = _moe_dense(cfg, lp, xt, gates)
        if cfg.num_shared_experts:
            y = y + mlp(cfg, xt, lp["ws_g"], lp["ws_u"], lp["ws_d"])
    return y.reshape(B, S, D)


def _moe_dense(cfg, lp, xt, gates):
    # Token dim stays DP-sharded and experts stay TP-sharded — without
    # these hints XLA resolves the (dp × model × fsdp) axis conflict by
    # replicating the full token dim in the backward pass (measured:
    # ~10 live f32[T_full, D] buffers on llama4-scout).
    xt = hint(xt, "dp", None)
    hg = hint(jnp.einsum("td,edf->tef", xt, dense_w(lp["we_g"])),
              "dp", "model", None)
    hu = hint(jnp.einsum("td,edf->tef", xt, dense_w(lp["we_u"])),
              "dp", "model", None)
    hh = act_fn(hg, cfg.act) * hu
    hh = hint(hh * gates.astype(hh.dtype)[:, :, None], "dp", "model", None)
    return hint(jnp.einsum("tef,efd->td", hh, dense_w(lp["we_d"])),
                "dp", None)


def _moe_local(cfg, lp, xt, topi, topv):
    """TP-native expert-local MoE (the §Perf hillclimb winner for MoE
    tenants).

    Activations are already replicated across the ``model`` axis under
    Megatron TP, so dispatch needs NO communication: each model-rank
    selects (capacity-bounded) the tokens routed to ITS experts from its
    replicated copy, runs a dense per-expert matmul, and the combine is
    the psum over ``model`` that the block performs anyway.  Spends only
    routed FLOPs (vs E/K× for the dense baseline) at the cost of
    capacity-dropping overflow tokens (capacity factor 2.0)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.ctx import get_ctx

    ctx = get_ctx()
    T, D = xt.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    m_ax, m = ctx.model_axis, ctx.model_size
    d_ax = ctx.dp_spec
    assert E % m == 0, "local MoE needs experts divisible by model axis"
    e_loc = E // m
    t_loc = T // ctx.dp_size
    # per (expert, data-shard); never more than the slot count
    cap = min(max(32, int(2.0 * t_loc * K / E)), t_loc * K)

    shared = bool(cfg.num_shared_experts)

    def local(x, we_g, we_u, we_d, ti, tv, *sw):
        # x: (t_loc, D) — this data-shard's tokens (replicated over model)
        # we_*: (e_loc, D, F) — this model-rank's experts
        # ti/tv: (t_loc, K) routed experts / gates
        # sw: optional model-sharded shared-expert weights
        rank = jax.lax.axis_index(m_ax)
        slots_e = ti.reshape(-1)  # (t_loc*K,)
        slots_v = tv.reshape(-1)
        slot_tok = jnp.arange(t_loc * K) // K
        out = jnp.zeros((t_loc, D), jnp.float32)
        for j in range(e_loc):
            eid = rank * e_loc + j
            match = slots_e == eid
            # fixed-capacity local selection (top_k on match positions)
            score = jnp.where(match, jnp.arange(t_loc * K), -1)
            sel = jax.lax.top_k(score, cap)[0]  # slot ids, -1 = empty
            valid = sel >= 0
            tok = jnp.where(valid, slot_tok[jnp.maximum(sel, 0)], 0)
            gate = jnp.where(valid, slots_v[jnp.maximum(sel, 0)], 0.0)
            xe = jnp.take(x, tok, axis=0)  # (cap, D)
            h = act_fn(xe @ we_g[j], cfg.act) * (xe @ we_u[j])
            ye = (h @ we_d[j]).astype(jnp.float32)
            ye = ye * gate[:, None]
            out = out.at[tok].add(jnp.where(valid[:, None], ye, 0.0))
        if sw:
            ws_g, ws_u, ws_d = sw  # (D, F/m), (D, F/m), (F/m, D)
            hs = act_fn(x @ ws_g, cfg.act) * (x @ ws_u)
            out = out + (hs @ ws_d).astype(jnp.float32)
        # Combine in bf16: each token's output comes from exactly K expert
        # ranks (the rest contribute zeros), so the low-precision sum is
        # benign — and the wire bytes halve on bf16-native fabrics.
        return jax.lax.psum(out.astype(x.dtype), m_ax)

    in_specs = [P(d_ax, None), P(m_ax, None, None), P(m_ax, None, None),
                P(m_ax, None, None), P(d_ax, None), P(d_ax, None)]
    args = [xt, dense_w(lp["we_g"]), dense_w(lp["we_u"]),
            dense_w(lp["we_d"]), topi, topv]
    if shared:
        in_specs += [P(None, m_ax), P(None, m_ax), P(m_ax, None)]
        args += [dense_w(lp["ws_g"]), dense_w(lp["ws_u"]),
                 dense_w(lp["ws_d"])]
    fn = jax.shard_map(
        local,
        in_specs=tuple(in_specs),
        out_specs=P(d_ax, None),
        check_vma=False,
    )
    out = fn(*args)
    return out.astype(xt.dtype)


def _moe_ragged(cfg, lp, xt, topi, topv):
    """Sort-based token routing with jax.lax.ragged_dot: only the routed
    top-K expert FLOPs are spent (the §Perf optimized path)."""
    T, D = xt.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    flat_e = topi.reshape(-1)  # (T*K,)
    order = jnp.argsort(flat_e)
    tok_of = order // K  # originating token per routed slot
    xs = jnp.take(xt, tok_of, axis=0)  # (T*K, D) sorted by expert
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    hg = lax.ragged_dot(xs, dense_w(lp["we_g"]), group_sizes)
    hu = lax.ragged_dot(xs, dense_w(lp["we_u"]), group_sizes)
    hh = act_fn(hg, cfg.act) * hu
    ys = lax.ragged_dot(hh, dense_w(lp["we_d"]), group_sizes)  # (T*K, D)
    w = jnp.take(topv.reshape(-1), order)  # gate per routed slot
    ys = ys * w[:, None].astype(ys.dtype)
    return jax.ops.segment_sum(ys, tok_of, num_segments=T)


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) branch
# ---------------------------------------------------------------------------
def _ssm_dims(cfg: ModelConfig, hybrid: bool):
    di = cfg.d_model if hybrid else cfg.ssm_d_inner
    nh = di // cfg.ssm_head_dim
    return di, nh


def ssm_prefill(
    cfg: ModelConfig,
    lp: dict,
    x: jnp.ndarray,  # (B, S, D) input-normed
    *,
    hybrid: bool = False,
    init_state=None,
    init_conv=None,
    return_state: bool = False,
):
    """Returns y (B, S, di) pre-out-proj [+ (ssm_state, conv_tail)]."""
    B, S, _ = x.shape
    di, nh = _ssm_dims(cfg, hybrid)
    G, N, W = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_conv_width
    zxbcdt = hint(mm(x, lp["ssm_in"]), "dp", None, "model")
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di: 2 * di + 2 * G * N]
    dt_raw = zxbcdt[..., 2 * di + 2 * G * N:]
    xbc = ops.causal_conv1d(xbc, lp["conv_w"], lp["conv_b"], init=init_conv)
    xs = xbc[..., :di]
    Bm = xbc[..., di: di + G * N].reshape(B, S, G, N)
    Cm = xbc[..., di + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    xh = hint(xs.reshape(B, S, nh, cfg.ssm_head_dim),
              "dp", None, "model", None)
    out = ops.ssd_scan(
        xh, dt.astype(xh.dtype), A, Bm, Cm, lp["D_skip"],
        init_state=init_state, return_state=return_state,
        chunk=cfg.ssm_chunk)
    if return_state:
        y, state = out
    else:
        y, state = out, None
    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 lp["ssm_gnorm"], cfg.norm_eps)
    if return_state:
        conv_tail = _conv_tail(xbc_pre_conv=zxbcdt[..., di: 2 * di + 2 * G * N],
                               init=init_conv, W=W)
        return y, state, conv_tail
    return y


def _conv_tail(xbc_pre_conv, init, W):
    """Last W-1 pre-activation conv inputs — the decode rolling buffer."""
    B, S, C = xbc_pre_conv.shape
    if init is None:
        init = jnp.zeros((B, W - 1, C), xbc_pre_conv.dtype)
    full = jnp.concatenate([init, xbc_pre_conv], axis=1)
    return full[:, -(W - 1):, :]


def ssm_decode(
    cfg: ModelConfig,
    lp: dict,
    x: jnp.ndarray,  # (B, 1, D) input-normed
    state: jnp.ndarray,  # (B, nh, hd, N)
    conv_buf: jnp.ndarray,  # (B, W-1, convd)
    *,
    hybrid: bool = False,
):
    """Single-token SSD step.  Returns (y (B,1,di), new_state, new_conv)."""
    B = x.shape[0]
    di, nh = _ssm_dims(cfg, hybrid)
    G, N = cfg.ssm_ngroups, cfg.ssm_state
    zxbcdt = mm(x[:, 0, :], lp["ssm_in"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di: 2 * di + 2 * G * N]
    dt_raw = zxbcdt[..., 2 * di + 2 * G * N:]
    xbc_act, new_conv = ops.causal_conv1d_step(
        xbc, lp["conv_w"], lp["conv_b"], conv_buf)
    xs = xbc_act[..., :di]
    Bm = xbc_act[..., di: di + G * N].reshape(B, G, N)
    Cm = xbc_act[..., di + G * N:].reshape(B, G, N)
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    xh = xs.reshape(B, nh, cfg.ssm_head_dim)
    y, new_state = ops.ssd_step(xh, dt, A, Bm, Cm, lp["D_skip"], state)
    y = y.reshape(B, di)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 lp["ssm_gnorm"], cfg.norm_eps)
    return y[:, None, :], new_state, new_conv
