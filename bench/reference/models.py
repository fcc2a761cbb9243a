"""Plain float32 references of the two served architectures.

Everything here is written from the architectures' equations and imports
nothing of the program.  Weights are made anew from the tenant's seed
with the same random-key schedule the program's initialiser documents
(``root = key(seed)``; ``split(root, 8)``: index 0 the embedding, index 2
the layers; one key per layer tensor in sorted name order, split once per
layer), so the reference and the program hold the same numbers without
sharing a byte.  Each variant then applies its own rounding:

* ``bf16``: every per-layer tensor and the embedding rounded to bfloat16
  (the final norm stays float32);
* ``int8`` / ``int4``: every per-layer matrix quantized symmetrically per
  (32-row group, column) and dequantized; everything else float32;
* ``fp8``: as ``bf16``, with the matrices and the embedding in float8
  e4m3 under one scale per tensor.

The forward pass is float32 at "highest" matmul precision, one layer per
call so that a layer's weights are the only ones alive.

Architectures (``model["family"]``):

* ``dense``: pre-norm GQA transformer, RMSNorm ``x * rsqrt(mean(x^2) +
  eps) * (1 + w)``, rotary embedding on halves, causal softmax attention
  scaled by ``head_dim ** -0.5``, SiLU-gated MLP, tied embedding.  The
  program serves granite-3-2b's widths with this block; Granite's
  embedding, attention, residual and logit multipliers are not part of it.
* ``ssm``: Mamba-2 blocks: in-projection to (z, x, B, C, dt), depthwise
  causal conv + SiLU over (x, B, C), the SSD recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``
  (computed here in its quadratic form), gated RMSNorm ``norm(y *
  silu(z))``, out-projection; tied embedding.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

GROUP = 32  # rows per quantization scale


@dataclass(frozen=True)
class Arch:
    family: str
    num_layers: int
    d_model: int
    vocab_size: int
    vocab_pad_multiple: int
    norm_eps: float = 1e-5
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    rope_theta: float = 10000.0
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_ngroups: int = 1

    @classmethod
    def from_model(cls, model: dict) -> "Arch":
        keys = cls.__dataclass_fields__
        return cls(**{k: v for k, v in model.items() if k in keys})

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def template(self) -> dict:
        """Per-layer tensor name -> (shape, init kind)."""
        D = self.d_model
        if self.family == "dense":
            H, KV, hd, F = (self.num_heads, self.num_kv_heads,
                            self.head_dim, self.d_ff)
            return {"ln1": ((D,), "zeros"), "ln2": ((D,), "zeros"),
                    "wq": ((D, H * hd), "dense"),
                    "wk": ((D, KV * hd), "dense"),
                    "wv": ((D, KV * hd), "dense"),
                    "wo": ((H * hd, D), "dense"),
                    "wg": ((D, F), "dense"), "wu": ((D, F), "dense"),
                    "wd": ((F, D), "dense")}
        if self.family == "ssm":
            di, nh = self.d_inner, self.ssm_heads
            GN = self.ssm_ngroups * self.ssm_state
            convd = di + 2 * GN
            return {"ln1": ((D,), "zeros"),
                    "ssm_in": ((D, 2 * di + 2 * GN + nh), "dense"),
                    "conv_w": ((self.ssm_conv_width, convd), "conv"),
                    "conv_b": ((convd,), "zeros"),
                    "A_log": ((nh,), "a_log"), "D_skip": ((nh,), "ones"),
                    "dt_bias": ((nh,), "dt_bias"),
                    "ssm_gnorm": ((di,), "zeros"),
                    "ssm_out": ((di, D), "dense")}
        raise ValueError(f"no reference for family {self.family!r}")


MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "ssm_in", "ssm_out")


def _init(key, shape, kind):
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jax.random.uniform(key, shape, jnp.float32, 1e-3, 0.1)
        return dt + jnp.log(-jnp.expm1(-dt))
    fan_in = shape[0] if kind == "conv" else shape[-2]
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def _int_round(w, bits):
    """Symmetric per-(row group, column) quantize, then dequantize."""
    K, N = w.shape
    g = GROUP if K % GROUP == 0 else K
    qmax = 2.0 ** (bits - 1) - 1
    wg = w.reshape(K // g, g, N)
    s = jnp.maximum(jnp.max(jnp.abs(wg), axis=1, keepdims=True) / qmax,
                    1e-8)
    return (jnp.clip(jnp.round(wg / s), -qmax - 1, qmax) * s).reshape(K, N)


def _fp8_round(w):
    s = jnp.maximum(jnp.max(jnp.abs(w)) / 448.0, 1e-30)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(w):
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def round_weight(name: str, w, variant: str):
    """One per-layer tensor (or ``"embed"``) as ``variant`` holds it."""
    matrix = name in MATRICES
    if variant in ("int8", "int4"):
        return _int_round(w, 8 if variant == "int8" else 4) if matrix else w
    if variant == "fp8" and (matrix or name == "embed"):
        return _fp8_round(w)
    if variant in ("bf16", "fp8"):
        return _bf16(w)
    raise ValueError(f"unknown variant {variant!r}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    """x: (R, T, heads, hd); positions 0..T-1."""
    T, half = x.shape[1], x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dense_block(a: Arch, p, h):
    R, T, D = h.shape
    H, KV, hd = a.num_heads, a.num_kv_heads, a.head_dim
    x = _rms(h, p["ln1"], a.norm_eps)
    q = _rope((x @ p["wq"]).reshape(R, T, H, hd), a.rope_theta)
    k = _rope((x @ p["wk"]).reshape(R, T, KV, hd), a.rope_theta)
    v = (x @ p["wv"]).reshape(R, T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    h = h + o.reshape(R, T, H * hd) @ p["wo"]
    x = _rms(h, p["ln2"], a.norm_eps)
    return h + (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def _ssm_block(a: Arch, p, h):
    R, T, D = h.shape
    di, nh, P = a.d_inner, a.ssm_heads, a.ssm_head_dim
    GN, W = a.ssm_ngroups * a.ssm_state, a.ssm_conv_width
    x = _rms(h, p["ln1"], a.norm_eps)
    zx = x @ p["ssm_in"]
    z, xbc, dt = zx[..., :di], zx[..., di:2 * di + 2 * GN], \
        zx[..., 2 * di + 2 * GN:]
    xp = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + T] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(R, T, nh, P)
    Bm = xbc[..., di:di + GN].reshape(R, T, a.ssm_ngroups, -1)
    Cm = xbc[..., di + GN:].reshape(R, T, a.ssm_ngroups, -1)
    rep = nh // a.ssm_ngroups
    Bm, Cm = jnp.repeat(Bm, rep, 2), jnp.repeat(Cm, rep, 2)  # (R,T,nh,N)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (R, T, nh)
    A = -jnp.exp(p["A_log"])
    cs = jnp.cumsum(dt * A, axis=1)  # (R, T, nh)
    seg = cs[:, :, None, :] - cs[:, None, :, :]  # (R, Tq, Tk, nh)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bqhn,bkhn->bqkh", Cm, Bm)
    y = jnp.einsum("bqkh,bkhp->bqhp", cb * decay * dt[:, None], xs)
    y = (y + xs * p["D_skip"][:, None]).reshape(R, T, di)
    y = _rms(y * jax.nn.silu(z), p["ssm_gnorm"], a.norm_eps)
    return h + y @ p["ssm_out"]


def _layer_keys(a: Arch, seed: int):
    keys = jax.random.split(jax.random.key(seed), 8)
    names = sorted(a.template())
    return keys[0], dict(zip(names, jax.random.split(keys[2], len(names))))


@functools.partial(jax.jit, static_argnames=("a", "variant"))
def _layer(a: Arch, variant: str, seed, layer, h):
    _, per_name = _layer_keys(a, seed)
    p = {}
    for name, (shape, kind) in a.template().items():
        k = jax.random.split(per_name[name], a.num_layers)[layer]
        p[name] = round_weight(name, _init(k, shape, kind), variant)
    with jax.default_matmul_precision("highest"):
        block = _dense_block if a.family == "dense" else _ssm_block
        return block(a, p, h)


@functools.partial(jax.jit, static_argnames=("a", "variant"))
def _embedding(a: Arch, variant: str, seed):
    k, _ = _layer_keys(a, seed)
    e = jax.random.normal(k, (1, a.padded_vocab, a.d_model), jnp.float32) \
        * a.d_model ** -0.5
    return round_weight("embed", e[0], variant)


@functools.partial(jax.jit, static_argnames=("a",))
def _head(a: Arch, emb, h, rows, cols):
    """Logits over the real vocabulary at ``h[rows, cols]``."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h[rows, cols], 0.0, a.norm_eps)
        return (x @ emb.T)[..., :a.vocab_size]


def logits(model: dict, seed: int, variant: str, tokens: np.ndarray,
           rows: np.ndarray, cols: np.ndarray) -> jax.Array:
    """Logits of ``variant`` over ``tokens`` (R, T) read at positions
    ``(rows, cols)`` (index arrays of one shape), float32."""
    a = Arch.from_model(model)
    emb = _embedding(a, variant, seed)
    h = emb[jnp.asarray(tokens)]
    for layer in range(a.num_layers):
        h = _layer(a, variant, seed, layer, h)
    return _head(a, emb, h, jnp.asarray(rows), jnp.asarray(cols))
