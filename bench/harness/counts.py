"""Operations and bytes from shapes: per kernel call, and per served batch.

A kernel's roofline share is the least time the chip could take for the
work (the larger of operations over the peak rate and bytes over the HBM
bandwidth) over the time the trace measured.  The counts here are the
algorithm's: useful operations (padding rows are not counted) and each
operand read once, each result written once.  So a share can only read
low, never above 100%, when a kernel does more than that.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

GROUP = 32  # rows per int8 scale: one f32 scale per (group, column)


def quant_matmul(M: int, K: int, N: int, x_bytes: int, out_bytes: int
                 ) -> Tuple[float, float]:
    """``x (M, K) @ dequant(int8 (K, N), f32 scales (K/32, N))``."""
    ops = 2.0 * M * K * N
    nbytes = (M * K * x_bytes + K * N + (K // GROUP) * N * 4
              + M * N * out_bytes)
    return ops, float(nbytes)


def ssd_chunks(S: int, Q: int) -> List[int]:
    """Rows of each chunk of an ``S``-token scan in chunks of ``Q``."""
    return [min(Q, S - s) for s in range(0, S, Q)]


def ssd_scan(B: int, S: int, H: int, P: int, N: int, G: int, chunk: int,
             x_bytes: int) -> Tuple[float, float]:
    """One chunked SSD scan over (B, S, H, P) with G groups of state N:
    per chunk of q rows and head, ``C B^T`` (2 q^2 N), its product with x
    (2 q^2 P), the carried state's contribution (2 q N P) and the state
    update (2 q N P)."""
    Q = min(chunk, S)
    ops = B * H * sum(2.0 * q * q * (N + P) + 4.0 * q * N * P
                      for q in ssd_chunks(S, Q))
    nbytes = (2 * B * S * H * P * x_bytes  # x in, y out
              + 2 * B * S * G * N * x_bytes  # B, C
              + 3 * B * H * S * 4  # dt and the cumulative decay (twice)
              + 2 * B * H * P * N * 4)  # state in, state out
    return ops, float(nbytes)


def _dense_mm(m: dict) -> List[Tuple[int, int]]:
    D, H, KV, hd, F = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    return [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D),
            (D, F), (D, F), (F, D)]


def _ssm_dims(m: dict):
    di = m["ssm_expand"] * m["d_model"]
    nh = di // m["ssm_head_dim"]
    GN = m["ssm_ngroups"] * m["ssm_state"]
    return di, nh, GN


def _ssm_mm(m: dict) -> List[Tuple[int, int]]:
    D = m["d_model"]
    di, nh, GN = _ssm_dims(m)
    return [(D, 2 * di + 2 * GN + nh), (di, D)]


def _padded_vocab(m: dict) -> int:
    k = m["vocab_pad_multiple"]
    return (m["vocab_size"] + k - 1) // k * k


def kernel_calls(m: dict, bits: int, B: int, S: int, max_new: int
                 ) -> Dict[str, Tuple[float, float, int]]:
    """Per kernel: (ops, bytes, calls) summed over one served batch of
    ``B`` prompts of ``S`` tokens and ``max_new`` output tokens (a prefill,
    then ``max_new - 1`` decode steps)."""
    out: Dict[str, Tuple[float, float, int]] = {}
    L = m["num_layers"]

    def add(name, ops, nbytes, n):
        o, b, c = out.get(name, (0.0, 0.0, 0))
        out[name] = (o + ops * n, b + nbytes * n, c + n)

    # int8 variants keep the embedding in f32, so activations are f32.
    x_bytes = 4 if bits < 16 else 2
    if bits < 16:
        mats = _dense_mm(m) if m["family"] == "dense" else _ssm_mm(m)
        for K, N in mats:
            add("quant_matmul", *quant_matmul(B * S, K, N, x_bytes, x_bytes),
                L)
            add("quant_matmul", *quant_matmul(B, K, N, x_bytes, x_bytes),
                L * (max_new - 1))
    if m["family"] == "ssm":
        di, nh, GN = _ssm_dims(m)
        add("ssd_scan", *ssd_scan(B, S, nh, m["ssm_head_dim"],
                                  m["ssm_state"], m["ssm_ngroups"],
                                  m["ssm_chunk"], x_bytes), L)
    return out


def model_flops(m: dict, B: int, S: int, max_new: int) -> float:
    """Operations the model's math needs for one served batch: every
    prompt position the batch holds (its padding included, which the
    program computes), then ``max_new - 1`` decode steps; causal attention
    over valid positions only; the LM head once per emitted token."""
    L, D = m["num_layers"], m["d_model"]
    head = 2.0 * D * _padded_vocab(m)
    if m["family"] == "dense":
        per_tok = sum(2.0 * K * N for K, N in _dense_mm(m))
        hd2 = 2.0 * 2.0 * m["num_heads"] * m["head_dim"]  # QK^T and PV
        prefill = L * (B * S * per_tok + hd2 * B * S * (S + 1) / 2)
        decode = sum(L * (B * per_tok + hd2 * B * (S + t + 1))
                     for t in range(max_new - 1))
    else:
        di, nh, GN = _ssm_dims(m)
        convd = di + 2 * GN
        per_tok = (sum(2.0 * K * N for K, N in _ssm_mm(m))
                   + 2.0 * m["ssm_conv_width"] * convd)
        scan, _ = ssd_scan(B, S, nh, m["ssm_head_dim"], m["ssm_state"],
                           m["ssm_ngroups"], m["ssm_chunk"], 2)
        prefill = L * (B * S * per_tok + scan)
        step = 6.0 * di * m["ssm_state"]  # state decay+update, C.h
        decode = (max_new - 1) * L * B * (per_tok + step)
    return prefill + decode + head * B * max_new
