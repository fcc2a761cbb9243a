"""Operation and byte counts of the served kernels, against hand counts
at one served shape each."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import counts  # noqa: E402


def test_quant_matmul_counts_granite_wq_decode_batch4():
    # x f32 (4, 2048) @ int8 (2048, 2048) with f32 scales (64, 2048).
    ops, nbytes = counts.quant_matmul(4, 2048, 2048, 4, 4)
    assert ops == 2 * 4 * 2048 * 2048 == 33_554_432
    assert nbytes == (4 * 2048 * 4 + 2048 * 2048 + 64 * 2048 * 4
                      + 4 * 2048 * 4) == 4_784_128


def test_ssd_scan_counts_mamba_prefill_320_tokens():
    # One 320-token prompt: chunks of 256 and 64 rows, 48 heads of
    # (P, N) = (64, 128), one group, bf16 activations.
    ops, nbytes = counts.ssd_scan(1, 320, 48, 64, 128, 1, 256, 2)
    per_head = (2 * 256 ** 2 * (128 + 64) + 4 * 256 * 128 * 64
                + 2 * 64 ** 2 * (128 + 64) + 4 * 64 * 128 * 64)
    assert per_head == 37_224_448
    assert ops == 48 * per_head == 1_786_773_504
    assert nbytes == (2 * 320 * 48 * 64 * 2 + 2 * 320 * 128 * 2
                      + 3 * 48 * 320 * 4 + 2 * 48 * 64 * 128 * 4)
    assert nbytes == 7_426_048


def test_ssd_chunks_cover_the_prompt():
    assert counts.ssd_chunks(320, 256) == [256, 64]
    assert counts.ssd_chunks(64, 64) == [64]


MAMBA = dict(family="ssm", num_layers=48, d_model=1536, vocab_size=50280,
             vocab_pad_multiple=256, ssm_state=128, ssm_head_dim=64,
             ssm_expand=2, ssm_chunk=256, ssm_conv_width=4, ssm_ngroups=1)
GRANITE = dict(family="dense", num_layers=40, d_model=2048, num_heads=32,
               num_kv_heads=8, head_dim=64, d_ff=8192, vocab_size=49155,
               vocab_pad_multiple=256)


def test_kernel_calls_per_batch():
    # int8 granite: 7 matmuls per layer in the prefill and in each of
    # the 31 decode steps; bf16 granite runs no kernel.
    calls = counts.kernel_calls(GRANITE, 8, 4, 64, 32)
    assert calls["quant_matmul"][2] == 7 * 40 * 32
    assert counts.kernel_calls(GRANITE, 16, 4, 64, 32) == {}
    # mamba: one scan per layer in the prefill, 2 matmuls per layer-step
    # when int8.
    calls = counts.kernel_calls(MAMBA, 8, 2, 320, 32)
    assert calls["ssd_scan"][2] == 48
    assert calls["quant_matmul"][2] == 2 * 48 * 32
    assert set(counts.kernel_calls(MAMBA, 16, 2, 320, 32)) == {"ssd_scan"}


def test_model_flops_dense_matches_a_hand_count():
    m = dict(family="dense", num_layers=1, d_model=4, num_heads=2,
             num_kv_heads=1, head_dim=2, d_ff=8, vocab_size=10,
             vocab_pad_multiple=16)
    # Per token: q 4x4, k 4x2, v 4x2, o 4x4, g/u 4x8 twice, d 8x4.
    per_tok = 2 * (16 + 8 + 8 + 16 + 32 + 32 + 32)
    attn = 2 * 2 * 2 * 2  # (QK^T + PV) * heads * head_dim per key
    S, new = 3, 2
    prefill = S * per_tok + attn * S * (S + 1) / 2
    decode = per_tok + attn * (S + 1)
    head = 2 * 4 * 16 * new
    assert counts.model_flops(m, 1, S, new) == prefill + decode + head
