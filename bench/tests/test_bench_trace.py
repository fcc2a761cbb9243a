"""The reduction from a profiler trace to device numbers."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace as T  # noqa: E402

# name, start_ns, duration_ns: two overlapping ops, a nested op, a gap.
OPS = [("%while.1 = (s32[]) while(...)", 100, 400),
       ("%fusion.2 = bf16[8] fusion(...)", 120, 100),
       ("%closed_call.3 = f32[8,8] custom-call(...)", 250, 200),
       ("%fusion.4 = bf16[8] fusion(...)", 900, 50)]
HOST = [("cluster_advance", 0, 1000), ("execute a #0", 90, 420),
        ("idle", 600, 250)]


def test_busy_is_the_union_of_intervals():
    assert T.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    # [100, 500) from the while and its children, [900, 950).
    assert T.busy_ns(OPS, 0, 1000) == 400 + 50
    assert T.busy_ns(OPS, 200, 300) == 100  # clipped to the window


def test_idle_gaps_and_their_names():
    assert T.idle_gaps(OPS, 0, 1000) == [(0, 100), (500, 900), (950, 1000)]
    gaps = T.named_gaps(OPS, HOST, 0, 1000)
    assert gaps[0] == ["idle", 400e-9]  # the longest, mid 700: harness idle
    assert ["cluster_advance", 100e-9] in gaps


def test_kernel_time_by_name():
    evs = T.within(OPS, 90, 510, "custom-call")
    assert [e[0] for e in evs] == ["%closed_call.3 = f32[8,8] custom-call(...)"]
    assert sum(d for _, _, d in evs) == 200


def test_top_ops_by_exclusive_time():
    top = dict(T.top_ops(OPS, 0, 1000))
    # The while's 400 ns less its two children's 300 ns.
    assert top == {"closed_call.3 custom-call f32[8,8]": 200e-9,
                   "while.1 while (s32[])": 100e-9,
                   "fusion.2 fusion bf16[8]": 100e-9,
                   "fusion.4 fusion bf16[8]": 50e-9}


def test_short_name_drops_the_layout():
    name = ("%closed_call.146 = f32[8,8192]{1,0:T(8,128)S(1)} custom-call("
            "f32[8,2048]{1,0:T(8,128)S(1)} %pad.169)")
    assert T.short_name(name) == "closed_call.146 custom-call f32[8,8192]"


RECORDED = os.path.join(HERE, "fixtures", "trace_v5e_excerpt.json")


@pytest.fixture(scope="module")
def recorded():
    """20 ms of a traced run of the contended cell on a TPU v5 lite:
    device operations (names cut to 300 characters), program runs and
    the harness's host spans, in nanoseconds."""
    with open(RECORDED) as f:
        d = json.load(f)
    return {k: [tuple(e) for e in d[k]] for k in ("ops", "modules", "host")}


def test_recorded_busy_matches_a_brute_force_count(recorded):
    ops = recorded["ops"]
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    step = 1000  # 1 us cells
    mask = np.zeros((t1 - t0) // step + 1, bool)
    for _, s, d in ops:
        mask[(s - t0) // step:(s + d - t0 + step - 1) // step] = True
    busy = T.busy_ns(ops, t0, t1)
    assert 0 < busy <= t1 - t0
    assert abs(busy - mask.sum() * step) <= 0.02 * busy
    gaps = T.idle_gaps(ops, t0, t1)
    assert sum(e - s for s, e in gaps) + busy == t1 - t0


def _reader_module(name):
    import importlib.util
    path = os.path.join(os.path.dirname(HERE), "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_recorded_kernels_are_found_by_their_readers(recorded):
    """The excerpt is an int8 mamba2-780m prefill of 320 tokens: 48
    layers' worth of the int8 matmul (a custom call on s8 weights) and
    the SSD scan (the custom call returning a tuple), all inside the
    serving program's run."""
    qmm = _reader_module("quant_matmul_roofline").match
    ssd = _reader_module("ssd_scan_roofline").match
    ops = recorded["ops"]
    q = [e for e in ops if qmm(e[0])]
    s = [e for e in ops if ssd(e[0])]
    assert len(q) == 33 and len(s) == 16
    assert not any(qmm(e[0]) and ssd(e[0]) for e in ops)
    assert all("(f32[1,48,2,256,64]" in e[0] for e in s)
    run = [m for m in recorded["modules"] if "_generate_tokens" in m[0]][0]
    assert all(run[1] <= e[1] and e[1] + e[2] <= run[1] + run[2]
               for e in q + s)
    assert T.within(ops, 0, 2 ** 62, qmm) == q


class _Session:
    def __init__(self, batches):
        self.batches = batches
        self.cell = None


def _view(batches, trace):
    from harness.view import View
    return View(_Session(batches), {"bf16_flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9}, trace=trace)


MAMBA = dict(family="ssm", num_layers=48, d_model=1536, vocab_size=50280,
             vocab_pad_multiple=256, ssm_state=128, ssm_head_dim=64,
             ssm_expand=2, ssm_chunk=256, ssm_conv_width=4, ssm_ngroups=1)


def test_kernel_share_counts_only_whole_batches(recorded, monkeypatch):
    """The excerpt holds 5 ms of a 145 ms batch: the share of a batch the
    trace cuts would overstate, so it is not read at all."""
    from harness import view as V
    from harness.serve import BatchRec
    b = BatchRec("mamba2-780m", 8, 0.0, 145.0, [0],
                 np.zeros((1, 320), np.int32), np.zeros((1, 32), np.int32),
                 32)
    batches = [None] * 60 + [b]
    monkeypatch.setattr(V.View, "models", property(lambda self: {
        "mamba2-780m": MAMBA}))
    v = _view(batches, recorded)
    assert v.traced_batches() == []
    assert v.kernel_share("ssd_scan",
                          _reader_module("ssd_scan_roofline").match) is None
    # The same batch with its whole span traced: the least time from
    # shapes over the measured time, in percent.
    from harness import counts
    ops, nbytes, calls = counts.kernel_calls(MAMBA, 8, 1, 320, 32)["ssd_scan"]
    least = max(ops / 197e12, nbytes / 819e9)
    trace = {"ops": [("%a = (f32[1]) custom-call(f32[48])", 1000, 10 ** 6),
                     ("%b = f32[1] fusion(f32[1])", 2 * 10 ** 6, 10 ** 6)],
             "modules": [], "host": [("execute mamba2-780m #60", 500,
                                      3 * 10 ** 6)]}
    trace["ops"].append(("%c = f32[1] fusion(f32[1])", 0, 4 * 10 ** 6))
    trace["ops"].sort(key=lambda e: e[1])
    v = _view(batches, trace)
    share = v.kernel_share("ssd_scan",
                           _reader_module("ssd_scan_roofline").match)
    assert share == pytest.approx(100 * least / 1e-3)
    assert calls == 48


def test_recorded_self_times_never_exceed_the_window(recorded):
    ops = recorded["ops"]
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    total = sum(d for _, _, d in T.self_times(ops))
    assert total <= T.busy_ns(ops, t0, t1) * 1.001
