"""The harness end to end on the CPU at the program's reduced sizes: only
the look for a chip is skipped.  A sound run is correct; each fault a
served cell can have, planted in the timed path, makes it not correct;
the lower-precision control fails the limit; a stall inside the window
shows in the end-to-end metrics; and without a chip the benchmark prints
nothing."""
import numpy as np
import pytest

import benchtest_support as B
import run
from harness import check
from harness.view import View

SEED = 2 ** 33 + 21


@pytest.fixture(scope="module")
def cell():
    return B.tiny_cell()


@pytest.fixture(scope="module")
def baseline(cell):
    return B.run_tiny(cell, SEED)


def _patched_generate(monkeypatch, fn):
    from repro.serving import server as S
    real = S._generate_tokens

    def broken(cfg, params, prompts, **kw):
        return fn(np.array(real(cfg, params, prompts, **kw)))
    monkeypatch.setattr(S, "_generate_tokens", broken)


def test_sound_run_is_correct(baseline):
    out, _ = baseline
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 10
    names = set(out["checks"])
    assert {"unanswered", "budget_overrun"} <= names
    assert any(n.startswith("gap.") for n in names)
    assert set(out["metrics"]) == {"latency_p50_ms", "warm_ratio",
                                   "tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_altered_token_is_not_correct(cell, monkeypatch):
    def alter(tokens):
        tokens[:, tokens.shape[1] // 2] = (tokens[:, tokens.shape[1] // 2]
                                           + 1) % 100
        return tokens
    s = B.start(cell)
    _patched_generate(monkeypatch, alter)
    B.drive(s, cell, SEED)
    out = run.measure(s, cell, SEED, False, B.CPU_PEAKS)
    assert not out["correct"]


def test_half_batch_left_out_is_not_correct(cell, monkeypatch):
    def drop_half(tokens):
        tokens[tokens.shape[0] // 2:] = 0
        return tokens
    s = B.start(cell)
    _patched_generate(monkeypatch, drop_half)
    B.drive(s, cell, SEED)
    out = run.measure(s, cell, SEED, False, B.CPU_PEAKS)
    assert not out["correct"]


def _control_verdict(s, cell):
    """The harness's verdict on the program and on the control in its
    place, from one set of readings."""
    groups = check.sample(s, SEED, cell.traffic["check_per_variant"])
    readings = check.gap_readings(
        s, groups, {t["name"]: t for t in cell.config["tenants"]},
        control=True)
    limits = cell.config["limits"]
    return (readings, check.verdict(s, readings, limits),
            check.verdict(s, readings, limits, key="control"))


def test_lower_precision_control_fails_the_limits(baseline, cell):
    """The reference one precision step lower (fp8 for bf16, int4 for
    int8) in the program's place comes out not correct, missing every
    gap limit."""
    _, s = baseline
    readings, program, control = _control_verdict(s, cell)
    assert readings
    assert check.all_ok(program)
    assert not check.all_ok(control)
    for c in control:
        assert c["ok"] != c["name"].startswith("gap."), c


def test_int8_path_is_correct_and_its_control_fails():
    cell = B.tiny_cell("tiny.int8.json")
    out, s = B.run_tiny(cell, SEED)
    assert out["correct"], out["checks"]
    assert any(k.endswith(".int8") for k in out["checks"])
    _, program, control = _control_verdict(s, cell)
    assert check.all_ok(program)
    assert not check.all_ok(control)
    for c in control:
        assert c["ok"] != c["name"].startswith("gap."), c


def test_stall_in_window_moves_tail_and_throughput(baseline, cell,
                                                   monkeypatch):
    """One executor call that stalls for a second late in the window
    delays every request due behind it."""
    import time
    out0, s0 = baseline
    stall_at = s0._window[0] + 1800.0
    state = {"done": False}
    s = B.start(cell)

    def stall(tokens):
        if not state["done"] and s.now_ms() >= stall_at:
            state["done"] = True
            time.sleep(1.0)
        return tokens
    _patched_generate(monkeypatch, stall)
    B.drive(s, cell, SEED)
    out = run.measure(s, cell, SEED, False, B.CPU_PEAKS)
    assert state["done"]
    m0, m1 = out0["metrics"], out["metrics"]
    p95 = run.reader("client.latency_p95_ms")
    assert p95(View(s, B.CPU_PEAKS)) > p95(View(s0, B.CPU_PEAKS)) + 300
    assert m1["tokens_per_s"]["value"] < m0["tokens_per_s"]["value"]


def test_no_accelerator_prints_no_result(capsys):
    rc = run.main(["--workload", "mamba2-granite.roomy.closed",
                   "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_traced_sub_window_stops_off_the_loop(cell, tmp_path):
    """The profiler stops on a thread of its own at the sub-window's end;
    the run waits for it and the trace holds the harness's spans."""
    from harness import trace as T
    s = B.start(cell, trace=True)
    tr = cell.traffic
    warm_ms, window_ms = tr["warmup_s"] * 1e3, 3000.0
    source = B.make_source(tr, {t["name"]: t["model"]["vocab_size"]
                                for t in cell.config["tenants"]},
                           SEED, warm_ms + window_ms)
    s.drive(source, warm_ms, window_ms, tr["drain_s"] * 1e3,
            profile=(warm_ms + 1000.0, warm_ms + window_ms, str(tmp_path)))
    t0, t1 = s.trace_span_ms
    assert t1 - t0 >= 1900.0
    assert s._stopper is None
    host = T.load(str(tmp_path))["host"]
    assert any(name.startswith("execute ") for name, _, _ in host)
