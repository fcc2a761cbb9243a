#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
The run builds the configuration through ``EdgeServer.build``, compiles
and runs every program its traffic can produce, replays the mix until the
arrival predictors have history, then measures ``--seconds`` of wall
clock.  After the window it frees the program and compares what the
window served with a plain reference (``harness/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (``--trace 1``) and last ``checks``: each number compared
beside its limit, which also close standard error.  Without an
accelerator, or with fewer chips than the cell asks for, the run exits
with status 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import check, chip, serve, spec  # noqa: E402
from harness import trace as T  # noqa: E402
from harness.traffic import make_source  # noqa: E402
from harness.view import View  # noqa: E402

TRACE_DIR = spec.ROOT / "bench_out" / "trace"
UNMET_MS = 1e9  # a latency percentile that falls on a failed request


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def reader(name: str):
    path = spec.BENCH_DIR / "metrics" / f"{name}.py"
    sp = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def enable_compile_cache(jax) -> None:
    """JAX's persistent cache, at a fixed path inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept, so a
    second run of a cell compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def serve_window(session, cell, seed: int, seconds: float, trace: bool):
    """Warm up, replay, then drive the window (and trace its sub-window)."""
    tr = cell.traffic
    session.build()
    session.warm_programs()
    warm_ms, window_ms = tr["warmup_s"] * 1e3, seconds * 1e3
    vocab = {t["name"]: t["model"]["vocab_size"]
             for t in cell.config["tenants"]}
    source = make_source(tr, vocab, seed, warm_ms + window_ms)
    profile = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        t0, length = tr["trace_window_s"]
        profile = (warm_ms + t0 * 1e3, warm_ms + (t0 + length) * 1e3,
                   str(TRACE_DIR))
    session.drive(source, warm_ms, window_ms, tr["drain_s"] * 1e3, profile)


def measure(session, cell, seed: int, trace: bool, peaks: dict) -> dict:
    """Everything after the window: peak memory, the correctness check on
    a freed chip, then the metrics."""
    import numpy as np
    peak = session.memory_peak_bytes()
    warm = {r.rid: r.warm for r in session.srv.engine.results}
    session.release()
    checks = check.compare(session, seed, cell.config.get("limits", {}),
                           cell.traffic["check_per_variant"])
    view = View(session, peaks, warm_by_rid=warm,
                trace=T.load(str(TRACE_DIR)) if trace else None)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m.name)(view)
        if value is None:
            print(f"metric {m.name}: nothing to read in this run",
                  file=sys.stderr)
            continue
        if not math.isfinite(value):
            print(f"metric {m.name}: falls on a failed request",
                  file=sys.stderr)
            value = UNMET_MS
        metrics[m.name] = {"value": value, "unit": m.unit}
    reqs = view.requests
    late = np.asarray(session.lateness_ms)
    print(f"generator lateness (submit - due) over {len(late)} requests: "
          f"p50 {np.percentile(late, 50):.3f} ms, p99 "
          f"{np.percentile(late, 99):.3f} ms", file=sys.stderr)
    out = {"correct": check.all_ok(checks),
           "attempted": len(reqs),
           "failed": sum(1 for r in reqs if not r.resolved or r.failed),
           "metrics": metrics,
           "device": {"memory_peak_bytes": peak}}
    if trace:
        t0, t1 = view.trace_bounds()
        ops = view.trace["ops"]
        out["device"].update(busy_s=T.busy_ns(ops, t0, t1) / 1e9,
                             window_s=(t1 - t0) / 1e9)
        out["breakdown"] = {
            "device_ops": T.top_ops(ops, t0, t1),
            "idle_gaps": T.named_gaps(ops, view.trace["host"], t0, t1)}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    return out


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    import jax
    try:
        device = chip.require_chips(jax, cell.chips)
    except chip.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(jax)
    peaks = chip.peaks(device["kind"])
    session = serve.Session(cell, trace=bool(args.trace),
                            t_process0=T_PROCESS0)
    serve_window(session, cell, args.seed, args.seconds, bool(args.trace))
    out = measure(session, cell, args.seed, bool(args.trace), peaks)
    out["device"] = {**device, **out["device"]}
    checks = out.pop("checks")
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
