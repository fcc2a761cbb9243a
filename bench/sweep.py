#!/usr/bin/env python3
"""Find the highest rate an open-loop mix sustains, once, on the chip.

    python3 bench/sweep.py --workload <cell> --seed 1 --rates 2,4,6 --seconds 24

One process builds the cell, replays its warm-up once, then drives one
window per rate in turn (each rate's arrivals follow the previous
window's drain).  Per rate it prints the latency percentiles, the
throughput, and the backlog -- requests due but not yet answered -- at
the end of every phase of the window: a backlog that climbs from phase to
phase is a queue that grows.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from harness import chip, serve, spec  # noqa: E402
from harness.traffic import make_source  # noqa: E402
from harness.view import View  # noqa: E402


def backlog(session, t_ms: float) -> int:
    return sum(1 for r in session.requests if r.due_ms <= t_ms and
               not (r.done_ms <= t_ms))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    try:
        chip.require_chips(jax, cell.chips)
    except chip.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache(jax)
    session = serve.Session(cell, t_process0=T_PROCESS0)
    session.build()
    session.warm_programs()
    vocab = {t["name"]: t["model"]["vocab_size"]
             for t in cell.config["tenants"]}
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(cell.traffic, rate_rps=rate)
        warm_ms = tr["warmup_s"] * 1e3 if i == 0 else 0.0
        window_ms = args.seconds * 1e3
        source = make_source(tr, vocab, args.seed, warm_ms + window_ms)
        session.drive(source, warm_ms, window_ms, tr["drain_s"] * 1e3)
        view = View(session, {}, warm_by_rid={
            r.rid: r.warm for r in session.srv.engine.results})
        lat = view.latencies_ms()
        w0, w1 = session._window
        phase_ms = tr.get("phase_s", args.seconds) * 1e3
        ends = np.arange(w0 + phase_ms, w1 + 1, phase_ms)
        done = [r.done_ms for r in view.requests if not r.failed]
        print(json.dumps({
            "rate_rps": rate, "requests": len(lat),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "tokens_per_s": run.reader("tokens_per_s")(view),
            "warm_ratio": run.reader("warm_ratio")(view),
            "batch_occupancy": run.reader("engine.batch_occupancy")(view),
            "backlog_at_phase_ends": [backlog(session, t) for t in ends],
            "drain_ms": max(done) - w1 if done else None,
            "moves": [dataclasses.astuple(m)[:3] for m in view.moves],
        }), flush=True)
    session.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
