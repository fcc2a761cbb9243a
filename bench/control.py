#!/usr/bin/env python3
"""Readings the correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds 16

One process builds the cell once, then per seed drives a short window of
the cell's own traffic (the first after the cell's warm-up replay) and
reads, for every (tenant, variant) served, the widest gap of the served
tokens under the reference (the program's reading) and, on the control
seeds, the widest gap of the tokens the reference one precision step
lower puts first (the control's reading).  Each seed's line carries the
harness's own verdict (``check.verdict``) on the program, ``correct``,
and on the control seeds on the control put in the program's place,
``control_correct``, which has to come out false.  A summary line closes:
the largest program reading and the smallest control reading per
number.  Benchmark runs never run the control.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from harness import check, chip, serve, spec  # noqa: E402
from harness.traffic import make_source  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    cell = spec.load_cell(args.workload)
    import jax
    try:
        chip.require_chips(jax, cell.chips)
    except chip.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache(jax)
    tr = cell.traffic
    session = serve.Session(cell, t_process0=T_PROCESS0)
    session.build()
    session.warm_programs()
    tenants = {t["name"]: t for t in cell.config["tenants"]}
    vocab = {n: t["model"]["vocab_size"] for n, t in tenants.items()}
    summary: dict = {}
    for i, seed in enumerate(seeds):
        warm_ms = tr["warmup_s"] * 1e3 if i == 0 else 0.0
        window_ms = args.seconds * 1e3
        source = make_source(tr, vocab, seed, warm_ms + window_ms)
        session.drive(source, warm_ms, window_ms, tr["drain_s"] * 1e3)
        groups = check.sample(session, seed, tr["check_per_variant"])
        readings = check.gap_readings(session, groups, tenants,
                                      control=seed in control)
        limits = cell.config.get("limits", {})
        line = {"seed": seed, "readings": readings,
                "correct": check.all_ok(
                    check.verdict(session, readings, limits))}
        if seed in control:
            line["control_correct"] = check.all_ok(
                check.verdict(session, readings, limits, key="control"))
        print(json.dumps(line), flush=True)
        for name, rec in readings.items():
            s = summary.setdefault(name, {"program_max": 0.0, "seeds": 0})
            s["program_max"] = max(s["program_max"], rec["value"])
            s["seeds"] += 1
            if "control" in rec:
                s["control_min"] = min(s.get("control_min", float("inf")),
                                       rec["control"])
    session.release()
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
