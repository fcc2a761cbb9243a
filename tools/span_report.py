#!/usr/bin/env python3
"""One benchmark run, as ``bench/run.py`` makes it, then a report from the
serving program's own spans and counters (``srv.tracer``).

    python3 tools/span_report.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run prints its result line as ``bench/run.py`` does.  The report
follows as one JSON object on the last line and is also written to
``chiprun_out/span_report.<cell>.<seed>.json``:

* ``twins``: the program's own readings beside the harness's outside
  ones over the window -- queue wait p95 (``engine.queue``, submit to
  dequeue) against ``engine.queue_wait_p95_ms`` (due to executor start),
  ``executor.run`` p50 against ``executor.batch_ms_p50``, compiles by
  span against both ``*.window_compiles``, ``manager.moves.*`` against
  the harness's weight moves;
* ``spans``: how many were kept and dropped, by name;
* ``end_to_end``: the cell's end-to-end metrics read from this run, so
  a traced run can be set beside a plain one of the same seed;
* with ``--trace 1``: ``clock``, the program's ``TraceAnnotation`` spans
  in the profiler's trace put on ``perf_counter`` with the offset the
  ``device.idle_in_program_share`` reader uses, against the in-memory
  spans' starts (median and worst difference); and ``gaps``, the traced
  sub-window's longest device idle gaps, each named by the loop's
  innermost program span at its middle and by what the worker threads
  were doing.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import bisect  # noqa: E402
import collections  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import numpy as np  # noqa: E402

import run  # noqa: E402
from harness import trace as T  # noqa: E402
from harness.view import View  # noqa: E402

OUT_DIR = os.path.join(ROOT, "chiprun_out")


def _pct(xs, q):
    return float(np.percentile(xs, q)) if len(xs) else None


def twins(view, tracer) -> dict:
    """The program's readings beside the harness's, over the window."""
    s = view.session
    origin_ns = s.origin * 1e9

    def in_window(sp):
        return view.in_window((sp.t0_ns - origin_ns) / 1e6)

    spans = [sp for sp in tracer.spans() if in_window(sp)]
    queue = [sp.ms for sp in spans if sp.name == "engine.queue"]
    runs = [sp.ms for sp in spans if sp.name == "executor.run"]
    by_span = collections.Counter(
        name for t, name, _, _ in tracer.compiles()
        if view.in_window((t - origin_ns) / 1e6))
    harness = collections.Counter(c[3] for c in view.compiles)
    moves = {k.split(".", 2)[2]: v for k, v in tracer.counters().items()
             if k.startswith("manager.moves.")}
    return {
        "queue_wait_p95_ms": {
            "engine.queue (submit to dequeue)": _pct(queue, 95),
            "engine.queue_wait_p95_ms (due to executor start)":
                run.reader("engine.queue_wait_p95_ms")(view)},
        "batch_ms_p50": {
            "executor.run": _pct(runs, 50),
            "executor.batch_ms_p50":
                run.reader("executor.batch_ms_p50")(view)},
        "window_compiles": {"by_span": dict(by_span),
                            "harness_by_owner": dict(harness),
                            "names": collections.Counter(
                                f"{name} {fun}"
                                for t, name, fun, _ in tracer.compiles()
                                if view.in_window((t - origin_ns) / 1e6)
                            ).most_common(20)},
        "moves": {"manager.moves (run)": moves,
                  "harness moves (run)": len(s.moves),
                  "loader.h2d in window": sum(
                      1 for sp in spans if sp.name == "loader.h2d"),
                  "harness moves in window": len(view.moves)},
    }


def offset_base(view):
    """trace ns = perf_counter ns + base (the reader's median offset)."""
    held = view.traced_batches()
    if not held:
        return None
    offset = statistics.median(s - b.t0_ms * 1e6 for b, s, _ in held)
    return offset - view.session.origin * 1e9


def clock(view, tracer, trace_dir: str, base: float) -> dict:
    """Program annotations in the trace, mapped back to perf_counter,
    against the in-memory starts of the same spans."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(files[-1])
    starts = collections.defaultdict(list)
    for sp in tracer.spans():
        starts[sp.name].append(sp.t0_ns)
    for v in starts.values():
        v.sort()
    diffs = collections.defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                mine = starts.get(e.name)
                if not mine:
                    continue
                t = e.start_ns - base
                i = bisect.bisect_left(mine, t)
                near = min((abs(mine[k] - t) for k in (i - 1, i)
                            if 0 <= k < len(mine)))
                diffs[e.name].append(near / 1e3)
    every = [d for ds in diffs.values() for d in ds]
    return {"matched": len(every),
            "median_us": _pct(every, 50), "p99_us": _pct(every, 99),
            "max_us": max(every) if every else None,
            "by_name_median_us": {k: _pct(v, 50) for k, v in diffs.items()}}


def gaps(view, tracer, base: float, n: int = 10) -> list:
    """The longest idle gaps, each with the program spans open at its
    middle: the loop's chain, innermost first, and each worker's."""
    t0, t1 = view.trace_bounds()
    spans = tracer.spans()
    by_id = {sp.sid: sp for sp in spans}
    loop = {sp.thread for sp in spans if sp.name == "engine.advance"}
    out = []
    for s, e in sorted(T.idle_gaps(view.trace["ops"], t0, t1),
                       key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2 - base
        open_ = [sp for sp in spans if sp.t0_ns <= mid < sp.t1_ns]
        inner = [sp for sp in open_ if sp.thread in loop]
        chain = []
        if inner:
            sp = min(inner, key=lambda x: x.t1_ns - x.t0_ns)
            while sp is not None:
                chain.append(sp.name)
                sp = by_id.get(sp.parent)
        workers = sorted({f"{sp.thread}:{sp.name}" for sp in open_
                          if sp.thread not in loop})
        out.append({"ms": (e - s) / 1e6,
                    "at_window_ms": (mid - view.session.origin * 1e9) / 1e6
                    - view.window[0],
                    "loop": chain or ["none"], "workers": workers})
    return out


def report(session, trace: bool) -> dict:
    view = View(session, {},
                trace=T.load(str(run.TRACE_DIR)) if trace else None,
                warm_by_rid={r.rid: r.warm
                             for r in session.srv.engine.results})
    tracer = session.srv.tracer
    spans = tracer.spans()
    out = {"workload": session.cell.name,
           "spans": {"kept": len(spans), "dropped": tracer.dropped,
                     "by_name": dict(collections.Counter(
                         sp.name for sp in spans))},
           "counters": tracer.counters(),
           "end_to_end": {m: run.reader(m)(view) for m in (
               "tokens_per_s", "latency_p50_ms", "warm_ratio", "setup_s")},
           "twins": twins(view, tracer)}
    base = offset_base(view) if trace else None
    if base is not None:
        out["clock"] = clock(view, tracer, str(run.TRACE_DIR), base)
        out["gaps"] = gaps(view, tracer, base)
    return out


def main(argv=None) -> int:
    args = run.parse(argv)
    sessions = []
    measure = run.measure

    def keep(session, *a, **kw):
        sessions.append(session)
        return measure(session, *a, **kw)

    run.measure = keep
    rc = run.main(argv)
    if rc != 0 or not sessions:
        return rc
    rep = report(sessions[0], bool(args.trace))
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"span_report.{args.workload}."
                                 f"{args.seed}.json")
    with open(path, "w") as f:
        json.dump(rep, f, indent=1, default=str)
    print(json.dumps(rep, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
