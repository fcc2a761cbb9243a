#!/usr/bin/env python3
"""Where the serving loop is held, on the chip: one run of a cell, as
``run.py`` makes it, with every thread's Python stack sampled.

    python3 bench/stalls.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A sampler thread reads the stacks of the harness's loop, the loader's
worker and the predictor-fit worker every 50 ms.  After the run's own
result it prints, as JSON lines: the run's phase times; each stretch in
which requests were submitted a second or more late (the loop was held),
with the stacks sampled in it; gaps between samples (a thread that held
the interpreter lock); the weight moves and committed loads on the
window's clock; and the window's compiles by owner and function name.
Benchmark runs never sample.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from harness import chip, serve, spec  # noqa: E402

THREADS = ("MainThread", "model-loader", "predictor-fit")
LATE_MS = 1000.0


def frames(frame, depth: int = 6) -> str:
    """The innermost ``depth`` frames, innermost first."""
    out = []
    while frame is not None and len(out) < depth:
        code = frame.f_code
        out.append(f"{code.co_name} ({os.path.basename(code.co_filename)}"
                   f":{frame.f_lineno})")
        frame = frame.f_back
    return " < ".join(out)


class Sampler(threading.Thread):
    def __init__(self, session, every_s: float = 0.05):
        super().__init__(name="stack-sampler", daemon=True)
        self.session, self.every_s = session, every_s
        self.samples = []  # (t_ms, {thread: stack})
        self.stop = threading.Event()

    def run(self):
        while not self.stop.is_set():
            if self.session.origin is not None:
                cur = sys._current_frames()
                self.samples.append((self.session.now_ms(), {
                    th.name: frames(cur[th.ident])
                    for th in threading.enumerate()
                    if th.ident in cur and th.name.startswith(THREADS)}))
            self.stop.wait(self.every_s)


def stretches(session, w0: float):
    """Merged ``[due, submit]`` intervals of requests submitted late."""
    ivals = sorted((r.due_ms, r.submit_ms) for r in session.requests
                   if r.submit_ms - r.due_ms >= LATE_MS)
    merged = []
    for s, e in ivals:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def report(session, sampler, phases: dict) -> None:
    w0, w1 = session._window
    emit = lambda d: print(json.dumps(d), flush=True)  # noqa: E731
    emit({"phases_s": phases})
    for s, e in stretches(session, w0):
        held = [st for t, st in sampler.samples if s <= t <= e]
        top = {}
        for name in THREADS:
            c = collections.Counter(
                v for st in held for k, v in st.items()
                if k.startswith(name))
            top[name] = c.most_common(3)
        emit({"held_ms": [s - w0, e - w0], "samples": len(held),
              "stacks": top})
    ts = [t for t, _ in sampler.samples]
    gaps = sorted(((b - a, a - w0) for a, b in zip(ts, ts[1:])
                   if b - a > 250.0), reverse=True)[:10]
    emit({"sample_gaps_ms": gaps})
    emit({"moves": [[m.app, m.from_bits, m.to_bits, m.nbytes,
                     m.t0_ms - w0, m.t1_ms - w0] for m in session.moves]})
    loader = session.srv.loader
    emit({"loads": [[r.app, r.bits, r.t_enqueue_ms - w0,
                     r.t_ready_ms - w0, r.demand]
                    for r in getattr(loader, "history", [])]})
    c = collections.Counter((owner, fun) for t, fun, _, owner
                            in session.compiles if w0 <= t < w1)
    secs = collections.defaultdict(float)
    for t, fun, dt, owner in session.compiles:
        if w0 <= t < w1:
            secs[owner] += dt
    emit({"window_compiles": [[o, f, n] for (o, f), n in c.most_common()],
          "compile_s": dict(secs)})


def main(argv=None) -> int:
    args = run.parse(argv)
    cell = spec.load_cell(args.workload)
    import jax
    try:
        device = chip.require_chips(jax, cell.chips)
    except chip.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache(jax)
    peaks = chip.peaks(device["kind"])
    session = serve.Session(cell, trace=bool(args.trace),
                            t_process0=T_PROCESS0)
    sampler = Sampler(session)
    sampler.start()
    run.serve_window(session, cell, args.seed, args.seconds,
                     bool(args.trace))
    t_served = time.perf_counter()
    sampler.stop.set()
    sampler.join()
    out = run.measure(session, cell, args.seed, bool(args.trace), peaks)
    out["device"] = {**device, **out["device"]}
    t_end = time.perf_counter()
    report(session, sampler, {
        "setup": session.t_window0 - T_PROCESS0,
        "window_and_drain": t_served - session.t_window0,
        "check_and_metrics": t_end - t_served,
        "total": t_end - T_PROCESS0})
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
