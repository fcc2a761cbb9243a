"""The readers of the serving program's own spans: planted spans with a
known clock offset on the recorded v5e trace, hand-computed values, and
on the CPU the program's annotations on the profiler's clock."""
import bisect
import collections
import glob
import os
import statistics
import sys
import threading

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import benchtest_support as B  # noqa: E402
import run  # noqa: E402
from harness import trace as T  # noqa: E402
from harness.serve import BatchRec  # noqa: E402
from harness.view import View  # noqa: E402

from repro.serving.tracing import Tracer  # noqa: E402

ORIGIN_NS = 10 ** 12  # perf_counter at traffic time 0
WINDOW = (0.0, 40000.0)  # harness ms
# trace ns = harness ms * 1e6 + OFFSET_NS (the median batch's offset)
OFFSET_NS = -4 * 10 ** 9


class _Srv:
    def __init__(self, tracer):
        self.tracer = tracer


class _Session:
    def __init__(self, tracer=None, batches=()):
        self.srv = _Srv(tracer) if tracer is not None else object()
        self.origin = ORIGIN_NS / 1e9
        self._window = WINDOW
        self.batches = list(batches)
        self.cell = None


def _ns(ms):
    """perf_counter ns of harness ms."""
    return ORIGIN_NS + round(ms * 1e6)


def _from_trace(t):
    """perf_counter ns of a trace timestamp."""
    return t - OFFSET_NS + ORIGIN_NS


@pytest.fixture
def tracer():
    tr = Tracer()
    yield tr
    tr.close()


def _on_thread(name, fn):
    th = threading.Thread(target=fn, name=name)
    th.start()
    th.join(timeout=10)
    assert not th.is_alive()


def test_predictor_time_per_window_second(tracer):
    """The loop thread's predictor spans that start in the window, nested
    ones once: (10 + 20 + 30) ms over 40 s."""
    rec = tracer.record
    rec("engine.advance", _ns(100), _ns(200))
    rec("predictor.predict", _ns(1000), _ns(1010))
    rec("predictor.fit_submit", _ns(2000), _ns(2020))
    rec("predictor.predict", _ns(2005), _ns(2010))  # inside the submit
    rec("predictor.predict", _ns(39990), _ns(40020))  # starts inside
    rec("predictor.predict", _ns(-50), _ns(-10))  # before the window
    rec("predictor.predict", _ns(40001), _ns(40100))  # after it
    rec("engine.maintain", _ns(5000), _ns(5100))  # not a predictor span
    _on_thread("predictor-fit_0", lambda: rec("predictor.fit", _ns(3000),
                                              _ns(3500)))
    read = run.reader("manager.predictor_ms_per_s")
    assert read(View(_Session(tracer), {})) == pytest.approx(60.0 / 40.0)


def test_copy_rate_over_the_window_moves(tracer):
    """(1 + 3) GB copied in (0.5 + 1.5) s; a copy before the window does
    not count, and a window without a copy reads nothing."""
    read = run.reader("loader.h2d_gbps")
    tracer.record("loader.h2d", _ns(-200), _ns(-100), bytes=5 * 10 ** 9)
    assert read(View(_Session(tracer), {})) is None
    tracer.record("loader.h2d", _ns(1000), _ns(1500), bytes=10 ** 9)
    tracer.record("loader.h2d", _ns(8000), _ns(9500), bytes=3 * 10 ** 9)
    assert read(View(_Session(tracer), {})) == pytest.approx(2.0)


@pytest.fixture(scope="module")
def recorded():
    """The recorded excerpt (see test_bench_trace.py) with three batches
    the trace holds whole planted among the harness's spans."""
    import json
    with open(os.path.join(HERE, "fixtures", "trace_v5e_excerpt.json")) as f:
        d = json.load(f)
    trace = {k: [tuple(e) for e in d[k]] for k in ("ops", "modules", "host")}
    planted = [("execute mamba2-780m #0", 151_000_000, 1_000_000),
               ("execute granite-3-2b #1", 155_000_000, 2_000_000),
               ("execute mamba2-780m #2", 160_000_000, 500_000)]
    # Host stamps a few microseconds off either way; the median batch
    # (#1) gives the offset exactly.
    t0_ms = [(s - OFFSET_NS) / 1e6 + jitter for (_, s, _), jitter
             in zip(planted, (0.003, 0.0, -0.005))]
    batches = [BatchRec(name.split()[1], 16, t, t + d / 1e6, [i],
                        np.zeros((1, 64), np.int32),
                        np.zeros((1, 32), np.int32), 32)
               for i, ((name, _, d), t) in enumerate(zip(planted, t0_ms))]
    return trace, planted, batches


def test_idle_while_the_loop_works(recorded, tracer):
    """Device idle inside the loop's engine.advance spans, on the trace's
    clock: the device is busy from 150372099 to 164703277 ns (7 ns of
    gaps near its start) inside harness spans from 100026899 to
    294687287 ns."""
    trace, planted, batches = recorded
    trace = dict(trace, host=trace["host"] + planted)
    spans = [(100_100_000, 150_000_000),  # all idle
             (160_000_000, 170_000_000),  # idle from 164703277
             (290_000_000, 300_000_000)]  # idle, cut at 294687287
    for s, e in spans:
        tracer.record("engine.advance", _from_trace(s), _from_trace(e))
    tracer.record("engine.maintain", _from_trace(101_000_000),
                  _from_trace(120_000_000))  # not the loop's outer span
    v = View(_Session(tracer, batches), {}, trace=trace)
    assert len(v.traced_batches()) == 3
    t0, t1 = v.trace_bounds()
    assert (t0, t1) == (100_026_899, 294_687_287)
    idle = (150_000_000 - 100_100_000) + (170_000_000 - 164_703_277) \
        + (294_687_287 - 290_000_000)
    read = run.reader("device.idle_in_program_share")
    assert read(v) == pytest.approx(100.0 * idle / (t1 - t0), rel=1e-12)
    # A brute-force count on a 1 us grid agrees.
    grid = np.arange(t0, t1, 1000) + 500
    busy = np.zeros(len(grid), bool)
    for _, s, d in trace["ops"]:
        busy[(grid >= s) & (grid < s + d)] = True
    loop = np.zeros(len(grid), bool)
    for s, e in spans:
        loop[(grid >= s) & (grid < e)] = True
    share = 100.0 * np.sum(loop & ~busy) / len(grid)
    assert read(v) == pytest.approx(share, abs=0.01)
    assert read(v) <= run.reader("device.idle_share")(v)


def test_readers_read_nothing_where_there_is_nothing(recorded, tracer):
    trace, planted, batches = recorded
    tracer.record("engine.advance", _from_trace(100_100_000),
                  _from_trace(150_000_000))
    idle = run.reader("device.idle_in_program_share")
    # The excerpt alone holds no whole batch: no offset, no reading.
    assert idle(View(_Session(tracer, batches), {}, trace=trace)) is None
    assert idle(View(_Session(tracer, batches), {})) is None
    # A program that records no spans (an older tree, or the "counters"
    # level) gives each reader nothing, and none raises.
    whole = dict(trace, host=trace["host"] + planted)
    for name in ("manager.predictor_ms_per_s", "loader.h2d_gbps",
                 "device.idle_in_program_share"):
        for session in (_Session(None, batches),
                        _Session(Tracer(spans=False), batches)):
            assert run.reader(name)(View(session, {}, trace=whole)) is None


def test_program_spans_share_the_profilers_clock(tmp_path):
    """A traced CPU run: the program's annotations in the profiler's
    trace, mapped to perf_counter with the offset of the harness's
    ``execute`` annotations, start where the in-memory spans start."""
    from jax.profiler import ProfileData
    cell = B.tiny_cell()
    s = B.start(cell, trace=True)
    tr = cell.traffic
    warm_ms, window_ms = tr["warmup_s"] * 1e3, 3000.0
    source = B.make_source(tr, {t["name"]: t["model"]["vocab_size"]
                                for t in cell.config["tenants"]},
                           2 ** 33 + 21, warm_ms + window_ms)
    s.drive(source, warm_ms, window_ms, tr["drain_s"] * 1e3,
            profile=(warm_ms + 500.0, warm_ms + window_ms, str(tmp_path)))
    s.release()
    tracer = s.srv.tracer
    offs = [st - s.batches[int(n.rsplit("#", 1)[1])].t0_ms * 1e6
            for n, st, _ in T.load(str(tmp_path))["host"]
            if n.startswith("execute ")]
    assert len(offs) >= 5
    base = statistics.median(offs) - s.origin * 1e9
    starts = collections.defaultdict(list)
    for sp in tracer.spans():
        starts[sp.name].append(sp.t0_ns)
    for v in starts.values():
        v.sort()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    diffs, names = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                mine = starts.get(e.name)
                if not mine:
                    continue
                t = e.start_ns - base
                i = bisect.bisect_left(mine, t)
                diffs.append(min(abs(mine[k] - t) for k in (i - 1, i)
                                 if 0 <= k < len(mine)))
                names.add(e.name)
    assert {"engine.advance", "manager.admit", "executor.run",
            "executor.fetch"} <= names
    assert statistics.median(diffs) < 50_000  # ns
    # The loop's predictor time reads a number on this run.
    v = View(s, B.CPU_PEAKS)
    assert run.reader("manager.predictor_ms_per_s")(v) > 0
