"""The serving program's spans and counters (``repro.serving.tracing``),
on a sim-executor server on the CPU."""
import collections
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import EdgeServer, poisson_trace
from repro.serving.api import BatchingSpec, ServingConfig, TenantSpec
from repro.serving.tracing import RESERVED, Tracer

TENANTS = ("tinyllama-1.1b", "mamba2-780m", "gemma2-2b")


def _run(audit="full", seed=1):
    """A contended three-tenant sim run in which one admission upgrades
    its tenant through the loader (seed 1)."""
    srv = EdgeServer.build(ServingConfig(
        tenants=tuple(TenantSpec(n) for n in TENANTS), policy="iws-bfe",
        executor="sim", delta_ms=750.0,
        batching=BatchingSpec(max_batch=4), kv_headroom_shape=(2, 12),
        audit=audit))
    cfgs = {n: get_config(n, reduced=True) for n in TENANTS}
    trace, _ = poisson_trace(cfgs, requests_per_app=20, mean_iat_ms=400.0,
                             seed=seed)
    srv.engine.run_trace(trace)
    srv.close()
    return srv, trace


@pytest.fixture(scope="module")
def traced():
    return _run()


def _chain(span, by_id):
    out = []
    while span is not None:
        out.append(span.name)
        span = by_id.get(span.parent)
    return out


def test_parent_links_cross_to_the_loader_worker(traced):
    """engine.advance -> manager.admit -> manager.enact on the loop, and
    the staged move on the loader's worker links back to the enact."""
    srv, _ = traced
    spans = srv.tracer.spans()
    by_id = {s.sid: s for s in spans}
    staged = [s for s in spans if s.name == "loader.stage"
              and _chain(s, by_id)[1:] == ["manager.enact", "manager.admit",
                                           "engine.advance"]]
    assert staged
    for s in staged:
        enact = by_id[s.parent]
        assert s.thread.startswith("model-loader")
        assert enact.thread == by_id[enact.parent].thread == "MainThread"
        assert enact.t0_ns <= s.t0_ns and s.t1_ns <= enact.t1_ns
        assert s.attrs["tenant"] == enact.attrs["tenant"]
    # Every span's parent was kept, and opened no later than its child
    # (a queue wait starts at submit, before the pass that dequeues it).
    for s in spans:
        if s.parent and s.name != "engine.queue":
            assert by_id[s.parent].t0_ns <= s.t0_ns


def test_queue_spans_carry_each_request_once(traced):
    srv, trace = traced
    queue = [s for s in srv.tracer.spans() if s.name == "engine.queue"]
    rids = collections.Counter(s.attrs["rid"] for s in queue)
    assert sorted(rids) == sorted(r.rid for r in trace)
    assert set(rids.values()) == {1}
    assert all(s.t0_ns <= s.t1_ns for s in queue)
    executed = [s for s in srv.tracer.spans() if s.name == "executor.run"]
    assert sum(len(s.attrs["rids"]) for s in executed) == len(trace)


def test_counters_match_the_engine(traced):
    srv, trace = traced
    c = srv.tracer.counters()
    assert c["engine.batches"] == len(
        [s for s in srv.tracer.spans() if s.name == "executor.run"])
    assert c["engine.batch_rows"] == len(trace)
    moves = {k: v for k, v in c.items() if k.startswith("manager.moves.")}
    assert moves["manager.moves.load"] == len(TENANTS)
    assert moves.get("manager.moves.upgrade", 0) >= 1
    assert srv.tracer.dropped == 0


def test_counters_level_records_no_span():
    srv, trace = _run(audit="counters")
    assert srv.tracer.spans() == []
    c = srv.tracer.counters()
    assert c["engine.batch_rows"] == len(trace)
    assert c["manager.moves.load"] == len(TENANTS)


def test_results_do_not_depend_on_the_recorder(traced):
    """Spans observe: the same trace gives the same results and audit
    trail with spans recorded or not."""
    full, _ = traced
    counters, _ = _run(audit="counters")
    a = [(r.rid, r.start_ms, r.done_ms, r.warm, r.bits)
         for r in full.engine.results]
    b = [(r.rid, r.start_ms, r.done_ms, r.warm, r.bits)
         for r in counters.engine.results]
    assert a == b
    assert full.engine.events_emitted == counters.engine.events_emitted


def test_compile_is_booked_to_the_innermost_span():
    tracer = Tracer()
    try:
        k = np.float32(np.random.default_rng().random())
        with tracer.span("predictor.predict"):
            with tracer.span("test.compile"):
                jax.jit(lambda x: x * k + 1)(jnp.ones(7)).block_until_ready()
        c = tracer.counters()
        assert c["compiles.test.compile"] >= 1
        assert c["compile_s.test.compile"] > 0
        assert "compiles.predictor.predict" not in c
        assert any(name == "test.compile" and "lambda" in fun
                   for _, name, fun, _ in tracer.compiles())
        jax.jit(lambda x: x * k + 2)(jnp.ones(5)).block_until_ready()
        assert tracer.counters()["compiles.none"] >= 1
    finally:
        tracer.close()
    n = len(tracer.compiles())
    jax.jit(lambda x: x * k + 3)(jnp.ones(3)).block_until_ready()
    assert len(tracer.compiles()) == n  # closed: books nothing more


def test_ring_counts_what_it_drops():
    tracer = Tracer(capacity=8)
    try:
        for i in range(13):
            with tracer.span("engine.advance", i=i):
                pass
        tracer.record("engine.queue", 1, 2, rid=0)
        spans = tracer.spans()
        assert len(spans) == 8 and tracer.dropped == 6
        assert spans[-1].name == "engine.queue"
        assert [s.attrs["i"] for s in spans[:-1]] == list(range(6, 13))
    finally:
        tracer.close()


@pytest.mark.parametrize("name", RESERVED + ("execute mamba #3",))
def test_reserved_prefix_raises(name):
    tracer = Tracer()
    try:
        with pytest.raises(ValueError, match="reserves"):
            tracer.span(name)
        with pytest.raises(ValueError, match="reserves"):
            tracer.record(name, 0, 1)
        assert tracer.spans() == []
    finally:
        tracer.close()


def test_spans_from_threads_keep_their_own_parents():
    """Each thread nests its own spans; a worker links to the span that
    handed it the work only when told."""
    tracer = Tracer()
    try:
        with tracer.span("engine.advance") as outer:
            handed = tracer.current()

            def work():
                with tracer.span("loader.stage", parent=handed):
                    with tracer.span("loader.inner"):
                        pass
                with tracer.span("predictor.fit"):
                    pass
            th = threading.Thread(target=work, name="model-loader_9")
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
        by_name = {s.name: s for s in tracer.spans()}
        assert by_name["loader.stage"].parent == outer.sid
        assert by_name["loader.inner"].parent == by_name["loader.stage"].sid
        assert by_name["predictor.fit"].parent == 0
        assert by_name["loader.stage"].thread == "model-loader_9"
        assert tracer.current() == 0
    finally:
        tracer.close()


def test_copy_span_ends_when_the_buffers_are_ready():
    tracer = Tracer()
    try:
        tree = {"w": jnp.ones((64, 64)), "b": jnp.zeros(64)}
        with tracer.span("loader.stage"):
            tracer.span_until_ready("loader.h2d", 5, tree, bytes=123)
    finally:
        tracer.close()  # waits for the readiness thread
    h2d = [s for s in tracer.spans() if s.name == "loader.h2d"]
    assert len(h2d) == 1
    stage = [s for s in tracer.spans() if s.name == "loader.stage"][0]
    assert h2d[0].parent == stage.sid
    assert h2d[0].t0_ns == 5 and h2d[0].t1_ns > 5
    assert h2d[0].attrs["bytes"] == 123
    assert h2d[0].thread.startswith("h2d-ready")


def test_generate_tokens_names_its_phases():
    """Prefill and decode carry their names into the program's metadata
    (and so into a device trace)."""
    from repro.serving import server as S
    from repro.models import transformer as T
    cfg = get_config("mamba2-780m", reduced=True)
    params = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0), jnp.float32))
    text = S._generate_tokens.lower(
        cfg, params, jax.ShapeDtypeStruct((2, 8), jnp.int32), max_new=4,
        max_len=12).as_text(debug_info=True)
    assert "/prefill/" in text and "/decode/" in text


def test_threads_lose_no_span_or_count():
    """More threads than cores recording at once, with the interpreter
    switching threads as often as it can: every span, parent link and
    count is kept."""
    import os
    import sys
    tracer = Tracer(capacity=1000)
    n_threads, n_each = 2 * (os.cpu_count() or 4), 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    wrong = []  # an assert in a thread would not fail the test
    try:
        def work():
            for _ in range(n_each):
                with tracer.span("engine.advance") as outer:
                    with tracer.span("executor.run") as inner:
                        if inner.parent != outer.sid:
                            wrong.append(inner)
                    tracer.count("engine.batches")
                    tracer.count("engine.batch_rows", 2)
        threads = [threading.Thread(target=work, name=f"w{i}")
                   for i in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
        tracer.close()
    assert not wrong
    total = 2 * n_threads * n_each
    c = tracer.counters()
    assert c["engine.batches"] == n_threads * n_each
    assert c["engine.batch_rows"] == 2 * n_threads * n_each
    assert len(tracer.spans()) == 1000
    assert tracer.dropped == total - 1000
