"""Builds a cell's server and drives it in real time.

The window drives ``server.engine.cluster_submit`` / ``cluster_advance``
with the horizon at wall-clock milliseconds since the traffic began, so
the engine's own loop (batcher, admission, ``predict_and_preload``,
demand staging, background loader, executor) runs as a deployment runs
it.  The harness only wraps three methods of the built instance --
``TenantRuntime.execute``, ``TenantRuntime.set_variant`` and
``EdgeServer.predict_and_preload`` -- to stamp them on the host clock
and name them in the profiler's trace.  Requests are timed from when
they were due until their tokens are on the host.
"""
from __future__ import annotations

import gc
import sys
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from .spec import ROOT

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclass
class Req:
    due_ms: float
    app: str
    max_new: int
    arrival: object  # traffic.Arrival
    in_window: bool
    submit_ms: float = float("nan")
    rid: Optional[int] = None
    start_ms: float = float("nan")  # executor start
    done_ms: float = float("nan")  # tokens on the host
    failed: bool = False
    batch: Optional[int] = None  # index into Session.batches
    row: Optional[int] = None

    @property
    def resolved(self) -> bool:
        return self.failed or not np.isnan(self.done_ms)


@dataclass
class BatchRec:
    app: str
    bits: Optional[int]
    t0_ms: float
    t1_ms: float
    rids: List[int]
    prompts: np.ndarray  # (B, S) as the executor ran it
    tokens: np.ndarray  # (B, max_new)
    max_new: int


@dataclass
class MoveRec:
    app: str
    from_bits: Optional[int]
    to_bits: Optional[int]
    nbytes: int
    t0_ms: float
    t1_ms: float


@dataclass
class Session:
    """One process's server for one cell, on the harness clock."""
    cell: object  # spec.Cell
    trace: bool = False
    t_process0: float = field(default_factory=time.perf_counter)

    def __post_init__(self):
        self.origin = None  # perf_counter at traffic time 0
        self.requests: List[Req] = []
        self.by_rid: Dict[int, Req] = {}
        self.batches: List[BatchRec] = []
        self.moves: List[MoveRec] = []
        # (t_ms, fun_name, seconds, owner): owner "predictor" for compiles
        # on the predictor-fit worker or inside ``predict_and_preload``,
        # "serving" for any other.
        self.compiles: List[tuple] = []
        self.lateness_ms: List[float] = []
        self._results_seen = 0
        self._lock = threading.Lock()
        self._span = threading.local()  # the wrapped call open here
        self._source = None
        self._window = (0.0, 0.0)
        self._base = 0.0
        self._profile = None  # (start_ms, stop_ms, dir)
        self.trace_span_ms = None  # (start, stop) actually traced
        self._stopper = None  # the thread that stops the profiler

    # -- clock ----------------------------------------------------------
    def now_ms(self) -> float:
        return (time.perf_counter() - self.origin) * 1e3

    def _ann(self, name: str):
        if not self.trace:
            return nullcontext()
        return self.jax.profiler.TraceAnnotation(name)

    # -- build ------------------------------------------------------------
    def build(self):
        """``EdgeServer.build`` for the cell's configuration, then the
        three wrappers."""
        sys.path.insert(0, str(ROOT / "src"))
        import jax

        from repro.serving.api import (BatchingSpec, EdgeServer,
                                       LoaderSpec, ServingConfig, TenantSpec)
        self.jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        cfg = self.cell.config
        tenants = tuple(
            TenantSpec(t["name"], arch=t["arch"],
                       precisions=tuple(t["precisions"]),
                       reduced=t.get("reduced", False), seed=t["seed"])
            for t in cfg["tenants"])
        self.srv = EdgeServer.build(ServingConfig(
            tenants=tenants, budget_mb=cfg["budget_mb"],
            policy=cfg["policy"],
            batching=BatchingSpec(max_batch=cfg["max_batch"]),
            loader=LoaderSpec(prefetch=True), executor="real"))
        self._check_models()
        for name, tr in self.srv.tenants.items():
            self._wrap_tenant(name, tr)
        ppl = self.srv.predict_and_preload

        def predict_and_preload(now_ms):
            self._span.name = "predict_and_preload"
            try:
                with self._ann("predict_and_preload"):
                    return ppl(now_ms)
            finally:
                self._span.name = None
        self.srv.predict_and_preload = predict_and_preload

    def _check_models(self):
        """The program must serve the sizes the configuration file
        states: a changed model is a new configuration, not a speed-up."""
        for t in self.cell.config["tenants"]:
            got = self.srv.tenants[t["name"]].cfg
            bad = {k: (v, getattr(got, k)) for k, v in t["model"].items()
                   if getattr(got, k) != v}
            if bad:
                raise RuntimeError(
                    f"{t['name']}: program config differs from the "
                    f"configuration file (file, program): {bad}")

    def _on_event(self, event, seconds, **kw):
        """A backend compile, booked to the arrival predictors where it
        ran for them (their fit worker, or inside ``predict_and_preload``)
        and to serving everywhere else."""
        if event == BACKEND_COMPILE and self.origin is not None:
            predictor = (
                threading.current_thread().name.startswith("predictor-fit")
                or getattr(self._span, "name", None) == "predict_and_preload")
            with self._lock:
                self.compiles.append((
                    self.now_ms(), kw.get("fun_name", ""), seconds,
                    "predictor" if predictor else "serving"))

    def _wrap_tenant(self, name, tr):
        execute, set_variant = tr.execute, tr.set_variant
        jax = self.jax

        def timed_execute(batch, extra=None):
            t0 = self.now_ms()
            bits = tr.loaded_bits
            with self._ann(f"execute {name} #{len(self.batches)}"):
                tokens, virt = execute(batch, extra)
            t1 = self.now_ms()
            self._on_batch(BatchRec(
                name, bits, t0, t1, [r.rid for r in batch.requests],
                np.array(batch.prompts), np.asarray(tokens),
                batch.max_new))
            return tokens, virt

        def timed_set_variant(variant):
            t0 = self.now_ms()
            before = tr.loaded_bits
            with self._ann(f"set_variant {name}"):
                set_variant(variant)
            t1 = self.now_ms()
            after = tr.loaded_bits
            if after != before:
                nbytes = (0 if variant is None else sum(
                    x.nbytes for x in jax.tree.leaves(tr.host[after])))
                with self._lock:
                    self.moves.append(MoveRec(name, before, after, nbytes,
                                              t0, t1))

        tr.execute = timed_execute
        tr.set_variant = timed_set_variant

    # -- warm-up ----------------------------------------------------------
    def warm_programs(self):
        """Compile and run every program the traffic can produce: each
        tenant's variants at batch 1..max_batch and each prompt length,
        on zero weights of the served shapes (nothing crosses the host
        link); then drop them."""
        import jax.numpy as jnp

        from repro.serving import server as S
        from repro.serving.engine import kv_cache_mb
        tr_cfg = self.cell.traffic
        max_new = tr_cfg["max_new"]
        for tr in self.srv.tenants.values():
            for bits, host in tr.host.items():
                params = self.jax.tree.map(
                    lambda a: jnp.zeros(a.shape, a.dtype), host)
                for L in tr_cfg["prompt_lens"]:
                    for B in range(1, self.cell.config["max_batch"] + 1):
                        kv_cache_mb(tr.cfg, B, L + max_new)
                        np.asarray(S._generate_tokens(
                            tr.cfg, params, jnp.zeros((B, L), jnp.int32),
                            max_new=max_new, max_len=L + max_new))
                del params
        gc.collect()

    # -- driving ------------------------------------------------------------
    def _on_batch(self, rec: BatchRec):
        idx = len(self.batches)
        self.batches.append(rec)
        for row, rid in enumerate(rec.rids):
            r = self.by_rid.get(rid)
            if r is None:
                continue
            r.start_ms, r.done_ms, r.batch, r.row = (rec.t0_ms, rec.t1_ms,
                                                     idx, row)
            self._source.on_done(r.arrival, rec.t1_ms - self._base)
        self._tick(rec.t1_ms)
        self._pump(rec.t1_ms)

    def _pump(self, now_ms: float):
        """Submit every arrival that is due."""
        from repro.serving.batcher import Request
        eng = self.srv.engine
        w0, w1 = self._window
        for a in self._source.pop_due(now_ms - self._base):
            due = self._base + a.due_ms
            req = Req(due, a.app, a.max_new, a, w0 <= due < w1)
            er = Request(app=a.app, prompt=a.prompt, max_new=a.max_new,
                         arrival_ms=due)
            req.submit_ms = self.now_ms()
            eng.cluster_submit(er)
            req.rid = er.rid
            self.requests.append(req)
            self.by_rid[er.rid] = req
            if req.in_window:
                self.lateness_ms.append(req.submit_ms - due)

    def _reap_failures(self):
        """Requests the engine answered with a failure (rejected, or an
        executor that raised) are resolved as failed."""
        res = self.srv.engine.results
        for r in res[self._results_seen:]:
            req = self.by_rid.get(r.rid)
            if r.failed and req is not None and not req.resolved:
                req.failed = True
                req.done_ms = self.now_ms()
                self._source.on_done(req.arrival, req.done_ms - self._base)
        self._results_seen = len(res)

    def _tick(self, now_ms: float):
        """Window start and profiler, checked between batches too: under
        a closed loop one ``cluster_advance`` call can run many."""
        if self.t_window0 is None and now_ms >= self._window[0]:
            self.t_window0 = time.perf_counter()
        self._profile_tick(now_ms)

    def _profile_tick(self, now_ms: float):
        """Start the profiler at the sub-window's start and stop it at its
        end.  Collecting and writing a trace of several seconds takes
        tens of seconds, so the profiler stops on a thread of its own
        while the loop serves on; ``drive`` waits for it before it
        returns."""
        if self._profile is None:
            return
        start, stop, out = self._profile
        if self.trace_span_ms is None and now_ms >= start:
            opts = self.jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1  # the harness's annotations only
            opts.enable_hlo_proto = False
            self.jax.profiler.start_trace(out, profiler_options=opts)
            self.trace_span_ms = [self.now_ms(), None]
        elif (self.trace_span_ms is not None
              and self.trace_span_ms[1] is None and now_ms >= stop):
            self._stop_trace()

    def _stop_trace(self):
        self.trace_span_ms[1] = self.now_ms()
        self._stopper = threading.Thread(
            target=self.jax.profiler.stop_trace, name="trace-stop")
        self._stopper.start()

    def drive(self, source, warmup_ms: float, window_ms: float,
              drain_ms: float, profile=None):
        """Replay ``source`` from now (traffic time 0 of its schedule);
        the window is ``[warmup_ms, warmup_ms + window_ms)`` of it.
        Returns once every request due in the window is resolved, or
        ``drain_ms`` after the window closed.  One session may drive
        several sources in turn: the engine's clock runs on."""
        if self.origin is None:
            self.origin = time.perf_counter()
        self._base = base = self.now_ms()
        self.requests, self.by_rid, self.lateness_ms = [], {}, []
        self._source = source
        self._window = (base + warmup_ms, base + warmup_ms + window_ms)
        w0, w1 = self._window
        self._profile = (None if profile is None else
                         (base + profile[0], base + profile[1], profile[2]))
        self.t_window0 = None
        eng = self.srv.engine
        while True:
            now = self.now_ms()
            self._tick(now)
            self._pump(now)
            if now >= w1 and all(r.resolved for r in self.requests
                                 if r.in_window):
                break
            if now >= w1 + drain_ms:
                break
            with self._ann("cluster_advance"):
                t_next = eng.cluster_advance(now)
            self._reap_failures()
            now = self.now_ms()
            wake = min(t_next, base + source.next_due_ms(), w1 + drain_ms)
            if self._profile is not None:
                wake = min([wake] + [t for t in self._profile[:2]
                                     if t > now])
            if self.t_window0 is None:
                wake = min(wake, w0)
            if wake > now:
                with self._ann("idle"):
                    time.sleep(min(wake - now, 100.0) / 1e3)
        if self.trace_span_ms is not None and self.trace_span_ms[1] is None:
            self._stop_trace()
        if self._stopper is not None:
            self._stopper.join()
            self._stopper = None

    def memory_peak_bytes(self) -> int:
        stats = self.jax.devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def release(self):
        """Stop the loader and drop every device buffer the program holds,
        so the reference runs on a free chip."""
        self.srv.close()
        for tr in self.srv.tenants.values():
            tr.device_params = None
        gc.collect()
