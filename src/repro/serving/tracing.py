"""Spans and counters of the serving program, kept in memory.

One :class:`Tracer` per ``EdgeServer`` (``srv.tracer``); the engine, the
loader and the tenant runtimes receive it at ``start()``.  A span records
its name, its start and end on ``time.perf_counter_ns()``, its own id,
the id of the span that caused it, the thread it ran on and a few
attributes (tenant, request ids, bits, bytes).  The cause is the
innermost span open on the same thread, or an id handed over explicitly
where work crosses to a worker thread (a staged load, a predictor fit).
While a span is open it is also a ``jax.profiler.TraceAnnotation`` of
the same name, so a profiler session shows it beside the device's
operations; outside a session that costs next to nothing.

Spans go into a ring of fixed size (``CAPACITY``); what falls off the
end is counted in :attr:`Tracer.dropped`.  Counters are plain numbers
added where the work happens.  Backend compiles are booked to the
innermost span open on the thread that compiled, or to ``none``.
Nothing is written out: :meth:`Tracer.spans`, :meth:`Tracer.counters`
and :meth:`Tracer.compiles` are the whole interface.

``Tracer(spans=False)`` (the engine's ``audit="counters"`` level) keeps
the counters and makes every span call a no-op.
"""
from __future__ import annotations

import itertools
import threading
import time
import weakref
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax

#: Prefixes of the benchmark harness's own annotations around the
#: program.  Its device-trace reduction keeps every host event that
#: starts with one, so a program span may not.
RESERVED = ("execute ", "set_variant ", "predict_and_preload",
            "cluster_advance", "idle")
CAPACITY = 1 << 20
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Span:
    """One recorded interval.  While open it is the context manager that
    :meth:`Tracer.span` returns."""
    __slots__ = ("sid", "parent", "name", "t0_ns", "t1_ns", "thread",
                 "attrs", "_tracer", "_ann")

    def __init__(self, tracer: "Tracer", sid: int, parent: Optional[int],
                 name: str, attrs: dict):
        self._tracer = tracer
        self.sid = sid
        self.parent = parent
        self.name = name
        self.attrs = attrs
        self.thread = ""
        self.t0_ns = self.t1_ns = 0
        self._ann = None

    @property
    def ms(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e6

    def __enter__(self) -> "Span":
        local = self._tracer._thread()
        stack = local.stack
        if self.parent is None:
            self.parent = stack[-1].sid if stack else 0
        self.thread = local.name
        stack.append(self)
        if _annotating():
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        tracer = self._tracer
        tracer._local.stack.pop()
        tracer._keep(self)
        return False

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, sid={self.sid}, parent={self.parent}, "
                f"{self.ms:.3f} ms, {self.thread}, {self.attrs})")


class _Off:
    """The span a tracer hands out when it records none."""
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()
# True while a profiler session is collecting: only then is a span also
# written into the profiler's trace.
_annotating = jax.profiler.TraceAnnotation.is_enabled


class Tracer:
    """In-memory spans and counters of one server."""

    def __init__(self, spans: bool = True, capacity: int = CAPACITY):
        self.recording = spans
        self._ring: deque = deque(maxlen=capacity)
        self._compiles: deque = deque(maxlen=capacity)
        self._kept = 0
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._counters: Dict[str, float] = {}
        self._names: set = set()
        # Waits for staged buffers off every serving thread (loader.h2d).
        self._ready: Optional[ThreadPoolExecutor] = None
        self._unlisten = None
        if spans:
            self._ready = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="h2d-ready")
            ref = weakref.ref(self)

            def on_event(event: str, secs: float, **kw) -> None:
                tracer = ref()
                if tracer is not None and event == BACKEND_COMPILE:
                    tracer._book_compile(str(kw.get("fun_name", "")), secs)

            jax.monitoring.register_event_duration_secs_listener(on_event)
            self._unlisten = weakref.finalize(
                self, jax.monitoring.unregister_event_duration_listener,
                on_event)

    # -- recording ---------------------------------------------------------
    def span(self, name: str, parent: Optional[int] = None, **attrs):
        """A context manager that records ``name`` around its block;
        ``parent`` overrides the innermost open span as the cause."""
        if not self.recording:
            return _OFF
        if name not in self._names:
            self._check(name)
        return Span(self, next(self._ids), parent, name, attrs)

    def record(self, name: str, t0_ns: int, t1_ns: int,
               parent: Optional[int] = None, **attrs) -> None:
        """Keep a span that has already ended (memory only: the profiler
        cannot be told about it after the fact)."""
        if not self.recording:
            return
        if name not in self._names:
            self._check(name)
        sp = Span(self, next(self._ids),
                  self.current() if parent is None else parent, name, attrs)
        sp.thread = self._thread().name
        sp.t0_ns, sp.t1_ns = t0_ns, t1_ns
        self._keep(sp)

    def span_until_ready(self, name: str, t0_ns: int, tree: Any,
                         **attrs) -> None:
        """Record ``name`` from ``t0_ns`` until every array of ``tree`` is
        ready on its device.  The wait runs on a thread of its own, which
        holds ``tree`` only until then; the caller never waits."""
        if self._ready is None:
            return
        self._ready.submit(self._await_ready, name, t0_ns, tree,
                           self.current(), attrs)

    def _await_ready(self, name, t0_ns, tree, parent, attrs) -> None:
        jax.block_until_ready(tree)
        self.record(name, t0_ns, time.perf_counter_ns(), parent, **attrs)

    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def current(self) -> int:
        """Id of the innermost span open on this thread, 0 if none."""
        stack = getattr(self._local, "stack", None)
        return stack[-1].sid if stack else 0

    def close(self) -> None:
        """Wait for pending readiness spans and stop booking compiles.
        What was recorded stays readable."""
        if self._ready is not None:
            self._ready.shutdown(wait=True)
            self._ready = None
        if self._unlisten is not None:
            self._unlisten()

    # -- reading -------------------------------------------------------------
    def spans(self) -> List[Span]:
        """Every kept span, in the order they ended."""
        with self._lock:
            return list(self._ring)

    def counters(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def compiles(self) -> List[Tuple[int, str, str, float]]:
        """``(t_ns, span name, fun_name, seconds)`` per backend compile."""
        with self._lock:
            return list(self._compiles)

    @property
    def dropped(self) -> int:
        """Spans that fell off the ring."""
        return max(0, self._kept - self._ring.maxlen)

    # -- internals -----------------------------------------------------------
    def _check(self, name: str) -> None:
        if name.startswith(RESERVED):
            raise ValueError(
                f"span name {name!r} starts with a prefix the benchmark "
                f"harness reserves for its own annotations: {RESERVED}")
        self._names.add(name)

    def _thread(self) -> threading.local:
        """This thread's open spans (``stack``) and name."""
        local = self._local
        if not hasattr(local, "stack"):
            local.stack = []
            local.name = threading.current_thread().name
        return local

    def _keep(self, sp: Span) -> None:
        sp._tracer = None  # a kept span must not keep its tracer alive
        with self._lock:
            self._ring.append(sp)
            self._kept += 1

    def _book_compile(self, fun_name: str, secs: float) -> None:
        stack = getattr(self._local, "stack", None)
        owner = stack[-1].name if stack else "none"
        with self._lock:
            self._compiles.append((time.perf_counter_ns(), owner, fun_name,
                                   secs))
            c = self._counters
            c[f"compiles.{owner}"] = c.get(f"compiles.{owner}", 0) + 1
            c[f"compile_s.{owner}"] = c.get(f"compile_s.{owner}", 0) + secs
