"""Reduction of one profiler trace to the numbers the readers need.

``load`` turns the profiler's ``.xplane.pb`` into plain lists of
``(name, start_ns, duration_ns)``: the device's operations and program
runs (one device: the first TPU plane) and the harness's host spans.
The functions below work on those lists only, so a small recorded trace
committed with the tests checks them without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import (Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple, Union)

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

HOST_SPANS = ("execute ", "set_variant ", "predict_and_preload",
              "cluster_advance", "idle")


def load(trace_dir: str) -> dict:
    """``{"ops", "modules", "host"}`` from the newest ``.xplane.pb`` under
    ``trace_dir``: the first TPU's operations and program runs, sorted by
    start, and the harness's host spans."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and not out["ops"]:
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    out[key] = sorted(
                        ((e.name, e.start_ns, e.duration_ns)
                         for e in line.events), key=lambda ev: ev[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                    if e.name.startswith(HOST_SPANS))
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(events: Sequence[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    return [(max(s, t0), min(s + d, t1)) for _, s, d in events
            if s < t1 and s + d > t0]


def busy_ns(events: Sequence[Event], t0: int, t1: int) -> int:
    """Time in ``[t0, t1)`` in which some operation ran."""
    return sum(e - s for s, e in union(clip(events, t0, t1)))


def idle_gaps(events: Sequence[Event], t0: int, t1: int
              ) -> List[Tuple[int, int]]:
    gaps, cur = [], t0
    for s, e in union(clip(events, t0, t1)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def host_span_at(host: Sequence[Event], t: int) -> str:
    """The innermost harness span open on the host at ``t``."""
    open_ = [(d, n) for n, s, d in host if s <= t < s + d]
    if not open_:
        return "none"
    name = min(open_)[1]
    return name.split(" #")[0]


_HLO = re.compile(r"^%(?P<id>\S+) = (?P<type>.+?) (?P<op>[a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """``%closed_call.3 = f32[8,8]{1,0:T(8,128)} custom-call(...)`` ->
    ``closed_call.3 custom-call f32[8,8]``: the HLO instruction, its
    opcode and its result type without the layout."""
    m = _HLO.match(name)
    if not m:
        return name[:100]
    typ = re.sub(r"\{[^}]*\}", "", m["type"])
    return f"{m['id']} {m['op']} {typ}"[:100]


def self_times(events: Sequence[Event]) -> List[Event]:
    """Each event with its exclusive time: its duration less that of the
    events nested directly inside it (a ``while`` holds its body)."""
    out: List[List] = []
    stack: List[List] = []
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1] + stack[-1][3]:
            stack.pop()
        rec = [name, s, d, d]  # name, start, self, duration
        if stack:
            stack[-1][2] -= d
        stack.append(rec)
        out.append(rec)
    return [(n, s, max(self_, 0)) for n, s, self_, _ in out]


def top_ops(events: Sequence[Event], t0: int, t1: int, n: int = 10
            ) -> List[list]:
    """The ``n`` device operations (HLO instructions, by exclusive time)
    that took most time in ``[t0, t1)``, with seconds."""
    tot: Dict[str, int] = {}
    for name, s, d in self_times(events):
        if t0 <= s < t1:
            key = short_name(name)
            tot[key] = tot.get(key, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def named_gaps(ops: Sequence[Event], host: Sequence[Event], t0: int,
               t1: int, n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of the device, each named by what the
    host was doing at its middle, with seconds."""
    gaps = sorted(idle_gaps(ops, t0, t1), key=lambda g: g[0] - g[1])[:n]
    return [[host_span_at(host, (s + e) // 2), (e - s) / 1e9]
            for s, e in gaps]


def within(events: Sequence[Event], s: int, e: int,
           match: Union[None, str, Callable[[str], bool]] = None,
           starts=None) -> List[Event]:
    """Events that start inside ``[s, e)``, optionally whose name holds
    ``match`` (a substring, or a test on the name).  With ``starts`` (the
    sorted events' start times) the range is found by bisection."""
    if starts is not None:
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        events = events[lo:hi]
    if isinstance(match, str):
        sub = match
        match = lambda name: sub in name  # noqa: E731
    return [ev for ev in events if s <= ev[1] < e
            and (match is None or match(ev[0]))]
