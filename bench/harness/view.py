"""What a finished run hands to the metric readers.

Each reader (``bench/metrics/<name>.py``) defines ``read(view)`` and
returns a number, or None when the run holds nothing for it to read (the
metric is then left out of the result line).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from . import counts


@dataclass
class View:
    session: object  # serve.Session
    peaks: dict
    trace: Optional[dict] = None  # trace.load() output, --trace 1 only
    warm_by_rid: Dict[int, bool] = field(default_factory=dict)

    @property
    def window(self):
        return self.session._window

    @property
    def window_s(self) -> float:
        w0, w1 = self.window
        return (w1 - w0) / 1e3

    @property
    def models(self) -> Dict[str, dict]:
        return {t["name"]: t["model"]
                for t in self.session.cell.config["tenants"]}

    def in_window(self, t_ms: float) -> bool:
        w0, w1 = self.window
        return w0 <= t_ms < w1

    @property
    def requests(self) -> List:
        """Requests due in the window."""
        return [r for r in self.session.requests if r.in_window]

    @property
    def batches(self) -> List:
        """Batches whose execution started in the window."""
        return [b for b in self.session.batches if self.in_window(b.t0_ms)]

    @property
    def moves(self) -> List:
        return [m for m in self.session.moves if self.in_window(m.t0_ms)]

    @property
    def compiles(self) -> List[tuple]:
        return [c for c in self.session.compiles if self.in_window(c[0])]

    def latencies_ms(self) -> np.ndarray:
        """Due to tokens-on-host; a failed or unanswered request never
        meets a limit (+inf)."""
        return np.array([np.inf if (r.failed or np.isnan(r.done_ms))
                         else r.done_ms - r.due_ms for r in self.requests])

    def traced_batches(self):
        """``(batch, start_ns, end_ns)`` for each batch whose executor call
        the trace holds whole (its host span inside the device's traced
        interval), from the harness's host spans."""
        if self.trace is None or not self.trace["ops"]:
            return []
        ops = self.trace["ops"]
        d0, d1 = ops[0][1], max(s + d for _, s, d in ops)
        out = []
        for name, s, d in self.trace["host"]:
            if name.startswith("execute ") and " #" in name \
                    and d0 <= s and s + d <= d1:
                idx = int(name.rsplit("#", 1)[1])
                if idx < len(self.session.batches):
                    out.append((self.session.batches[idx], s, s + d))
        return out

    def trace_bounds(self):
        """The traced interval, from the harness's host spans."""
        host = self.trace["host"]
        if not host:
            raise RuntimeError("the trace holds none of the harness's spans")
        return (min(s for _, s, _ in host), max(s + d for _, s, d in host))

    def starts(self, line: str) -> List[int]:
        """Start times of a trace line's events (sorted by ``load``)."""
        cache = self.__dict__.setdefault("_starts", {})
        if line not in cache:
            cache[line] = [ev[1] for ev in self.trace[line]]
        return cache[line]

    def kernel_share(self, kernel: str, match) -> Optional[float]:
        """Least time over measured time of ``kernel``'s device events
        (names that ``match`` accepts), in percent, over the traced
        batches."""
        from . import trace as T
        least = spent = 0.0
        pk = self.peaks
        for b, s, e in self.traced_batches():
            m = self.models[b.app]
            calls = counts.kernel_calls(m, b.bits, *b.prompts.shape,
                                        b.max_new).get(kernel)
            evs = T.within(self.trace["ops"], s, e, match,
                           self.starts("ops"))
            if not calls or not evs:
                continue
            ops, nbytes, _ = calls
            least += max(ops / pk["bf16_flops_per_s"],
                         nbytes / pk["hbm_bytes_per_s"])
            spent += sum(d for _, _, d in evs) / 1e9
        return 100.0 * least / spent if spent > 0 else None
