"""The one traffic generator: every mix is a data file of parameters.

Open loop (``"loop": "open"``): arrivals at ``rate_rps`` in phases of
``phase_s`` seconds.  With ``hot_share`` the phases rotate a hot tenant
in the configuration's order, which gets that share of each phase's
arrivals; without it tenants share evenly.  Closed loop (``"loop":
"closed"``): ``clients_per_tenant`` clients per tenant, each sending its
next request the moment its last reply is on the host.

Every seed gets the same work in another order, so runs on different
seeds measure the same thing: each phase holds exactly ``round(rate *
phase_s)`` arrivals whose gaps are the exponential distribution's
quantiles, shuffled; tenant and prompt-length counts are exact shares,
shuffled.  Prompt ids are drawn from the tenant's vocabulary.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclass
class Arrival:
    due_ms: float
    app: str
    prompt: np.ndarray  # (S,) int32
    max_new: int
    client: Optional[int] = None  # closed loop: the client that sent it


def exact_counts(n: int, shares: Sequence[float]) -> List[int]:
    """Split ``n`` by ``shares`` with the largest-remainder rule."""
    raw = np.asarray(shares, float) / float(sum(shares)) * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def _lengths(rng, traffic: dict, n: int) -> List[int]:
    lens = [L for L, c in zip(traffic["prompt_lens"],
                              exact_counts(n, traffic["prompt_shares"]))
            for _ in range(c)]
    rng.shuffle(lens)
    return lens


def _prompt(rng, vocab: int, length: int) -> np.ndarray:
    return rng.integers(1, vocab, size=length, dtype=np.int32)


class OpenLoop:
    """A fixed schedule of arrivals over ``[0, span_ms)``."""

    def __init__(self, traffic: dict, vocab: Dict[str, int], seed: int,
                 span_ms: float):
        rng = np.random.default_rng(seed)
        apps = list(vocab)
        phase_ms = traffic.get("phase_s", span_ms / 1e3) * 1e3
        n_phase = int(round(traffic["rate_rps"] * phase_ms / 1e3))
        q = -np.log1p(-(np.arange(n_phase) + 0.5) / n_phase)
        self.arrivals: List[Arrival] = []
        for k in range(int(np.ceil(span_ms / phase_ms))):
            t0 = k * phase_ms
            gaps = rng.permutation(q)
            times = t0 + (np.cumsum(gaps) - rng.random() * gaps[0]) \
                * phase_ms / gaps.sum()
            if "hot_share" in traffic:
                hot = apps[k % len(apps)]
                cold = [a for a in apps if a != hot]
                counts = exact_counts(n_phase, [traffic["hot_share"]] + [
                    (1 - traffic["hot_share"]) / len(cold)] * len(cold))
                who = [a for a, c in zip([hot] + cold, counts)
                       for _ in range(c)]
            else:
                who = [a for a, c in zip(
                    apps, exact_counts(n_phase, [1] * len(apps)))
                    for _ in range(c)]
            rng.shuffle(who)
            for t, app, L in zip(times, who, _lengths(rng, traffic,
                                                      n_phase)):
                if t < span_ms:
                    self.arrivals.append(Arrival(
                        float(t), app, _prompt(rng, vocab[app], L),
                        traffic["max_new"]))
        self.arrivals.sort(key=lambda a: a.due_ms)
        self._i = 0

    def pop_due(self, now_ms: float) -> List[Arrival]:
        out = []
        while (self._i < len(self.arrivals)
               and self.arrivals[self._i].due_ms <= now_ms):
            out.append(self.arrivals[self._i])
            self._i += 1
        return out

    def next_due_ms(self) -> float:
        if self._i < len(self.arrivals):
            return self.arrivals[self._i].due_ms
        return float("inf")

    def on_done(self, arrival: Arrival, t_ms: float) -> None:
        """Open loop: a reply changes nothing."""


class ClosedLoop:
    """``clients_per_tenant`` clients per tenant with no think time; the
    clients stop sending at ``span_ms``."""

    BLOCK = 20  # prompt lengths keep their exact shares per 20 requests

    def __init__(self, traffic: dict, vocab: Dict[str, int], seed: int,
                 span_ms: float):
        self._rng = np.random.default_rng(seed)
        self._traffic = traffic
        self._vocab = vocab
        self._span_ms = span_ms
        self._lens: Dict[int, List[int]] = {}
        self.clients = [app for app in vocab
                        for _ in range(traffic["clients_per_tenant"])]
        self._ready = [self._next(c, 0.0)
                       for c in range(len(self.clients))]

    def _next(self, client: int, t_ms: float) -> Arrival:
        lens = self._lens.setdefault(client, [])
        if not lens:
            lens.extend(_lengths(self._rng, self._traffic, self.BLOCK))
        app = self.clients[client]
        return Arrival(t_ms, app, _prompt(self._rng, self._vocab[app],
                                          lens.pop()),
                       self._traffic["max_new"], client)

    def pop_due(self, now_ms: float) -> List[Arrival]:
        out = [a for a in self._ready if a.due_ms <= now_ms]
        self._ready = [a for a in self._ready if a.due_ms > now_ms]
        return out

    def next_due_ms(self) -> float:
        return min((a.due_ms for a in self._ready), default=float("inf"))

    def on_done(self, arrival: Arrival, t_ms: float) -> None:
        if t_ms < self._span_ms:
            self._ready.append(self._next(arrival.client, t_ms))


def make_source(traffic: dict, vocab: Dict[str, int], seed: int,
                span_ms: float):
    loop = {"open": OpenLoop, "closed": ClosedLoop}[traffic["loop"]]
    return loop(traffic, vocab, seed, span_ms)
