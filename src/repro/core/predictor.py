"""The paper's two lightweight many-to-one vanilla RNN predictors
(§III-A "NN Model Manager"), implemented and trained in pure JAX:

* **Request predictor** — consumes the recent inter-arrival history of one
  application and predicts the next inter-arrival gap (hence the next
  request time).
* **Memory predictor** — consumes the recent sequence of memory-usage
  samples and predicts availability at the next decision point.

Both are the same tiny architecture (the paper calls it "edge-friendly"):
one tanh RNN cell + linear head, trained with AdamW on sliding windows.
No Pallas kernel is warranted here — the model is a few thousand FLOPs,
so it runs on the host's CPU backend, off the accelerator the served
models use, with each program compiled once per shape.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.training.optim import AdamW


def init_rnn(key: jax.Array, hidden: int = 32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wx": jax.random.normal(k1, (1, hidden), jnp.float32) * 0.5,
        "wh": jax.random.normal(k2, (hidden, hidden), jnp.float32)
        * (hidden ** -0.5),
        "b": jnp.zeros((hidden,), jnp.float32),
        "wo": jax.random.normal(k3, (hidden, 1), jnp.float32)
        * (hidden ** -0.5),
        "bo": jnp.zeros((1,), jnp.float32),
    }


def rnn_forward(params: dict, xs: jnp.ndarray) -> jnp.ndarray:
    """xs: (B, T) normalized series -> (B,) prediction (many-to-one)."""
    B, T = xs.shape
    h0 = jnp.zeros((B, params["wh"].shape[0]), jnp.float32)

    def cell(h, x):
        h = jnp.tanh(x[:, None] @ params["wx"] + h @ params["wh"]
                     + params["b"])
        return h, ()

    h, _ = jax.lax.scan(cell, h0, jnp.moveaxis(xs, 1, 0))
    return (h @ params["wo"] + params["bo"])[:, 0]


# One compile per (context, hidden) per process: ``params`` are
# arguments, not a closure, and the input is always ``(1, context)``.
_forward = jax.jit(rnn_forward)


@functools.partial(jax.jit, static_argnames=("steps",))
def _fit(params, opt_state, xs, ys, mask, *, steps: int = 200):
    """``steps`` AdamW steps on the windows where ``mask`` is 1.  Rows
    where it is 0 are padding: the loss is the mean over the real rows,
    so they add nothing to it or to the gradients."""
    opt = AdamW(lr=1e-2, weight_decay=0.0, clip_norm=1.0)
    n = jnp.sum(mask)

    def loss_fn(p):
        pred = rnn_forward(p, xs)
        return jnp.sum(mask * (pred - ys) ** 2) / n

    def step(carry, _):
        p, s = carry
        loss, g = jax.value_and_grad(loss_fn)(p)
        p, s, _ = opt.update(g, s, p)
        return (p, s), loss

    (params, opt_state), losses = jax.lax.scan(
        step, (params, opt_state), None, length=steps)
    return params, opt_state, losses


@dataclass
class SeriesPredictor:
    """Sliding-window RNN regressor over a scalar series.

    ``min_fit_samples`` / ``refit_interval`` drive the serving runtime's
    *background* training schedule: once the history holds at least
    ``min_fit_samples`` observations, :meth:`fit_due` turns true, and
    again every ``refit_interval`` further observations — the server
    hands due predictors to the loader's staging worker
    (``BackgroundLoader.submit_fit``) so training never blocks the
    serving loop.
    """
    context: int = 16
    hidden: int = 32
    seed: int = 0
    min_fit_samples: int = 24
    refit_interval: int = 16
    fit_steps: int = 150  # AdamW steps per background fit

    def __post_init__(self):
        # The RNN lives on the host's CPU backend: its jitted programs
        # run where their committed arguments are, so a forward pass or
        # a fit never dispatches to (or syncs with) an accelerator the
        # served models are using.
        self.device = jax.local_devices(backend="cpu")[0]
        with jax.default_device(self.device):
            params = init_rnn(jax.random.key(self.seed), self.hidden)
            opt_state = AdamW(lr=1e-2, weight_decay=0.0).init(params)
        self.params, self.opt_state = jax.device_put(
            (params, opt_state), self.device)
        self.mean = 1.0
        self.history: list[float] = []
        self.losses: Optional[np.ndarray] = None
        self.fits = 0  # completed fit() calls
        self._fit_len = 0  # history length at the last completed fit
        # Pre-refactor reference cost model: materialize the whole
        # history per predict() (see predict's comment).
        self.full_history_predict = False

    def observe(self, value: float) -> None:
        self.history.append(float(value))

    def fit_due(self) -> bool:
        """Enough new history to (re)train?  False until
        ``min_fit_samples`` accumulate, then true every
        ``refit_interval`` observations past the previous fit."""
        n = len(self.history)
        if n < max(self.min_fit_samples, self.context + 2):
            return False
        return self._fit_len == 0 or n - self._fit_len >= self.refit_interval

    def fit(self, steps: int = 200) -> float:
        """Train on all (context -> next) windows in the history.
        Returns the final training loss.  Safe to run off-thread while
        the owner keeps observing: the history is snapshotted, and the
        trained parameters land in one reference swap."""
        h = np.asarray(list(self.history), np.float32)
        if len(h) < self.context + 2:
            return float("nan")
        self.mean = float(np.mean(h)) or 1.0
        hn = h / self.mean
        windows = np.lib.stride_tricks.sliding_window_view(
            hn, self.context + 1)
        # Pad the windows to the next power of two (mask 0 on the
        # padding) so refits at a growing history reuse one compiled
        # ``_fit`` per bucket instead of compiling once per length.
        n = len(windows)
        padded = np.zeros((1 << (n - 1).bit_length(), self.context + 1),
                          np.float32)
        padded[:n] = windows
        mask = np.zeros(len(padded), np.float32)
        mask[:n] = 1.0
        xs, ys, mask = jax.device_put(
            (padded[:, :-1], padded[:, -1], mask), self.device)
        self.params, self.opt_state, losses = _fit(
            self.params, self.opt_state, xs, ys, mask, steps=steps)
        self.losses = np.asarray(losses)
        self.fits += 1
        self._fit_len = len(h)
        return float(self.losses[-1])

    def predict(self) -> float:
        """Predict the next value from the trailing context.

        The normalizer is recomputed from the trailing context rather than
        taken from ``self.mean``: the history keeps growing between
        ``fit()`` calls (the serving engine observes every arrival), so
        the fit-time mean goes stale and a drifting series would be fed to
        the RNN at the wrong scale.  Before the first ``fit()`` the RNN
        weights are random, so the running mean of the context *is* the
        prediction — the same fallback used while history is short.
        """
        # Only the trailing context is ever read, so only it is
        # materialized — the history list grows unboundedly under the
        # serving engine, and converting all of it per call would make
        # each prediction O(history).  Bit-identical: the slice holds
        # the same elements the full-array path reads, so either branch
        # returns the same floats.  ``full_history_predict`` keeps the
        # pre-refactor O(history) materialization — the serving
        # engine's ``scheduler="linear"`` reference path sets it so the
        # fast-path A/B measures against a cost-faithful baseline.
        if self.full_history_predict:
            h = np.asarray(self.history, np.float32)
        else:
            h = np.asarray(self.history[-self.context:], np.float32)
        if len(h) < self.context:
            return float(np.mean(h)) if len(h) else self.mean
        ctx = h[-self.context:]
        mean = float(np.mean(ctx)) or 1.0
        if self.losses is None:  # never fit: untrained RNN is noise
            return mean
        xs = jax.device_put((ctx / mean)[None], self.device)
        return float(np.asarray(_forward(self.params, xs))[0]) * mean


class RequestPredictor(SeriesPredictor):
    """Predicts the next request *time* of one application from its
    inter-arrival history."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.last_time: Optional[float] = None

    def observe_request(self, t: float) -> None:
        if self.last_time is not None:
            self.observe(max(t - self.last_time, 1e-6))
        self.last_time = t

    def predict_next_time(self) -> float:
        if self.last_time is None:
            return float("inf")
        gap = max(self.predict(), 1e-6)
        return self.last_time + gap


class MemoryPredictor(SeriesPredictor):
    """Predicts near-future memory availability from recent usage samples."""

    def predict_free(self, budget: float) -> float:
        used = self.predict()
        return max(budget - used, 0.0)
