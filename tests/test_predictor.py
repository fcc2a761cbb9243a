"""The arrival predictors' compiled programs: the forward pass compiles
once per shape, refits share one compiled ``_fit`` per power-of-two bucket
of windows, the padded and masked fit trains exactly as an unpadded one,
and the RNN lives on the host's CPU backend."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictor import (RequestPredictor, SeriesPredictor,
                                  _forward, rnn_forward)
from repro.training.optim import AdamW

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def compiles():
    """A live count of backend compiles while the test runs."""
    count = [0]

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    yield count
    jax.monitoring.unregister_event_duration_listener(on_event)


def _arrivals(p, rng, n, t=0.0):
    """Feed ``p`` arrivals until its history holds ``n`` gaps."""
    while len(p.history) < n:
        t += float(rng.exponential(100.0)) + 1.0
        p.observe_request(t)
    return t


def test_predict_and_refit_compile_once(compiles):
    rng = np.random.default_rng(0)
    p = RequestPredictor(context=8, hidden=16, seed=0)
    t = _arrivals(p, rng, 75)
    p.fit(150)
    before = compiles[0]
    p.predict_next_time()
    assert compiles[0] - before <= 1, "the first forward compiles at most once"
    before = compiles[0]
    for _ in range(50):
        t = _arrivals(p, rng, len(p.history) + 1, t)
        assert np.isfinite(p.predict_next_time())
    assert compiles[0] == before, "no compile after the first forward pass"
    # 67, 83 and 99 windows all pad to 128 rows: one compiled fit.
    p = RequestPredictor(context=8, hidden=16, seed=1)
    t = 0.0
    before = compiles[0]
    for n in (75, 91, 107):
        t = _arrivals(p, rng, n, t)
        assert np.isfinite(p.fit(150))
    assert compiles[0] - before <= 1, "three refits in one bucket"
    assert p.fits == 3


@functools.partial(jax.jit, static_argnames=("steps",))
def _reference_fit(params, opt_state, xs, ys, *, steps):
    """The unpadded fit: the plain mean over every window."""
    opt = AdamW(lr=1e-2, weight_decay=0.0, clip_norm=1.0)

    def loss_fn(p):
        return jnp.mean((rnn_forward(p, xs) - ys) ** 2)

    def step(carry, _):
        p, s = carry
        loss, g = jax.value_and_grad(loss_fn)(p)
        p, s, _ = opt.update(g, s, p)
        return (p, s), loss

    (params, opt_state), losses = jax.lax.scan(
        step, (params, opt_state), None, length=steps)
    return params, opt_state, losses


# Steps of the fit compared below.  AdamW amplifies rounding step by
# step: two unpadded fits that differ only in summation order (``mean``
# against ``sum / n``) part by up to ~4e-7 within 30 steps on such
# series and by ~2e-4 at 150, so a longer comparison measures the
# optimizer, not the padding.
REFERENCE_STEPS = 20


@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("n", [10, 33, 75, 130])
def test_padded_fit_and_jitted_forward_match_reference(n, seed):
    rng = np.random.default_rng(seed)
    p = SeriesPredictor(context=8, hidden=16, seed=seed)
    for _ in range(n):
        p.observe(float(rng.exponential(100.0)) + 1.0)
    cpu = jax.local_devices(backend="cpu")[0]
    assert p.params["wh"].devices() == {cpu}
    h = np.asarray(p.history, np.float32)
    windows = np.lib.stride_tricks.sliding_window_view(
        h / float(np.mean(h)), p.context + 1)
    ref_params, _, ref_losses = _reference_fit(
        p.params, p.opt_state, jnp.asarray(windows[:, :-1]),
        jnp.asarray(windows[:, -1]), steps=REFERENCE_STEPS)
    loss = p.fit(REFERENCE_STEPS)
    assert loss == pytest.approx(float(ref_losses[-1]), rel=1e-5, abs=1e-5)
    for k in ref_params:
        np.testing.assert_allclose(np.asarray(p.params[k]),
                                   np.asarray(ref_params[k]),
                                   rtol=1e-5, atol=1e-5)
    assert {d for leaf in jax.tree.leaves((p.params, p.opt_state))
            for d in leaf.devices()} == {cpu}
    xs = jnp.asarray(windows[-1:, 1:])
    eager = float(rnn_forward(p.params, xs)[0])
    assert float(_forward(p.params, xs)[0]) == pytest.approx(eager, rel=1e-6)
