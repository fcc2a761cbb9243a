"""The plain reference regenerates the program's weights from the seed and
computes the same function, checked at the program's reduced sizes on the
CPU.  (The reference itself imports nothing of the program; this test
does, to hold the two side by side.)"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from reference import models  # noqa: E402

CFG = json.load(open(os.path.join(HERE, "fixtures", "tiny.contended.json")))
TENANTS = {t["name"]: t for t in CFG["tenants"]}


def _program(name):
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config(name, reduced=True)
    return cfg, T.init_params(cfg, jax.random.key(TENANTS[name]["seed"]),
                              jnp.float32)


@pytest.mark.parametrize("name", sorted(TENANTS))
def test_reference_weights_equal_the_programs(name):
    t = TENANTS[name]
    a = models.Arch.from_model(t["model"])
    cfg, params = _program(name)
    emb = models._embedding(a, "bf16", t["seed"])
    np.testing.assert_array_equal(
        np.asarray(emb),
        np.asarray(params["embed"][0].astype(jnp.bfloat16), np.float32))
    _, per_name = models._layer_keys(a, t["seed"])
    for lname, (shape, kind) in a.template().items():
        for layer in range(a.num_layers):
            k = jax.random.split(per_name[lname], a.num_layers)[layer]
            np.testing.assert_array_equal(
                np.asarray(models._init(k, shape, kind)),
                np.asarray(params["layers"][lname][layer]), err_msg=lname)


@pytest.mark.parametrize("name", sorted(TENANTS))
def test_reference_int8_logits_match_the_programs_forward(name):
    """The int8 variant computes in float32, so the program's full forward
    over its dequantized weights and the reference agree to f32
    rounding."""
    from repro.models import transformer as T
    from repro.quant.quantize import dequantize_params, quantize_params
    t = TENANTS[name]
    cfg, params = _program(name)
    deq = dequantize_params(quantize_params(params, bits=8, group=32))
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size, size=(2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = T.forward(cfg, deq, {"tokens": jnp.asarray(tokens)})
    want = np.asarray(want[:, :, 0, :cfg.vocab_size])
    rows = np.repeat(np.arange(2), 40)
    cols = np.tile(np.arange(40), 2)
    got = np.asarray(models.logits(t["model"], t["seed"], "int8", tokens,
                                   rows, cols)).reshape(2, 40, -1)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() / scale < 1e-4


def test_rounding_of_each_variant():
    w = jax.random.normal(jax.random.key(0), (64, 8)) * 0.05
    for variant, tol in (("bf16", 2 ** -8), ("int8", 0.02), ("int4", 0.3),
                         ("fp8", 0.13)):
        r = models.round_weight("wq", w, variant)
        rel = float(jnp.max(jnp.abs(r - w)) / jnp.max(jnp.abs(w)))
        assert 0 < rel < tol, variant
    # Vectors and the embedding are not quantized by the int variants.
    v = jnp.linspace(-1, 1, 16)
    assert jnp.array_equal(models.round_weight("ln1", v, "int8"), v)
    assert jnp.array_equal(models.round_weight("embed", w, "int4"), w)
