"""Checkpointing, fault tolerance, compression, elastic resharding.

Multi-device cases run in a subprocess with 8 fake CPU devices (the flag
must be set before jax initializes, so it cannot live in this process)."""
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import checkpoint as ckpt
from repro.distributed.compression import CompressionState, compress_grads
from repro.distributed.fault_tolerance import (FailureInjector, NodeFailure,
                                               run_supervised)
from repro.training.data import DataConfig, SyntheticStream
from repro.training.optim import AdamW, warmup_cosine
from repro.training.train_step import init_state, make_train_step


class TestCheckpoint:
    def test_roundtrip(self):
        tree = {"a": jnp.arange(12.0).reshape(3, 4),
                "b": {"c": jnp.ones((5,), jnp.int32)}}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(tree, d, step=7)
            assert ckpt.latest_step(d) == 7
            out = ckpt.restore(tree, d)
            np.testing.assert_array_equal(np.asarray(out["a"]),
                                          np.asarray(tree["a"]))

    def test_atomic_no_partial_commit(self):
        tree = {"a": jnp.zeros((4,))}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(tree, d, step=1)
            # simulate a crashed save: stray tmp dir must be ignored
            os.makedirs(os.path.join(d, "step_00000002.tmp"))
            assert ckpt.latest_step(d) == 1
            ckpt.restore(tree, d)

    def test_gc_keeps_recent(self):
        tree = {"a": jnp.zeros((2,))}
        with tempfile.TemporaryDirectory() as d:
            for s in range(6):
                ckpt.save(tree, d, step=s)
            kept = sorted(x for x in os.listdir(d) if x.startswith("step_"))
            assert len(kept) == 3

    def test_bf16_roundtrip(self):
        """numpy stores bf16 as void16; restore must view it back."""
        tree = {"w": jnp.arange(8.0, dtype=jnp.bfloat16),
                "q": jnp.arange(4, dtype=jnp.int8)}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(tree, d, step=1)
            out = ckpt.restore(tree, d)
            assert out["w"].dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(out["w"], np.float32),
                np.asarray(tree["w"], np.float32))

    def test_async_save(self):
        tree = {"a": jnp.arange(6.0)}
        with tempfile.TemporaryDirectory() as d:
            saver = ckpt.AsyncCheckpointer()
            saver.save_async(tree, d, step=3)
            saver.wait()
            assert ckpt.latest_step(d) == 3


class TestFaultTolerance:
    def _setup(self):
        cfg = get_config("tinyllama-1.1b", reduced=True)
        opt = AdamW(lr=warmup_cosine(3e-3, 5, 40), weight_decay=0.01)
        step_fn = jax.jit(make_train_step(cfg, opt, remat=True,
                                          compute_dtype=None))
        state = init_state(cfg, jax.random.key(0), opt)
        ds = SyntheticStream(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=32, global_batch=4))
        def batch_fn(s):
            return {k: jnp.asarray(v)
                    for k, v in ds.batch_at(s).items()}
        return state, step_fn, batch_fn

    def test_recovery_bitwise_identical(self):
        state, step_fn, batch_fn = self._setup()
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            a = run_supervised(init_state=state, step_fn=step_fn,
                               batch_fn=batch_fn, total_steps=14,
                               ckpt_dir=d1, ckpt_every=4, async_save=False)
            b = run_supervised(
                init_state=state, step_fn=step_fn, batch_fn=batch_fn,
                total_steps=14, ckpt_dir=d2, ckpt_every=4,
                injector=FailureInjector(fail_at_steps=(6, 11)),
                async_save=False)
            assert b.restarts == 2
            np.testing.assert_allclose(a.losses[-1], b.losses[-1],
                                       rtol=1e-6)

    def test_loss_decreases(self):
        state, step_fn, batch_fn = self._setup()
        with tempfile.TemporaryDirectory() as d:
            rep = run_supervised(init_state=state, step_fn=step_fn,
                                 batch_fn=batch_fn, total_steps=25,
                                 ckpt_dir=d, ckpt_every=10,
                                 async_save=False)
        assert rep.losses[-1] < rep.losses[0] * 0.8

    def test_gives_up_after_max_restarts(self):
        state, step_fn, batch_fn = self._setup()
        with tempfile.TemporaryDirectory() as d:
            with pytest.raises(NodeFailure):
                run_supervised(
                    init_state=state, step_fn=step_fn, batch_fn=batch_fn,
                    total_steps=10, ckpt_dir=d, ckpt_every=100,
                    injector=FailureInjector(fail_at_steps=(1,) ),
                    max_restarts=0)


class TestCompression:
    def test_error_feedback_unbiased(self):
        """Long-run mean of compressed grads ≈ mean of true grads."""
        rng = np.random.default_rng(0)
        g_true = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
        state = CompressionState.init({"w": g_true})
        acc = jnp.zeros_like(g_true)
        for _ in range(50):
            out, state = compress_grads({"w": g_true}, state)
            acc = acc + out["w"]
        np.testing.assert_allclose(np.asarray(acc / 50),
                                   np.asarray(g_true), atol=5e-3)

    def test_training_with_compression_converges(self):
        cfg = get_config("tinyllama-1.1b", reduced=True)
        opt = AdamW(lr=3e-3)
        step = jax.jit(make_train_step(cfg, opt, compression=True,
                                       compute_dtype=None))
        state = init_state(cfg, jax.random.key(0), opt, compression=True)
        ds = SyntheticStream(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=32, global_batch=4))
        losses = []
        for s in range(20):
            batch = {k: jnp.asarray(v) for k, v in ds.batch_at(s).items()}
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]


MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.compression import compressed_allreduce_demo
    from repro.distributed import checkpoint as ckpt
    from repro.distributed.elastic import reshard, validate_elastic_plan

    from jax.sharding import AxisType
    mesh8 = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    mesh24 = jax.make_mesh((2, 4), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2)

    # 1. compressed all-reduce ~= exact all-reduce
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 128)),
                    jnp.float32)
    got = compressed_allreduce_demo(x, mesh8)
    want = x.reshape(8, 1, 128).sum(0)
    rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert rel < 0.02, rel
    print("compressed_allreduce ok", rel)

    # 2. sharded checkpoint -> restore onto a DIFFERENT mesh (elastic)
    w = jnp.arange(16 * 32, dtype=jnp.float32).reshape(16, 32)
    sh8 = NamedSharding(mesh8, P("data", None))
    w8 = jax.device_put(w, sh8)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save({"w": w8}, d, step=1)
        sh24 = NamedSharding(mesh24, P("data", "model"))
        out = ckpt.restore({"w": w}, d, shardings={"w": sh24})
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(w))
        assert out["w"].sharding == sh24
    print("elastic restore ok")

    # 3. live reshard
    r = reshard({"w": w8}, {"w": P("data", "model")}, mesh24)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(w))
    plan = validate_elastic_plan(mesh8, mesh24, global_batch=16)
    assert plan["ok"]
    print("reshard ok")
""")


def test_multidevice_subprocess():
    """Compression collective + elastic checkpoint on 8 fake devices."""
    proc = subprocess.run(
        [sys.executable, "-c", MULTIDEV], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "reshard ok" in proc.stdout
