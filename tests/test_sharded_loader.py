"""Sharded loader tests: per-shard staging schedules, whole-load claims
released shard-by-shard, per-device budget ledgers, the
shard-doesn't-fit → whole-load-failure → downgrade path, sim-executor
bit-determinism, and (under the CI ``test-multidevice`` job's 8 fake CPU
devices) real-mesh shard placement matching the accounting fractions.

Synthetic-zoo tests drive the manager + channel directly (no models);
engine tests build through the declarative API with sim executors.
"""
import math

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import EdgeMultiAI
from repro.core.memory_state import DeviceLedger
from repro.core.model_zoo import ModelVariant, ModelZoo, zoo_from_config
from repro.distributed import sharding as SH
from repro.serving import Batch, EdgeServer, Request, poisson_trace
from repro.serving.api import (BatchingSpec, LoaderSpec, ServingConfig,
                               SimTenant, TenantSpec)
from repro.serving.sharded_loader import ShardedLoaderChannel

N_DEV = 4


def _zoo(name, sizes):
    return ModelZoo(app_name=name, variants=tuple(
        ModelVariant(f"{name}-{i}", bits=32 >> i, size_mb=s,
                     accuracy=90.0 - 10 * i, load_ms=s * 2)
        for i, s in enumerate(sizes)))


def make_manager(budget_mb=1000.0, device_budget_mb=None, **zoos):
    zoos = zoos or {"a": _zoo("a", [500, 300]), "b": _zoo("b", [400, 200])}
    mgr = EdgeMultiAI(zoos, budget_mb=budget_mb, policy="iws-bfe",
                      delta_ms=10.0)
    per_dev = (budget_mb / N_DEV if device_budget_mb is None
               else device_budget_mb)
    mgr.state.devices = DeviceLedger(
        (per_dev,) * N_DEV,
        split_fn=lambda app, v: SH.variant_shard_mb(v.size_mb, N_DEV))
    return mgr


# ---------------------------------------------------------------------------
# Per-shard schedule + claim lifecycle (synthetic zoos, no models)
# ---------------------------------------------------------------------------
def test_enqueue_claims_whole_load_and_shards_tile_the_transfer():
    mgr = make_manager()
    loader = ShardedLoaderChannel(mgr, n_devices=N_DEV)
    ld = loader.enqueue(mgr.plan_demand("a", 0.0), now_ms=0.0, demand=True)
    assert ld is not None and ld.charge_mb == 500.0
    st = mgr.state
    assert st.inflight_mb == 500.0, "claim charged once, up front"
    led = st.devices
    assert led.inflight["a"] == pytest.approx([125.0] * N_DEV)
    # Shared host link: shard slots tile [0, load_ms] exactly.
    assert [s.load_ms for s in ld.shards] == pytest.approx([250.0] * N_DEV)
    assert ld.shards[0].t_start_ms == 0.0
    assert ld.shards[-1].ready_ms == pytest.approx(1000.0)  # 500 * 2
    assert ld.ready_ms == pytest.approx(1000.0)
    assert sum(s.global_mb for s in ld.shards) == pytest.approx(500.0)
    # Wake semantics match the single-stream loader (next commit) so
    # the A/B differs only in staging accounting, but progress is still
    # observable per shard at any reap point.
    assert loader.earliest_ready() == pytest.approx(1000.0)
    assert loader.reap(250.0) == []
    assert ld.shards[0].landed and not ld.shards[1].landed
    assert loader.shards_landed == 1
    assert loader.reap(510.0) == []
    assert loader.shards_landed == 2
    recs = loader.reap(1000.0)
    assert [r.app for r in recs] == ["a"]
    assert len(recs[0].shard_intervals) == N_DEV
    assert st.inflight_mb == 0.0
    assert led.inflight == {}
    assert led.weights["a"] == pytest.approx([125.0] * N_DEV)
    assert st.tenants["a"].loaded.size_mb == 500.0
    loader.close()


def test_cancel_releases_shard_claims_in_device_order():
    mgr = make_manager()
    loader = ShardedLoaderChannel(mgr, n_devices=N_DEV)
    loader.enqueue(mgr.plan_proactive("a", 0.0), 0.0, predicted_ms=900.0)
    led = mgr.state.devices
    order = []
    orig = led.release_inflight_shard

    def spy(app, device, mb):
        order.append((device, mb))
        orig(app, device, mb)

    led.release_inflight_shard = spy
    # Two shards landed by t=600; cancel mid-flight.
    loader.reap(600.0)
    assert loader.shards_landed == 2
    ld = loader.cancel("a", 600.0)
    assert ld is not None
    assert [d for d, _ in order] == list(range(N_DEV)), \
        "claims released shard-by-shard in device order"
    assert all(mb == pytest.approx(125.0) for _, mb in order)
    assert mgr.state.inflight_mb == 0.0
    assert led.inflight == {}
    assert mgr.state.tenants["a"].loaded is None
    # The landed shards' transfer still earns overlap credit: a partial
    # record is queued for the engine's next reap.
    recs = loader.reap(600.0)
    assert len(recs) == 1 and recs[0].partial
    assert len(recs[0].shard_intervals) == 2
    assert recs[0].load_ms == pytest.approx(500.0), "2 of 4 shard slots"
    assert loader.loads_committed == 0
    loader.close()


def test_shard_that_does_not_fit_fails_whole_load_cleanly():
    """One overfull chip fails the load before any claim lands."""
    # Global 1000MB is plenty; per-chip 100MB < a.bf16's 125MB shard.
    mgr = make_manager(device_budget_mb=100.0)
    loader = ShardedLoaderChannel(mgr, n_devices=N_DEV)
    plan = mgr.plan_demand("a", 0.0)
    assert plan is not None and plan.variant.size_mb == 500.0
    assert loader.enqueue(plan, 0.0, demand=True) is None
    assert mgr.state.inflight_mb == 0.0, "no global claim landed"
    assert mgr.state.devices.inflight == {}, "no shard claim landed"
    assert "a" not in loader.inflight
    loader.close()


def test_sharded_shrink_restages_smaller_shards():
    mgr = make_manager()
    loader = ShardedLoaderChannel(mgr, n_devices=N_DEV)
    loader.enqueue(mgr.plan_proactive("a", 0.0), 0.0, predicted_ms=2000.0)
    loader.reap(300.0)  # one 250ms shard slot landed
    small = mgr.state.tenants["a"].zoo.smallest  # 300MB, load 600ms
    ld = loader.shrink_inflight("a", small, 300.0)
    assert ld is not None and ld.variant is small
    assert mgr.state.inflight_mb == pytest.approx(300.0)
    assert mgr.state.devices.inflight["a"] == pytest.approx([75.0] * N_DEV)
    assert ld.shards[-1].ready_ms == pytest.approx(300.0 + 600.0)
    assert loader.prefetch_shrunk == 1
    # The old load's landed shard is credited; the shrunk load commits.
    recs = loader.reap(900.0)
    kinds = [(r.partial, r.bits) for r in recs]
    assert (True, 32) in kinds and (False, small.bits) in kinds
    assert mgr.state.tenants["a"].loaded is small
    assert mgr.state.inflight_mb == 0.0
    loader.close()


def test_shrink_mid_release_cannot_double_release_claims():
    """The cancel-vs-shrink race: shrinking an in-flight prefetch retires
    the old action record and reserves fresh (smaller) claims under a new
    one — a stale path still holding the OLD record (its shards
    mid-release) must not release the NEW record's claims.  The record
    state machine (staging → cancelled, one-way) guards every release."""
    mgr = make_manager()
    loader = ShardedLoaderChannel(mgr, n_devices=N_DEV)
    old = loader.enqueue(mgr.plan_proactive("a", 0.0), 0.0,
                         predicted_ms=2000.0)
    small = mgr.state.tenants["a"].zoo.smallest
    new = loader.shrink_inflight("a", small, 100.0)
    assert new is not None and new is not old
    assert old.state == "cancelled" and new.staging
    st, led = mgr.state, mgr.state.devices
    assert st.inflight_mb == pytest.approx(300.0)
    claims_before = {a: list(c) for a, c in led.inflight.items()}
    # The race, replayed deliberately: retire the old record again.
    assert loader._retire_load(old) is False, "stale release refused"
    assert st.inflight_mb == pytest.approx(300.0), "no double release"
    assert {a: list(c) for a, c in led.inflight.items()} == claims_before
    # And the live record releases exactly once under repeated cancels.
    assert loader.cancel("a", 200.0) is not None
    assert loader.cancel("a", 200.0) is None
    assert st.inflight_mb == 0.0 and led.inflight == {}
    assert loader.prefetch_wasted == 1
    loader.close()


# ---------------------------------------------------------------------------
# Engine integration: downgrade path, invariant, determinism
# ---------------------------------------------------------------------------
def _sim_server(device_budget_mb, names=("tinyllama-1.1b",)):
    srv = EdgeServer(budget_mb=0.0, policy="iws-bfe", delta_ms=1000.0,
                     sharded_mesh=(N_DEV,),
                     device_budget_mb=device_budget_mb)
    for name in names:
        cfg = get_config(name, reduced=True)
        srv.register_tenant(name, SimTenant(name, cfg))
    srv.budget_mb = srv.contention_budget(0.05)
    srv.start()
    return srv


def test_device_pressure_feeds_admission_downgrade_path():
    """A demand load whose bf16 shard overflows its chip fails in the
    loader; the synchronous admission then downgrades until every shard
    fits — the per-device analogue of the KV self-downgrade."""
    app = "tinyllama-1.1b"
    cfg = get_config(app, reduced=True)
    zoo = zoo_from_config(cfg, precisions=(16, 8))
    mesh = SH.serving_mesh((N_DEV,))
    frac = SH.weight_shard_fraction(cfg, mesh)
    shard16 = zoo.by_bits(16).size_mb * frac
    shard8 = zoo.by_bits(8).size_mb * frac
    assert shard8 < shard16
    srv = _sim_server(device_budget_mb=(shard8 + shard16) / 2)
    plan = srv.manager.plan_demand(app, 0.0)
    assert plan is not None and plan.variant.bits == 16
    assert srv.loader.enqueue(plan, 0.0, demand=True) is None, \
        "bf16 shard overflows its chip: whole load fails cleanly"
    prompts = np.zeros((1, 4), np.int32)
    reqs = [Request(app=app, prompt=prompts[0], max_new=2,
                    arrival_ms=0.0)]
    results, _, toks = srv.engine.execute_batch(
        Batch(app, reqs, prompts, 2), now_ms=0.0)
    assert toks is not None and not results[0].failed
    assert results[0].bits == 8, "admission downgraded to the fitting shard"
    led = srv.manager.state.devices
    led.check_invariant()
    assert led.weights[app] == pytest.approx([shard8] * N_DEV)
    srv.engine.check_event_invariant()
    ev = srv.engine.events[-1]
    assert ev.device_mb is not None and len(ev.device_mb) == N_DEV
    srv.close()


def test_unfittable_smallest_shard_rejects_batch_cleanly():
    """When even the smallest variant's shard overflows its chip, the
    admission is a counted weight failure — never over-budget committed
    per-device state that trips the invariant later."""
    app = "tinyllama-1.1b"
    cfg = get_config(app, reduced=True)
    zoo = zoo_from_config(cfg, precisions=(16, 8))
    frac = SH.weight_shard_fraction(cfg, SH.serving_mesh((N_DEV,)))
    shard8 = zoo.by_bits(8).size_mb * frac
    srv = _sim_server(device_budget_mb=shard8 * 0.5)
    prompts = np.zeros((1, 4), np.int32)
    reqs = [Request(app=app, prompt=prompts[0], max_new=2,
                    arrival_ms=0.0)]
    results, _, toks = srv.engine.execute_batch(
        Batch(app, reqs, prompts, 2), now_ms=0.0)
    assert toks is None and results[0].failed
    assert srv.engine.weight_failures == 1
    assert srv.engine.kv_rejections == 0
    assert srv.manager.state.tenants[app].loaded is None
    srv.manager.state.devices.check_invariant()
    srv.engine.check_event_invariant()
    srv.close()


def test_event_invariant_holds_with_sharded_loads_in_flight():
    srv = _sim_server(device_budget_mb=None,
                      names=("tinyllama-1.1b", "mamba2-780m"))
    cfgs = {n: t.cfg for n, t in srv.tenants.items()}
    trace, _ = poisson_trace(cfgs, requests_per_app=15,
                             mean_iat_ms=300.0, seed=3)
    stats = srv.engine.run_trace(trace)
    assert stats.requests == len(trace)
    srv.engine.check_event_invariant()
    assert any(e.device_mb is not None for e in srv.engine.events)
    assert srv.manager.state.inflight_mb == 0.0, "no stranded claims"
    assert srv.manager.state.devices.inflight == {}
    srv.close()


def _deterministic_run():
    srv = EdgeServer.build(ServingConfig(
        tenants=(TenantSpec("tinyllama-1.1b"), TenantSpec("mamba2-780m")),
        policy="iws-bfe", delta_ms=750.0,
        batching=BatchingSpec(max_batch=4, window_ms=20.0),
        loader=LoaderSpec(sharded=True, mesh_shape=(N_DEV,)),
        executor="sim", kv_headroom_shape=(2, 12)))
    cfgs = {t.name: t.cfg for t in srv.tenants.values()}
    trace, _ = poisson_trace(cfgs, requests_per_app=20,
                             mean_iat_ms=400.0, seed=0)
    stats = srv.engine.run_trace(trace)
    srv.engine.check_event_invariant()
    base = min(r.rid for r in srv.engine.results)
    results = [(r.rid - base, r.app, r.arrival_ms, r.start_ms, r.done_ms,
                r.warm, r.failed, r.bits) for r in srv.engine.results]
    srv.close()
    return stats, results


def test_sharded_sim_run_is_bit_deterministic():
    """Two full sharded sim-executor runs must agree bit-for-bit (the
    acceptance criterion the CI multidevice job re-checks): virtual
    shard schedules never read the wall clock."""
    s1, r1 = _deterministic_run()
    s2, r2 = _deterministic_run()
    assert r1 == r2
    assert s1 == s2
    assert s1.shards_landed > 0 and s1.shards_landed % N_DEV == 0


def test_loader_spec_round_trip_and_validation():
    spec = LoaderSpec(sharded=True, mesh_shape=[2, 4])
    assert spec.mesh_shape == (2, 4)  # list normalized to tuple
    cfg = ServingConfig(tenants=(TenantSpec("tinyllama-1.1b"),),
                        loader=spec, executor="sim")
    rt = ServingConfig.from_dict(cfg.to_dict())
    assert rt.loader == spec
    with pytest.raises(ValueError):
        LoaderSpec(sharded=True, prefetch=False)
    with pytest.raises(ValueError):
        LoaderSpec(sharded=True, mesh_shape=(2, 2, 2))


# ---------------------------------------------------------------------------
# Real mesh placement (CI test-multidevice: 8 fake CPU devices)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (the CI test-multidevice "
                           "job forces 8 fake CPU devices)")
def test_real_mesh_placement_matches_ledger_fractions():
    """device_put the real partition specs onto an 8-way mesh and check
    the bytes each chip actually holds match weight_shard_fraction — the
    figure the per-device ledger budgets with."""
    import jax.numpy as jnp

    from jax.sharding import AxisType
    from repro.models import transformer as T

    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        T.init_params(cfg, jax.random.key(0), jnp.float32))
    mesh = jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    specs = SH.param_specs(cfg, params, mesh, fsdp=False)
    placed = jax.device_put(params, SH.named(mesh, specs))
    per_device = {d.id: 0 for d in mesh.devices.flatten()}
    total = 0
    for leaf in jax.tree.leaves(placed):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_device[sh.device.id] += sh.data.nbytes
    frac = SH.weight_shard_fraction(
        cfg, SH.LogicalMesh({"data": 1, "model": 8}))
    for dev, nbytes in per_device.items():
        assert nbytes / total == pytest.approx(frac, rel=1e-6), \
            (dev, nbytes, total, frac)


def test_real_tenants_without_enough_accelerators_raise(monkeypatch):
    """On an accelerator, real tenants that cannot be placed on the
    requested mesh stop the build; only a CPU backend keeps the skip
    (the logical mesh still drives the accounting there)."""
    cfg = ServingConfig(tenants=(TenantSpec("tinyllama-1.1b"),),
                        loader=LoaderSpec(sharded=True,
                                          mesh_shape=(jax.device_count()
                                                      + 1,)),
                        executor="real")
    srv = EdgeServer.build(cfg)  # CPU: placement skipped
    assert srv.physical_mesh is None
    srv.close()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="cannot place"):
        EdgeServer.build(cfg)


def test_sharded_int8_serving_runs_kernel_per_shard():
    """A real int8 tenant served from a mesh over local devices runs
    the Pallas matmul per shard (``PlacedQuant`` → ``shard_map``) and
    decodes the same tokens as its weights on one device, on the fused
    path and on the eager path that batches with extra inputs take."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.serving.server import _generate_tokens

    # The reduced model's row-parallel ``wo`` (K=64) holds two scale
    # groups, so at most two shards keep whole groups.
    n = math.gcd(jax.device_count(), 2)
    srv = EdgeServer.build(ServingConfig(
        tenants=(TenantSpec("tinyllama-1.1b", precisions=(8,)),),
        loader=LoaderSpec(sharded=True, mesh_shape=(n,)),
        kv_headroom_shape=(1, 10), executor="real"))
    tr = srv.tenants["tinyllama-1.1b"]
    assert srv.physical_mesh is not None and tr.mesh is srv.physical_mesh
    prompt = (np.arange(7, dtype=np.int32) * 5 % tr.cfg.vocab_size)[None]
    ops.set_impl("pallas")  # interpret mode on CPU
    try:
        r = srv.serve("tinyllama-1.1b", prompt, max_new=3, now_ms=0.0)
        # A text model ignores the stub vision input; it only routes the
        # batch through the eager prefill/decode path.
        eager = tr.generate(prompt, 3, extra={"patch_embeds": np.zeros(
            (1, 1, tr.cfg.d_model), np.float32)})
        one = jax.device_put(tr.host[8], jax.devices()[0])
        want = _generate_tokens(tr.cfg, one, jnp.asarray(prompt),
                                max_new=3, max_len=10)
    finally:
        ops.set_impl(None)
        srv.close()
    assert not r.failed and r.bits == 8
    np.testing.assert_array_equal(r.tokens, np.asarray(want))
    np.testing.assert_array_equal(eager, np.asarray(want))
