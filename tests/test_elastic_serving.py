"""Elastic-mesh tests: chip loss & recovery as transactional drain plans.

Planner tests drive synthetic zoos against the manager directly (the
three drain outcomes — migrate, downgrade+migrate, unload — plus KV-page
preemption and the all-or-nothing applier).  Engine tests build the
declarative sim stack with a ``FaultSpec`` and check the per-event
ledger invariant, the typed elastic counters, warm-ratio recovery, and
bit-determinism of a faulted run.  Under the CI ``test-multidevice``
job's 8 fake CPU devices, ``TenantRuntime.set_variant`` on an attached
mesh must place real per-chip buffers matching the ledger fractions.
"""
import jax
import pytest

from repro.core import EdgeMultiAI
from repro.core import actions as A
from repro.core.memory_state import DeviceLedger, KVPagePool
from repro.core.model_zoo import ModelVariant, ModelZoo
from repro.distributed import sharding as SH
from repro.serving import EdgeServer, poisson_trace
from repro.serving.api import (BatchingSpec, FaultSpec, LoaderSpec,
                               ServingConfig, TenantSpec)
from repro.serving.elastic import (ElasticController, drain_plan,
                                   rebalance_plan)
from repro.serving.stats import EventKind

N_DEV = 4


def _zoo(name, sizes):
    return ModelZoo(app_name=name, variants=tuple(
        ModelVariant(f"{name}-{i}", bits=32 >> i, size_mb=s,
                     accuracy=90.0 - 10 * i, load_ms=s * 2)
        for i, s in enumerate(sizes)))


def make_manager(budgets, budget_mb=4000.0, **zoos):
    zoos = zoos or {"a": _zoo("a", [400, 200]), "b": _zoo("b", [400, 200])}
    mgr = EdgeMultiAI(zoos, budget_mb=budget_mb, policy="iws-bfe",
                      delta_ms=10.0, migrate=True)
    mgr.state.devices = DeviceLedger(
        tuple(budgets),
        split_fn=lambda app, v: SH.variant_shard_mb(v.size_mb,
                                                    len(budgets)))
    return mgr


# ---------------------------------------------------------------------------
# FaultSpec
# ---------------------------------------------------------------------------
def test_fault_spec_normalizes_and_validates():
    spec = FaultSpec(events=[[9000.0, 3, "up"], (3000, 3, "down")])
    assert spec.events == ((3000.0, 3, "down"), (9000.0, 3, "up"))
    with pytest.raises(ValueError):
        FaultSpec(events=((0.0, 0, "explode"),))
    with pytest.raises(ValueError):
        FaultSpec(events=((-1.0, 0, "down"),))


def test_controller_rejects_chip_beyond_mesh_and_ledgerless_state():
    mgr = make_manager(budgets=(500.0,) * N_DEV)
    with pytest.raises(ValueError, match="chip 9"):
        ElasticController(FaultSpec(events=((0.0, 9, "down"),)), mgr)
    mgr.state.devices = None
    with pytest.raises(ValueError, match="device ledger"):
        ElasticController(FaultSpec(), mgr)


# ---------------------------------------------------------------------------
# The drain planner: simulate == apply, three outcomes
# ---------------------------------------------------------------------------
def test_drain_migrates_dead_shard_and_simulate_matches_apply():
    mgr = make_manager(budgets=(500.0,) * N_DEV)
    st = mgr.state
    st.apply(A.plan_of(A.Load("a", st.tenants["a"].zoo.largest)))
    st.apply(A.plan_of(A.Load("b", st.tenants["b"].zoo.largest)))
    dead = 1
    st.devices.offline(dead)
    acts, counters, preempted, vacated = drain_plan(st, dead)
    assert counters == {"migrations": 2, "downgrades": 0, "unloads": 0}
    assert preempted == () and vacated == pytest.approx(200.0)
    assert st.simulate(A.ResidencyPlan(acts)) is None
    st.apply(A.ResidencyPlan(acts))
    st.devices.check_invariant()
    assert st.devices.weights["a"][dead] == 0.0
    assert st.devices.weights["b"][dead] == 0.0
    assert sum(st.devices.weights["a"]) == pytest.approx(400.0)
    # Both tenants stay resident at full precision.
    assert st.tenants["a"].loaded.size_mb == 400.0


def test_drain_downgrades_when_survivors_cannot_absorb_full_share():
    # One tenant at 120/chip; survivors have 10 free each (30 total):
    # the 120 share cannot rehome, the 200MB variant's layout-preserving
    # projection (60/chip, freeing 60 on each survivor) can.
    mgr = make_manager(budgets=(130.0,) * N_DEV,
                       a=_zoo("a", [480, 200]))
    st = mgr.state
    st.apply(A.plan_of(A.Load("a", st.tenants["a"].zoo.largest)))
    dead = 0
    st.devices.offline(dead)
    acts, counters, _, _ = drain_plan(st, dead)
    assert counters["downgrades"] == 1 and counters["unloads"] == 0
    assert counters["migrations"] >= 1
    assert st.simulate(A.ResidencyPlan(acts)) is None
    st.apply(A.ResidencyPlan(acts))
    st.devices.check_invariant()
    assert st.tenants["a"].loaded.size_mb == 200.0
    assert st.devices.weights["a"][dead] == 0.0
    assert sum(st.devices.weights["a"]) == pytest.approx(200.0)


def test_drain_unloads_when_nothing_fits():
    # Survivors are full at every variant size: the tenant goes cold.
    mgr = make_manager(budgets=(100.0,) * N_DEV,
                       a=_zoo("a", [400, 399]))
    st = mgr.state
    st.apply(A.plan_of(A.Load("a", st.tenants["a"].zoo.largest)))
    st.devices.offline(2)
    acts, counters, _, _ = drain_plan(st, 2)
    assert counters == {"migrations": 0, "downgrades": 0, "unloads": 1}
    assert st.simulate(A.ResidencyPlan(acts)) is None
    st.apply(A.ResidencyPlan(acts))
    st.devices.check_invariant()
    assert st.tenants["a"].loaded is None
    assert "a" not in st.devices.weights


def test_drain_evicts_kv_pages_homed_on_the_dead_chip():
    mgr = make_manager(budgets=(500.0,) * N_DEV)
    st = mgr.state
    st.kv_pool = KVPagePool(page_mb=1.0, device_pages=(4,) * N_DEV)
    st.apply(A.plan_of(A.Load("a", st.tenants["a"].zoo.largest)))
    # Pin sequences to known chips through the pool's device choice.
    st.apply(A.plan_of(A.ChargeKV("a", 4.0, seq=1, pages=4)))   # chip 0
    st.apply(A.plan_of(A.ChargeKV("a", 4.0, seq=2, pages=4)))   # chip 1
    dead = next(d for d in range(N_DEV)
                if any(pid in range(*_page_range(st.kv_pool, d))
                       for pid in st.kv_pool.tables["a"][2]))
    st.devices.offline(dead)
    st.kv_pool.offline_device(dead)
    acts, _, preempted, _ = drain_plan(st, dead)
    assert ("a", 2) in preempted or ("a", 1) in preempted
    assert st.simulate(A.ResidencyPlan(acts)) is None
    st.apply(A.ResidencyPlan(acts))
    st.kv_pool.check_invariant()
    assert st.kv_pool.seqs_on_device(dead) == []


def _page_range(pool, device):
    start = pool._starts[device]
    return start, start + pool.device_pages[device]


def test_apply_is_all_or_nothing_on_mid_plan_failure():
    mgr = make_manager(budgets=(500.0,) * N_DEV)
    st = mgr.state
    st.apply(A.plan_of(A.Load("a", st.tenants["a"].zoo.largest)))
    st.devices.offline(1)
    acts, _, _, _ = drain_plan(st, 1)
    # Poison the tail: a migration from an empty chip must fail after
    # the genuine drain actions already applied.
    poisoned = A.ResidencyPlan(acts + (A.MigrateShard("a", 1, 0, 999.0),))
    before = ({app: tuple(w) for app, w in st.devices.weights.items()},
              st.used_mb, st.devices.shards_migrated)
    assert st.simulate(poisoned) is not None
    with pytest.raises(A.PlanError):
        st.apply(poisoned)
    after = ({app: tuple(w) for app, w in st.devices.weights.items()},
             st.used_mb, st.devices.shards_migrated)
    assert before == after, "failed plan leaked partial state"
    # The genuine plan still applies cleanly afterwards and reconciles
    # the offline chip with its zeroed budget.
    st.apply(A.ResidencyPlan(acts))
    st.devices.check_invariant()


def test_rebalance_moves_surplus_back_toward_canonical():
    mgr = make_manager(budgets=(500.0,) * N_DEV)
    st = mgr.state
    st.apply(A.plan_of(A.Load("a", st.tenants["a"].zoo.largest)))
    st.devices.offline(1)
    acts, _, _, _ = drain_plan(st, 1)
    st.apply(A.ResidencyPlan(acts))
    st.devices.online(1)
    back = rebalance_plan(st, 1)
    assert back and all(isinstance(a, A.MigrateShard) and a.dst == 1
                        for a in back)
    assert st.simulate(A.ResidencyPlan(back)) is None
    st.apply(A.ResidencyPlan(back))
    st.devices.check_invariant()
    canon = st.devices.split("a", st.tenants["a"].loaded)
    assert st.devices.weights["a"] == pytest.approx(list(canon))


# ---------------------------------------------------------------------------
# The controller in the engine loop (declarative sim stack)
# ---------------------------------------------------------------------------
ELASTIC_TENANTS = ("tinyllama-1.1b", "mamba2-780m")
FAULT = FaultSpec(events=((3000.0, 3, "down"), (9000.0, 3, "up")))


def _run_elastic(fault, continuous=False, requests=30):
    srv = EdgeServer.build(ServingConfig(
        tenants=tuple(TenantSpec(n) for n in ELASTIC_TENANTS),
        executor="sim", policy="iws-bfe", delta_ms=750.0,
        batching=BatchingSpec(max_batch=4, window_ms=20.0,
                              continuous=continuous),
        loader=LoaderSpec(sharded=True, mesh_shape=(N_DEV,)),
        kv_headroom_shape=(2, 12), fault=fault))
    cfgs = {t.name: t.cfg for t in srv.tenants.values()}
    trace, _ = poisson_trace(cfgs, requests_per_app=requests,
                             mean_iat_ms=400.0, seed=7)
    stats = srv.engine.run_trace(trace)
    srv.engine.check_event_invariant()
    events = [(ev.t_ms, str(ev.kind), ev.app, ev.kv_mb, ev.used_mb,
               ev.device_mb, ev.device_budget_mb)
              for ev in srv.engine.events]
    srv.close()
    return stats, events


def test_faulted_run_holds_event_invariant_and_counts_the_cycle():
    stats, events = _run_elastic(FAULT)
    assert stats.chips_lost == 1 and stats.chips_recovered == 1
    assert stats.drain_migrations >= 1
    kinds = [e[1] for e in events]
    assert "chip_down" in kinds and "chip_up" in kinds
    assert "drain" in kinds
    assert kinds.index("chip_down") < kinds.index("drain") \
        < kinds.index("chip_up")
    # The chip_down event snapshots the pre-loss budget; every event
    # after it (until chip_up) shows chip 3 budget 0 and weights 0.
    down = next(i for i, e in enumerate(events) if e[1] == "chip_down")
    up = next(i for i, e in enumerate(events) if e[1] == "chip_up")
    assert events[down][6][3] > 0.0
    for t, kind, app, kv, used, dev, budget in events[down + 1:up]:
        if dev is not None:
            assert budget[3] == 0.0
            assert dev[3] <= A.EPS, (kind, app, dev)


def test_serving_continues_during_drain_and_recovery_restores_warm():
    faulted, _ = _run_elastic(FAULT)
    clean, _ = _run_elastic(None)
    assert faulted.requests == clean.requests, "no request lost to loss"
    assert faulted.weight_failures == 0
    # Recovery restores the pre-loss warm ratio (the drain plan rehomes
    # shards instead of cold-starting tenants; the cycle may cost at
    # most a bounded dip on this trace).
    assert faulted.warm_ratio >= clean.warm_ratio - 0.1
    assert clean.chips_lost is None  # elastic block absent without fault


def test_faulted_sim_run_is_bit_deterministic():
    s1, e1 = _run_elastic(FAULT)
    s2, e2 = _run_elastic(FAULT)
    assert s1 == s2
    assert e1 == e2


def test_continuous_engine_preempts_and_requeues_across_loss():
    stats, events = _run_elastic(FAULT, continuous=True)
    assert stats.chips_lost == 1 and stats.chips_recovered == 1
    assert stats.kv_pages_used == 0, "every sequence drained its pages"
    assert stats.kv_overrelease_mb == 0.0
    kinds = {e[1] for e in events}
    assert {"chip_down", "chip_up", "drain"} <= kinds


def test_stats_to_dict_carries_elastic_block_only_when_configured():
    faulted, _ = _run_elastic(FAULT)
    clean, _ = _run_elastic(None)
    d = faulted.to_dict()
    assert d["chips_lost"] == 1 and d["drain_downgrades"] >= 0
    assert "chips_lost" not in clean.to_dict()
    assert str(EventKind.CHIP_DOWN) == "chip_down"


# ---------------------------------------------------------------------------
# Physical placement (CI test-multidevice: 8 fake CPU devices)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (the CI test-multidevice "
                           "job forces 8 fake CPU devices)")
def test_set_variant_places_real_shards_matching_ledger_fractions():
    """``TenantRuntime.set_variant`` on an attached mesh must put real
    per-chip buffers whose byte fractions match the figure the
    DeviceLedger budgets with — and ``reshard_device_params`` must keep
    them on-mesh."""
    import jax.numpy as jnp

    from repro.configs import get_config
    from jax.sharding import AxisType
    from repro.models import transformer as T
    from repro.serving.server import TenantRuntime

    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = T.init_params(cfg, jax.random.key(0), jnp.float32)
    tr = TenantRuntime("tinyllama-1.1b", cfg, params, precisions=(16, 8))
    mesh = jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    tr.attach_mesh(mesh)
    frac = SH.weight_shard_fraction(
        cfg, SH.LogicalMesh({"data": 1, "model": 8}))
    for bits in (16, 8):
        tr.set_variant(tr.zoo.by_bits(bits))
        per_device = {d.id: 0 for d in mesh.devices.flatten()}
        total = 0
        for leaf in jax.tree.leaves(tr.device_params):
            total += leaf.nbytes
            for sh in leaf.addressable_shards:
                per_device[sh.device.id] += sh.data.nbytes
        assert len(per_device) == 8 and total > 0
        # Host trees are quantized (replicated scale/meta leaves), so
        # per-chip bytes track the unquantized ledger fraction only to a
        # few percent (int8's scales are a larger share of the tree).
        for dev, nbytes in per_device.items():
            assert nbytes / total == pytest.approx(frac, rel=0.06), \
                (bits, dev, nbytes, total)
    tr.reshard_device_params()  # recovery path: same mesh, still placed
    leaf = jax.tree.leaves(tr.device_params)[0]
    assert len(leaf.addressable_shards) == 8


# ---------------------------------------------------------------------------
# Degrade-aware drain ranking + re-promotion (cluster-tier satellites)
# ---------------------------------------------------------------------------
def _ranked_manager():
    """Two equal tenants on a mesh where only ONE dead-chip share can
    rehome intact: 240/chip budgets, both loaded at 400 (100/chip), so
    survivors hold 3x40 free — exactly one share.  Whoever drain_plan
    ranks first migrates intact; the other degrades."""
    mgr = make_manager(budgets=(240.0,) * N_DEV,
                       a=_zoo("a", [400, 200]), b=_zoo("b", [400, 200]))
    st = mgr.state
    st.apply(A.plan_of(A.Load("a", st.tenants["a"].zoo.largest)))
    st.apply(A.plan_of(A.Load("b", st.tenants["b"].zoo.largest)))
    return mgr


@pytest.mark.parametrize("busy,idle", [("a", "b"), ("b", "a")])
def test_drain_ranks_by_accuracy_times_readiness(busy, idle):
    mgr = _ranked_manager()
    st = mgr.state
    # The busy tenant's next request is imminent -> readiness 0 -> it
    # ranks last and eats the downgrade; the idle one (no prediction ->
    # pure accuracy) migrates intact.  Symmetric under the swap, so the
    # order is the score's doing, not the name tie-break.
    st.tenants[busy].predicted_next = 100.0
    st.tenants[idle].predicted_next = None
    st.devices.offline(3)
    acts, counters, _, _ = drain_plan(st, 3, now=100.0)
    assert counters["downgrades"] == 1
    assert st.simulate(A.ResidencyPlan(acts)) is None
    st.apply(A.ResidencyPlan(acts))
    st.devices.check_invariant()
    assert st.tenants[idle].loaded.size_mb == 400.0
    assert st.tenants[busy].loaded.size_mb == 200.0


def test_chip_up_repromotes_demoted_variant():
    # Tight mesh from the downgrade test: the drain demotes 480 -> 200;
    # the chip's return must restore the original variant and count it.
    mgr = make_manager(budgets=(130.0,) * N_DEV,
                       a=_zoo("a", [480, 200]))
    st = mgr.state
    st.apply(A.plan_of(A.Load("a", st.tenants["a"].zoo.largest)))
    ctl = ElasticController(
        FaultSpec(events=((10.0, 0, "down"), (50.0, 0, "up"))), mgr)
    ctl.poll(10.0)
    assert ctl.drain_downgrades == 1
    assert st.tenants["a"].loaded.size_mb == 200.0
    assert ctl.repromotions == 0
    ctl.poll(50.0)
    assert ctl.repromotions == 1
    assert st.tenants["a"].loaded.size_mb == 480.0
    assert not ctl._demoted
    st.devices.check_invariant()
    # Idempotent: a second cycle with nothing demoted re-promotes nothing.
    assert ctl.next_event_ms() == float("inf")


def test_repromotion_dropped_when_capacity_never_returns():
    # The demoting chip comes back while ANOTHER chip is still down, so
    # the original variant's canonical split (120/chip incl. the dead
    # one) cannot fit: the re-promotion is dropped (not retried forever)
    # and the tenant keeps its demoted variant.
    mgr = make_manager(budgets=(130.0,) * N_DEV,
                       a=_zoo("a", [480, 200]))
    st = mgr.state
    st.apply(A.plan_of(A.Load("a", st.tenants["a"].zoo.largest)))
    ctl = ElasticController(
        FaultSpec(events=((10.0, 0, "down"), (20.0, 1, "down"),
                          (50.0, 0, "up"))), mgr)
    ctl.poll(20.0)
    assert st.tenants["a"].loaded.size_mb == 200.0
    ctl.poll(50.0)
    assert ctl.repromotions == 0
    assert not ctl._demoted
    assert st.tenants["a"].loaded.size_mb == 200.0
    st.devices.check_invariant()


def test_fault_prob_validates_and_gates_the_schedule():
    with pytest.raises(ValueError, match="prob"):
        FaultSpec(prob=1.5)
    with pytest.raises(ValueError, match="prob"):
        FaultSpec(prob=-0.1)
    # prob=1.0: every scheduled down fires through the injector's
    # counter-based stream; prob~0: none do (the schedule is armed but
    # the dice never land).
    for prob, lost in ((1.0, 1), (1e-12, 0)):
        mgr = make_manager(budgets=(500.0,) * N_DEV)
        ctl = ElasticController(
            FaultSpec(events=((10.0, 1, "down"),), prob=prob, seed=5),
            mgr)
        ctl.poll(10.0)
        assert ctl.chips_lost == lost, prob


def test_stochastic_fault_run_is_bit_deterministic():
    spec = FaultSpec(events=FAULT.events, prob=0.5, seed=3)
    s1, e1 = _run_elastic(spec)
    s2, e2 = _run_elastic(spec)
    assert s1 == s2 and e1 == e2
    # And the deterministic path (prob=0) is unchanged by the knob:
    # FaultSpec(prob=0.0) equals the legacy spec field for field.
    assert FaultSpec(events=FAULT.events) == FAULT


def test_stats_carry_repromotions_counter():
    stats, _ = _run_elastic(FAULT)
    d = stats.to_dict()
    assert d["repromotions"] >= 0
