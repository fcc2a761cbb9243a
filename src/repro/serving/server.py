"""Multi-tenant serving runtime: Edge-MultiAI managing *real* JAX models.

This is where the paper's framework meets actual weights: each tenant is an
LM architecture with a real zoo (bf16 / int8 / int4 variants built by
``repro.quant``), "storage" is host RAM (numpy), "memory" is the device
budget tracked in MB of true buffer bytes, and load/evict callbacks move
weights with ``jax.device_put``.  The manager decides *which variant is
resident when*; serving runs true prefill/decode steps with whatever is
loaded (quantized variants run through the fused dequant matmul path).
"""
from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import actions as RA
from repro.core.manager import EdgeMultiAI
from repro.core.policies import Policy
from repro.core.model_zoo import ModelVariant, ModelZoo
from repro.core.predictor import RequestPredictor
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.quant.quantize import (params_nbytes, place_params,
                                  quantize_params)
from repro.serving.tracing import Tracer

MB = 1024 * 1024


@functools.partial(jax.jit, static_argnames=("cfg", "max_new", "max_len"))
def _generate_tokens(cfg: ModelConfig, params, prompts: jnp.ndarray, *,
                     max_new: int, max_len: int) -> jnp.ndarray:
    """Fused greedy decode: prefill + ``max_new − 1`` scanned decode
    steps in one XLA program (cache shapes are static — prefill pads to
    ``max_len``), so serving cost is one dispatch per batch instead of
    hundreds of eager ops per token."""
    with jax.named_scope("prefill"):
        logits, cache = T.prefill(cfg, params, {"tokens": prompts},
                                  max_len=max_len)
        tok = T.greedy_token(cfg, logits)

    def step(carry, _):
        prev, c = carry
        lg, c2 = T.decode_step(cfg, params, c, prev)
        # Keep the carry type stable: some archs (Mamba conv state)
        # decode in f32 while prefill emits the storage dtype.
        c2 = jax.tree.map(lambda new, old: new.astype(old.dtype), c2, c)
        nxt = T.greedy_token(cfg, lg)
        return (nxt, c2), nxt

    if max_new == 1:
        return tok[:, None]
    with jax.named_scope("decode"):
        _, rest = jax.lax.scan(step, (tok, cache), None,
                               length=max_new - 1)
    return jnp.concatenate([tok[:, None], jnp.moveaxis(rest, 0, 1)],
                           axis=1)


@dataclass
class ServeResult:
    app: str
    tokens: np.ndarray
    warm: bool
    failed: bool
    bits: Optional[int]
    latency_s: float


class TenantRuntime:
    """One application: config + host-side zoo + device-side loaded params.

    The production implementation of the engine's ``TenantExecutor``
    protocol — :meth:`execute` runs the real fused prefill+decode and is
    timed by wall clock (it returns no virtual service time)."""

    def __init__(self, name: str, cfg: ModelConfig, params,
                 precisions: Tuple[int, ...] = (16, 8),
                 predictor: Optional[RequestPredictor] = None):
        self.name = name
        self.cfg = cfg
        # Host "storage": every zoo variant, kept off-device as numpy.
        self.host: Dict[int, Any] = {}
        sizes: Dict[int, float] = {}
        for bits in precisions:
            variant = quantize_params(params, bits=bits, group=32)
            self.host[bits] = jax.tree.map(np.asarray, variant)
            sizes[bits] = params_nbytes(variant) / MB
        self.zoo = ModelZoo(
            app_name=name,
            variants=tuple(
                ModelVariant(
                    name=f"{name}-{b}bit", bits=b, size_mb=sizes[b],
                    accuracy={16: 100.0, 8: 97.0, 4: 85.0}.get(b, 90.0),
                    load_ms=max(sizes[b], 0.01))
                for b in precisions))
        self.device_params: Optional[Any] = None
        self.loaded_bits: Optional[int] = None
        self.predictor = predictor or RequestPredictor(context=8, hidden=16)
        self.tracer = Tracer(spans=False)  # the server's, from start()
        self._decode = None  # jitted per (bits)
        # Physical placement (sharded mesh): when a mesh is attached,
        # set_variant device_puts each leaf with a NamedSharding from
        # the real partition specs, so per-chip buffer bytes track the
        # DeviceLedger's shard fractions.  None = single-device asarray.
        self.mesh = None
        self._specs: Dict[int, Any] = {}  # per-bits PartitionSpec trees

    def attach_mesh(self, mesh) -> None:
        """Route weight placement through ``jax.device_put`` +
        ``NamedSharding`` on ``mesh``; a variant already resident is
        re-placed so its buffers match the specs immediately."""
        self.mesh = mesh
        self._specs.clear()
        if self.loaded_bits is not None:
            bits, self.loaded_bits = self.loaded_bits, None
            self.set_variant(self.zoo.by_bits(bits))

    def _spec_tree(self, bits: int):
        specs = self._specs.get(bits)
        if specs is None:
            from repro.distributed import sharding as SH
            specs = SH.param_specs(self.cfg, self.host[bits], self.mesh,
                                   fsdp=False)
            self._specs[bits] = specs
        return specs

    def reshard_device_params(self) -> None:
        """Elastic recovery: re-place the resident variant's buffers on
        the attached mesh after the ledger layout changed.  No-op off-mesh or when nothing is
        loaded."""
        if self.mesh is None or self.loaded_bits is None:
            return
        self.device_params = place_params(
            self.device_params, self._spec_tree(self.loaded_bits),
            self.mesh)

    # -- loader callback target -------------------------------------------
    def set_variant(self, variant: Optional[ModelVariant]) -> None:
        if variant is None:
            self.device_params = None
            self.loaded_bits = None
            return
        if variant.bits == self.loaded_bits:
            return
        host_tree = self.host[variant.bits]
        t0 = time.perf_counter_ns()
        if self.mesh is not None:
            self.device_params = place_params(
                host_tree, self._spec_tree(variant.bits), self.mesh)
        else:
            self.device_params = jax.tree.map(jnp.asarray, host_tree)
        self.loaded_bits = variant.bits
        # The copies run on after this returns: loader.h2d ends when the
        # new buffers are ready, measured off this thread.
        nbytes = sum(x.nbytes for x in jax.tree.leaves(host_tree))
        self.tracer.count("loader.h2d_bytes", nbytes)
        self.tracer.span_until_ready("loader.h2d", t0, self.device_params,
                                     tenant=self.name, bits=variant.bits,
                                     bytes=nbytes)

    def generate(self, prompts: np.ndarray, max_new: int,
                 extra: Optional[dict] = None) -> np.ndarray:
        """Greedy-decode ``max_new`` tokens for a batch of prompts.

        The no-extras path runs one fused, jitted prefill+scan-decode —
        the seed's eager per-op dispatch made every batch cost seconds
        on CPU, which both swamped the serving benchmark and hid the
        load/infer asymmetry the framework exists to exploit.  Batches
        with extra modality inputs keep the eager path."""
        assert self.device_params is not None, f"{self.name}: not loaded"
        cfg, params = self.cfg, self.device_params
        S = prompts.shape[1]
        if not extra:
            with self.tracer.span("executor.dispatch"):
                out = _generate_tokens(cfg, params, jnp.asarray(prompts),
                                       max_new=max_new, max_len=S + max_new)
            # The fetch holds any wait for the device, a weight copy
            # still in flight included.
            with self.tracer.span("executor.fetch"):
                return np.asarray(out)
        batch = {"tokens": jnp.asarray(prompts)}
        batch.update({k: jnp.asarray(v) for k, v in extra.items()})
        logits, cache = T.prefill(cfg, params, batch, max_len=S + max_new)
        toks = [T.greedy_token(cfg, logits)]
        for _ in range(max_new - 1):
            logits, cache = T.decode_step(cfg, params, cache, toks[-1])
            toks.append(T.greedy_token(cfg, logits))
        return np.stack([np.asarray(t) for t in toks], axis=1)

    # -- TenantExecutor protocol ------------------------------------------
    def execute(self, batch, extra: Optional[dict] = None
                ) -> Tuple[np.ndarray, Optional[float]]:
        """Run one batch; wall-clock timed (no virtual service time)."""
        return self.generate(batch.prompts, batch.max_new, extra), None


class EdgeServer:
    """The end-to-end system: Edge-MultiAI + real tenants + batching.

    This object is the *tenant registry and facade* (the engine's
    ``ServingHost``): ``serve()`` keeps its one-call API but delegates
    every admit/execute/retire cycle to the :class:`ServingEngine`, which
    also charges each batch's KV cache against the memory budget.

    The declarative front door is :meth:`build` — one call that resolves
    a :class:`~repro.serving.api.ServingConfig` into a fully wired,
    started server (tenants registered, policy resolved through the
    registry, loader and engine attached, budget derived).  The
    imperative ``__init__`` / ``register`` / ``start`` path underneath
    stays public for callers that need custom params or executors.
    """

    def __init__(self, budget_mb: float, policy="iws-bfe",
                 delta_ms: float = 500.0, max_batch: int = 8,
                 batch_window_ms: float = 0.0,
                 prefetch: bool = True, history_ms: float = 3000.0,
                 fallback="desperation",
                 sharded_mesh: Optional[Tuple[int, ...]] = None,
                 device_budget_mb: "Optional[float | Tuple[float, ...]]"
                 = None,
                 migrate: bool = True,
                 compress: Optional[str] = None,
                 adaptive_delta: bool = False,
                 continuous: bool = False,
                 kv_page_mb: float = 0.0,
                 fault=None,
                 audit: str = "full",
                 scheduler: str = "indexed"):
        self.tenants: Dict[str, Any] = {}  # TenantExecutor implementations
        self.budget_mb = budget_mb
        self.policy = policy
        self.fallback = fallback
        self.delta_ms = delta_ms
        self.history_ms = history_ms
        # Sharded multi-device serving: a mesh shape ((8,) = 8-way tensor
        # parallel) swaps the loader for the per-shard staging channel
        # and installs per-device budget ledgers; None = single device.
        self.sharded_mesh = (tuple(sharded_mesh)
                             if sharded_mesh is not None else None)
        # One float = uniform per-chip budgets; a tuple gives per-chip
        # (skewed) budgets — the regime cross-device victim migration
        # exists for.  None derives a uniform budget covering the worst
        # tenant's replication overhead.
        self.device_budget_mb = (tuple(device_budget_mb)
                                 if isinstance(device_budget_mb,
                                               (tuple, list))
                                 else device_budget_mb)
        self.migrate = migrate
        # Quantize-on-the-wire staging ("int8" or None): both loader
        # channels ship compressed bytes host→chip and dequantize on
        # land, shrinking every load's virtual transfer time by the
        # wire ratio while residency accounting is unchanged.
        self.compress = compress
        self.adaptive_delta = adaptive_delta
        # Continuous batching: requests join/leave the running decode
        # batch per step, and KV is charged page-granularly through a
        # KVPagePool sized at start().  kv_page_mb=0 derives the page
        # size from the largest tenant's 8-token decode cache.
        self.continuous = continuous
        self.kv_page_mb = kv_page_mb
        # Chip fault schedule (a serving.elastic.FaultSpec): start()
        # installs an ElasticController that fires chip-down drain plans
        # and chip-up rebalances on the engine clock.
        self.fault = fault
        # Engine fast-path knobs (see ServingEngine): audit level and
        # event-scheduling mode.  scheduler="indexed" also memoizes the
        # per-tenant prediction triggers here (the predictors' forward
        # pass re-materializes full arrival history on every call).
        self.audit = audit
        self.scheduler = scheduler
        # Spans and counters of the serving path (repro.serving.tracing):
        # spans at the "full" audit level, counters only at "counters".
        self.tracer = Tracer(spans=audit == "full")
        self._tpred_memo: Dict[str, Tuple[tuple, float]] = {}
        # Horizon before which a repeat of the last maintenance pass is
        # provably the identical no-op (every tenant took the indexed
        # fast skip).  The engine's continuous loop consults it — see
        # predict_and_preload; -inf means "never skip".
        self.maint_valid_ms = float("-inf")
        self.manager: Optional[EdgeMultiAI] = None
        self.engine = None  # type: Optional["ServingEngine"]
        self.loader = None  # type: Optional["BackgroundLoader"]
        self.elastic = None  # type: Optional["ElasticController"]
        self.physical_mesh = None  # real per-shard placement (sharded)
        self.prefetch = prefetch
        self.max_batch = max_batch
        self.batch_window_ms = batch_window_ms
        self.results: List[ServeResult] = []
        # Sim-executor builds set this: background fits complete before
        # the next prediction so virtual-time runs stay bit-deterministic
        # (a wall-clock fit racing the virtual clock would flip
        # predictions at a nondeterministic timestamp).
        self.sync_predictor_fits = False

    @classmethod
    def build(cls, config) -> "EdgeServer":
        """Resolve a :class:`repro.serving.api.ServingConfig` into a
        started server — the single wiring point every benchmark,
        example, and launcher goes through."""
        from repro.serving.api import build_server  # local: avoids cycle
        return build_server(config, cls=cls)

    def register(self, name: str, cfg: ModelConfig, params,
                 precisions: Tuple[int, ...] = (16, 8),
                 predictor: Optional[RequestPredictor] = None) -> None:
        """Register a real-model tenant (host-side zoo built from
        ``params`` by quantization)."""
        self.tenants[name] = TenantRuntime(name, cfg, params, precisions,
                                           predictor=predictor)

    def register_tenant(self, name: str, tenant) -> None:
        """Register any ``TenantExecutor`` implementation — e.g. the
        sim-time executor (:class:`repro.serving.api.SimTenant`) for
        deterministic, XLA-free tests."""
        self.tenants[name] = tenant

    def contention_budget(self, kv_headroom_mb: float = 0.0) -> float:
        """Standard contended budget over the registered tenants: every
        tenant resident at its smallest variant, plus room to upgrade the
        widest zoo to full precision, 5% slack, and explicit headroom for
        KV caches (which are charged against the budget too).  All-bf16
        residency stays impossible."""
        small = sum(t.zoo.smallest.size_mb for t in self.tenants.values())
        room = max(t.zoo.largest.size_mb - t.zoo.smallest.size_mb
                   for t in self.tenants.values())
        return (small + room) * 1.05 + kv_headroom_mb

    def start(self) -> None:
        from repro.serving.engine import ServingEngine
        from repro.serving.loader import BackgroundLoader

        zoos = {n: t.zoo for n, t in self.tenants.items()}
        tracer = self.tracer
        for t in self.tenants.values():
            if hasattr(t, "tracer"):
                t.tracer = tracer

        def stage(app: str, variant: Optional[ModelVariant]) -> None:
            tr = self.tenants[app]
            before = getattr(tr, "loaded_bits", None)
            tr.set_variant(variant)
            after = getattr(tr, "loaded_bits", None)
            if after != before:
                tracer.count(f"manager.moves.{_move_kind(before, after)}")

        def loader_cb(app: str, variant: Optional[ModelVariant]) -> None:
            # Synchronous (admission-path) weight moves ride the same
            # single-worker staging channel as background loads, so
            # device mutations land in the order their accounting did.
            with tracer.span("manager.enact", tenant=app,
                             bits=variant.bits if variant else None):
                if self.loader is not None:
                    self.loader.stage_sync(app, variant)
                else:
                    stage(app, variant)

        self.manager = EdgeMultiAI(
            zoos, self.budget_mb, policy=self.policy,
            delta_ms=self.delta_ms, history_ms=self.history_ms,
            loader=loader_cb, fallback=self.fallback,
            adaptive_delta=self.adaptive_delta, migrate=self.migrate)
        if self.sharded_mesh is not None:
            if not self.prefetch:
                raise ValueError(
                    "sharded serving requires the background loader "
                    "(prefetch=True): the reactive engine has no "
                    "staging channel to decompose per shard")
            self.manager.state.devices = self._device_ledger()
            from repro.serving.sharded_loader import ShardedLoaderChannel
            self.loader = ShardedLoaderChannel(
                self.manager,
                n_devices=self.manager.state.devices.n_devices,
                stage_fn=stage, migrate=self.migrate,
                compress=self.compress)
            self._attach_physical_mesh()
        else:
            self.loader = (BackgroundLoader(self.manager, stage_fn=stage,
                                            compress=self.compress)
                           if self.prefetch else None)
        if self.loader is not None:
            self.loader.tracer = tracer
            # Admission-path migrations land in the same audit trail as
            # loader-path ones (the engine mirrors loader events).
            self.manager.on_migrate = (
                lambda t, app, mb: self.loader._emit(t, "migrate",
                                                     app, mb))
        if self.continuous:
            self._install_kv_pool()
        self.engine = ServingEngine(
            self, max_batch=self.max_batch,
            batch_window_ms=self.batch_window_ms, loader=self.loader,
            continuous=self.continuous, audit=self.audit,
            scheduler=self.scheduler, tracer=tracer)
        if self.fault is not None:
            from repro.serving.elastic import ElasticController
            ctrl = ElasticController(self.fault, self.manager,
                                     loader=self.loader)
            # chip_down/chip_up/drain ride the loader's event hook into
            # the engine's audit trail, like migrations do.
            ctrl.on_event = (
                lambda t, kind, app, mb: self.loader._emit(t, kind,
                                                           app, mb))
            ctrl.on_reshard = self._reshard_tenant
            self.elastic = ctrl
            self.engine.elastic = ctrl

    def _attach_physical_mesh(self) -> None:
        """True per-shard placement for real-model tenants: build the
        physical mesh matching the ledger's logical one and route every
        ``set_variant`` through ``NamedSharding`` device_puts.  On a CPU
        backend with fewer devices than the mesh asks for (sim builds,
        CPU tests) placement is skipped and the ledger stays the
        accounting authority; on an accelerator, real tenants that cannot
        be placed are an error, never a silent single-device run."""
        shape = self.sharded_mesh
        n = math.prod(shape)
        if jax.device_count() < n:
            real = [a for a, t in self.tenants.items()
                    if hasattr(t, "attach_mesh")]
            if real and jax.default_backend() != "cpu":
                raise RuntimeError(
                    f"sharded mesh {shape} needs {n} devices, the "
                    f"{jax.default_backend()} backend has "
                    f"{jax.device_count()}: cannot place {real}")
            return
        dims = (1, shape[0]) if len(shape) == 1 else tuple(shape)
        self.physical_mesh = jax.make_mesh(
            dims, ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        for tr in self.tenants.values():
            if hasattr(tr, "attach_mesh"):
                tr.attach_mesh(self.physical_mesh)

    def _reshard_tenant(self, app: str) -> None:
        """Elastic-plan hook: re-place a tenant's resident buffers after
        a drain/rebalance changed its layout (real runtimes on a mesh;
        no-op for sim executors)."""
        tr = self.tenants[app]
        if hasattr(tr, "reshard_device_params"):
            tr.reshard_device_params()

    def _install_kv_pool(self) -> None:
        """Size and attach the paged-KV pool for continuous batching.

        Page size defaults to the largest tenant's 8-token decode cache
        (so one page ~ one short burst of decoding for the heaviest
        model); the whole budget is divided into pages because KV shares
        the same ledger as weights — a page the pool holds is memory a
        weight load cannot claim, and simulate/apply validates both the
        same way.  Under a sharded mesh the pages are partitioned across
        chips proportional to each chip's ledger budget."""
        from repro.core.memory_state import KVPagePool
        from repro.serving.engine import kv_cache_mb

        page_mb = self.kv_page_mb or max(
            kv_cache_mb(t.cfg, 1, 8) for t in self.tenants.values())
        n_pages = int(self.budget_mb // page_mb)
        if n_pages < 1:
            raise ValueError(
                f"kv_page_mb={page_mb:.1f} exceeds the whole budget "
                f"({self.budget_mb:.1f} MB): no page fits")
        dev = self.manager.state.devices
        if dev is not None:
            total = sum(dev.budgets_mb)
            counts = [int(n_pages * b / total) for b in dev.budgets_mb]
            counts[0] += n_pages - sum(counts)  # remainder to chip 0
            self.manager.state.kv_pool = KVPagePool(
                page_mb, device_pages=tuple(counts))
        else:
            self.manager.state.kv_pool = KVPagePool(page_mb, n_pages)

    def _device_ledger(self):
        """Per-device budgets + spec-derived shard splits for the mesh.

        Each tenant's per-chip fraction comes from the real partition
        rules (``weight_shard_fraction`` — replicated leaves included),
        so the ledger budgets what a chip actually holds.  The default
        per-device budget covers the worst tenant's replication overhead
        over the even ``budget/n`` split: anything fundable globally is
        then fundable per-chip, and tighter (explicit) budgets surface
        as clean whole-load failures in the sharded loader."""
        from repro.core.memory_state import DeviceLedger
        from repro.distributed import sharding as SH

        mesh = SH.serving_mesh(self.sharded_mesh)
        n = mesh.size
        fracs = {name: SH.weight_shard_fraction(t.cfg, mesh)
                 for name, t in self.tenants.items()}
        if isinstance(self.device_budget_mb, tuple):
            # Per-chip (skewed) budgets: the migration regime — one
            # tight chip while neighbors keep slack.
            if len(self.device_budget_mb) != n:
                raise ValueError(
                    f"{len(self.device_budget_mb)} device budgets for "
                    f"a {n}-chip mesh")
            budgets = self.device_budget_mb
        else:
            per_dev = (self.device_budget_mb
                       if self.device_budget_mb is not None
                       else self.budget_mb / n * max(
                           f * n for f in fracs.values()))
            budgets = (per_dev,) * n
        return DeviceLedger(
            budgets,
            split_fn=lambda app, v: SH.variant_shard_mb(
                v.size_mb, n, fracs[app]))

    def close(self) -> None:
        """Drain and shut down the background staging worker; what the
        tracer recorded stays readable."""
        if self.loader is not None:
            self.loader.close()
        self.tracer.close()

    # ------------------------------------------------------------------
    def _predict_time(self, name: str, predictor) -> float:
        """``predictor.predict_next_time()``, memoized on the indexed
        scheduler.  The prediction is a pure function of the predictor's
        observable state — arrival history (appends only), trained
        params (change only when ``fits`` increments), and the last
        arrival — so caching on that key returns the identical float
        while skipping the O(history) forward pass the linear path runs
        once per tenant per maintenance pass."""
        if self.scheduler != "indexed":
            with self.tracer.span("predictor.predict", tenant=name):
                return predictor.predict_next_time()
        key = (len(predictor.history), predictor.fits,
               predictor.last_time)
        hit = self._tpred_memo.get(name)
        if hit is not None and hit[0] == key:
            return hit[1]
        with self.tracer.span("predictor.predict", tenant=name):
            t = predictor.predict_next_time()
        self._tpred_memo[name] = (key, t)
        return t

    def predict_and_preload(self, now_ms: float) -> None:
        """Drive the RNN request predictors -> proactive loads.

        With the background loader attached, predicted-next tenants get
        their iWS-BFE-chosen variant *enqueued* for staging instead of
        loaded on the caller's thread, and prefetches whose predicted
        window expired without a request are cancelled (releasing their
        in-flight memory claim).  Without a loader this is the PR-1
        synchronous proactive load.

        This is also where the RNNs get *trained*: a predictor with
        enough fresh inter-arrival history (``fit_due``) is handed to
        the loader's background fit worker — the live path runs on the
        mean-gap fallback until the first fit lands, then on the
        trained RNN, and never blocks on training."""
        # Indexed fast path: when a tenant's memoized prediction is
        # current and no fit is due, its pass can only end in "do
        # nothing" — prove it with cheap reads and skip the planner.
        # Soundness: (a) the prediction is rewritten so state matches
        # the linear pass even when the memo was filled by
        # ``next_prefetch_trigger``; (b) Δ is recomputed fresh when
        # adaptive (it drifts with arrival residuals); (c) outside
        # [t_pred−Δ−θ, t_pred+Δ] nothing fires, and inside it a tenant
        # with queued requests is demand-loaded, never prefetched —
        # both exactly the linear conditions; (d) for the
        # un-overridden base ``plan_prefetch`` hook the eviction-free
        # surplus decision is replicated verbatim against a pass-level
        # ``free_mb`` (one budget sum per pass, dropped whenever a
        # full pass may have mutated the state).  A custom policy hook
        # gets no structural credit — the full pass runs so its plan
        # is actually consulted.  This loop is the engine's hottest
        # code (once per tenant per event-loop iteration), hence the
        # hoisted locals and the inlined window/fit/hook checks.
        mgr = self.manager
        fast = self.scheduler == "indexed" and self.loader is not None
        free_mb = None  # one budget sum per pass; reset on mutation
        # Skip horizon accounting: while every tenant takes the fast
        # skip, the pass decisions can only flip at the earliest
        # still-ahead window opening (t_pred − Δ − θ) — tenants already
        # in or past their window stay no-ops until an arrival, fit, or
        # memory mutation, all of which reset the engine's clean flag.
        valid = float("inf")
        all_skipped = fast
        if fast:
            memo = self._tpred_memo
            tstates = mgr.state.tenants
            queues = (self.engine.batcher.queues
                      if self.engine is not None else None)
            delta_const = None if mgr.adaptive_delta else mgr.delta
            policy = mgr.policy
            base_hook = (policy is not None and
                         type(policy).plan_prefetch is Policy.plan_prefetch)
        for name, tr in self.tenants.items():
            if fast:
                p = tr.predictor
                hit = memo.get(name)
                n_hist = len(p.history)
                if (hit is not None
                        and hit[0] == (n_hist, p.fits, p.last_time)
                        # fit_due is False while the history is short
                        # (n < max(min_fit_samples, context+2)); only
                        # past that must the refit cadence be asked.
                        and (n_hist < p.min_fit_samples
                             or n_hist < p.context + 2
                             or not p.fit_due())):
                    t_pred = hit[1]
                    t = tstates[name]
                    t.predicted_next = t_pred  # == set_prediction
                    delta = (delta_const if delta_const is not None
                             else mgr.delta_for(name))
                    largest = t.zoo.variants[0]  # zoo sorts desc
                    start = t_pred - delta - largest.load_ms
                    if now_ms < start:  # ahead of the window
                        if start < valid:
                            valid = start
                        continue
                    if now_ms > t_pred + delta:  # window passed
                        continue
                    if queues is not None and queues.get(name):
                        continue  # queued: demand path, not prefetch
                    if policy is None:
                        continue  # manager.plan_prefetch is None
                    if base_hook:
                        if (t.loaded is largest
                                or t.inflight_mb > 0.0):
                            continue  # the hook's two early outs
                        if free_mb is None:
                            free_mb = mgr.state.free_mb
                        cur = t.loaded.size_mb if t.loaded else 0.0
                        planless = True
                        for v in t.zoo.variants:  # mirror the hook
                            if t.loaded is not None \
                                    and v.size_mb <= cur:
                                break
                            if v.size_mb - cur <= free_mb:
                                planless = False  # hook would plan
                                break
                        if planless:
                            continue
                    # In-window, unqueued, and the hook might plan:
                    # fall through to the full pass below.
            # The full pass may mutate the memory state (stage a load,
            # reserve a claim): drop the pass-level free_mb cache, and
            # give the engine no skip credit for this pass.
            all_skipped = False
            free_mb = None
            if self.loader is not None and tr.predictor.fit_due():
                with self.tracer.span("predictor.fit_submit", tenant=name):
                    fut = self.loader.submit_fit(tr.predictor)
                    if fut is not None and self.sync_predictor_fits:
                        fut.result()  # lands at this exact virtual instant
            t_pred = self._predict_time(name, tr.predictor)
            self.manager.set_prediction(name, t_pred)
            theta = tr.zoo.largest.load_ms
            # Per-tenant Δ: the configured constant, or the residual-
            # adapted window when ``adaptive_delta`` is on.
            delta = self.manager.delta_for(name)
            in_window = (t_pred - delta - theta <= now_ms
                         <= t_pred + delta)
            if self.loader is None:
                if t_pred - delta - theta <= now_ms:
                    self.manager.proactive_load(name, now_ms)
            elif in_window:
                # Only prefetch inside the predicted window: past its
                # far edge the prediction is already wrong, and a stale-
                # cancelled prefetch must not immediately re-enqueue.
                if (self.engine is None
                        or self.engine.batcher.queued(name) == 0):
                    # A tenant with requests already queued is not a
                    # prefetch target — its load is demand-triggered
                    # (the engine stages it and admits the batch cold);
                    # calling it a prefetch would count a request that
                    # waited out the transfer as a warm start.
                    with self.tracer.span("manager.plan_prefetch",
                                          tenant=name):
                        plan = self.manager.plan_prefetch(name, now_ms)
                        if plan is not None:
                            self.loader.execute(
                                RA.ResidencyPlan(
                                    RA.procure_actions(plan, staged=True)),
                                now_ms, predicted_ms=t_pred)
        self.maint_valid_ms = valid if all_skipped else float("-inf")
        if (self.loader is not None and self.engine is not None
                and self.loader.inflight):  # nothing staged: no-op
            # Per-tenant Δ so staleness agrees with the (possibly
            # adaptive) window that justified the prefetch.
            with self.tracer.span("loader.cancel_stale"):
                self.loader.cancel_stale(
                    now_ms, self.manager.delta_for,
                    has_queued=lambda a: self.engine.batcher.queued(a) > 0)

    def next_prefetch_trigger(self, now_ms: float) -> float:
        """Earliest *future* t_pred − Δ − θ across tenants that could use
        a proactive load: the engine's idle path wakes here, otherwise a
        drained queue would sleep straight through its prefetch window
        and every load would degenerate to demand-time."""
        out = float("inf")
        for name, tr in self.tenants.items():
            t = self.manager.state.tenants[name]
            if t.loaded is t.zoo.largest or t.inflight_mb > 0.0:
                continue
            trig = (self._predict_time(name, tr.predictor)
                    - self.manager.delta_for(name)
                    - tr.zoo.largest.load_ms)
            if now_ms < trig < out:
                out = trig
        return out

    def serve(self, app: str, prompts: np.ndarray, max_new: int = 8,
              now_ms: Optional[float] = None,
              extra: Optional[dict] = None) -> ServeResult:
        """Synchronous one-batch API, delegating to the engine: the batch
        is admitted with its KV cache charged against the budget and the
        charge released on retirement."""
        assert self.manager is not None, "call start() first"
        from repro.serving.batcher import Batch, Request

        now_ms = time.monotonic() * 1e3 if now_ms is None else now_ms
        tr = self.tenants[app]
        prompts = np.asarray(prompts, np.int32)
        if len(prompts) == 0:  # nothing to admit, nothing to charge
            return self._record(ServeResult(
                app, np.zeros((0, max_new), np.int32), False, False,
                tr.loaded_bits, 0.0))
        tr.predictor.observe_request(now_ms)
        reqs = [self.engine.batcher.assign(
            Request(app=app, prompt=prompts[i], max_new=max_new,
                    arrival_ms=now_ms)) for i in range(len(prompts))]
        batch = Batch(app, reqs, prompts, max_new)
        results, service_ms, toks = self.engine.execute_batch(
            batch, now_ms, extra=extra)
        warm = results[0].warm
        if toks is None:
            return self._record(ServeResult(
                app, np.zeros((len(prompts), 0), np.int32), warm, True,
                None, service_ms / 1e3))
        return self._record(ServeResult(
            app, toks, warm, False, tr.loaded_bits, service_ms / 1e3))

    def _record(self, r: ServeResult) -> ServeResult:
        self.results.append(r)
        return r

    # ------------------------------------------------------------------
    def stats(self) -> "ServingStats":
        """The engine's typed :class:`~repro.serving.stats.ServingStats`
        with the server-level gauges filled in (residency, predictor
        fits, adaptive windows, device ledger).
        All request counts are per *request* (the engine's unit), so the
        top-level ratios and the per-tenant breakdown describe the same
        population — a multi-row serve() batch counts once per row."""
        import dataclasses

        from repro.serving.stats import ServingStats

        eng_results = self.engine.results if self.engine else []
        if not eng_results:  # serve() always routes through the engine
            return ServingStats()
        n = len(eng_results)
        extra: dict = {
            "resident_mb": self.manager.state.used_mb,
            "weights_mb": self.manager.state.weights_mb,
            "kv_mb": self.manager.state.kv_mb,
            "requests": n,
            "warm_ratio": sum(r.warm for r in eng_results) / n,
            "fail_ratio": sum(r.failed for r in eng_results) / n,
            # Completed background predictor fits (the hit rate itself
            # comes from the engine view).
            "predictor_fits": sum(
                getattr(t.predictor, "fits", 0)
                for t in self.tenants.values()),
        }
        if self.adaptive_delta:
            # The residual-adapted prediction windows, per tenant.
            extra["delta_ms"] = {name: self.manager.delta_for(name)
                                 for name in self.tenants}
        if self.manager.state.devices is not None:
            led = self.manager.state.devices
            extra["device_used_mb"] = led.device_used()
            extra["device_budget_mb"] = led.budgets_mb
        return dataclasses.replace(self.engine.stats(), **extra)


def _move_kind(before: Optional[int], after: Optional[int]) -> str:
    """What a change of resident precision did: load, unload, upgrade or
    downgrade."""
    if before is None:
        return "load"
    if after is None:
        return "unload"
    return "upgrade" if after > before else "downgrade"
