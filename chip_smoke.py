#!/usr/bin/env python3
"""On-chip smoke run: two published-width tenants served on one TPU.

    python3 chip_smoke.py             # one chip: mamba2-780m + granite-3-2b
    python3 chip_smoke.py --chips 4   # granite-3-2b on a 4-device mesh

One chip: ``EdgeServer.build(ServingConfig(..., executor="real"))`` builds
mamba2-780m (Pallas ``ssd_scan`` prefill; ``quant_matmul`` in every int8
projection) and granite-3-2b (dense GQA) at published widths from seeded
random weights, under the derived contended budget, in which all-bf16
residency does not fit.  Twelve requests alternate between the tenants on
a regular schedule, so the iWS-BFE manager loads, upgrades and downgrades
variants by itself.  The run fails unless every request is answered,
every (tenant, precision) pair serves a batch, the manager moved weights
after ``start()``, each served variant's prefill logits from the
Pallas path agree with the jnp reference within its ``LOGIT_LIMIT`` while
a planted lower-precision kernel misses it, and each Pallas kernel alone
agrees with its f32 oracle within ``KERNEL_TOL``.

``--chips 4`` runs only the sharded path: granite-3-2b served from a
``LoaderSpec(sharded=True, mesh_shape=(4,))`` server, checked against the
same weights on one device within ``MESH_LIMIT``.

Everything runs in this one process.  Without a TPU the script exits
non-zero before printing any result.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MAMBA, GRANITE = "mamba2-780m", "granite-3-2b"
PROMPT_LEN = 16
MAX_NEW = 16
# One request every 5 s of virtual time, alternating tenants: each tenant
# then arrives on a regular 10 s period, which the arrival predictors
# learn by the third request, so later arrivals are "expected" and
# iWS-BFE upgrades the requester while downgrading the other tenant.
SCHEDULE = tuple((5000.0 * i, (GRANITE, MAMBA)[i % 2]) for i in range(12))
# The logit checks run the served prompt through a copy of the served
# variant cut to its first DEPTH layers, at published widths, under
# "highest" matmul precision.  At full depth a random-weight model
# compounds one layer's roundoff: mamba2-780m's bf16 logits move by
# 9.4e-2 at 48 layers, 7.0e-3 at 2, between two correct programs.
DEPTH = 2
# Prefill logits, max|Δ| / max|ref| over the real vocabulary, Pallas
# path against the jnp reference, per (tenant, bits).  In interpret mode
# on CPU at this depth every variant agrees to at most 1.4e-6 (f32: the
# kernels compute the reference's math).  On the TPU the int8 variants
# compute in f32 (their embedding stays f32) and agree as closely,
# 5.4e-7 and 6.1e-7; their limit is about 30x that and 250x below the
# controls (5.2e-3, 6.2e-3).  mamba2-780m bf16 rounds ``ssd_scan``'s
# output to bf16 where the reference's XLA fusion rounds elsewhere: one
# bf16 step (2^-8) per layer, 7.4e-3 at 2 layers on the TPU; its limit
# is 2x that and 2.5x below the control (3.8e-2).
# granite-3-2b bf16 runs no Pallas kernel, so both programs are the same
# and must agree exactly.  The control runs the Pallas program with each
# kernel's output rounded one precision step lower (``lower_precision``);
# the run fails unless every control misses its limit.
LOGIT_LIMIT = {(MAMBA, 16): 1.5e-2, (MAMBA, 8): 2e-5,
               (GRANITE, 16): 0.0, (GRANITE, 8): 2e-5}
# --chips 4: the mesh run against one device, both on the Pallas path, per
# bits.  int8 runs the kernel per shard and sums f32 partials: 3.7e-7 to
# 4.2e-7 on a 4-device CPU mesh at this depth and published widths.
# bf16 all-reduces bf16 partial sums that one device keeps in f32, one
# bf16 rounding per row-parallel product: 8.9e-3 to 1.13e-2 there over
# three prompts.  Each limit is about 4x (bf16) and 50x (int8) those;
# a shard's lost or misplaced partial sum misses by order one.
MESH_LIMIT = {16: 4e-2, 8: 2e-5}
# Each served kernel alone at the served widths, random inputs, against
# the f32 oracle (``ref.quant_matmul``, the sequential ``ref.ssd_scan``)
# under "highest" matmul precision: one layer, so no depth amplifies the
# roundoff.  In interpret mode on CPU the matmul agrees to 2.5e-6 and the
# scan's state to 2.3e-6; the scan's bf16 output is off by its own
# rounding, 3.0e-3.  The TPU's f32 matmul passes add roundings of about
# 2^-9; the tolerance is about 3x the sum.
KERNEL_TOL = 1e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def say(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


class CompileLog:
    """Counts backend compiles and sums trace+lower+compile seconds."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.n = 0
        self.secs = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.secs += duration
            self.n += event == self.EVENTS[-1]


def lower_precision(fn, calls: list):
    """``fn`` with its (first) output rounded one precision step below
    the dtype it returns: f32 to bf16's 8 significant bits, bf16 to fp8
    e4m3's 4.  A planted kernel fault for the logit control; ``calls``
    counts the traced calls."""
    import jax

    def wrapped(*args, **kw):
        calls.append(fn)
        out = fn(*args, **kw)
        y, rest = (out[0], out[1:]) if isinstance(out, tuple) else (out, ())
        y = jax.lax.reduce_precision(
            y, exponent_bits=8, mantissa_bits=7 if y.dtype.itemsize > 2
            else 3)
        return (y, *rest) if rest else y

    return wrapped


def prefill_logits_fn(impl: str, control_calls: list | None = None):
    """A jitted last-token prefill whose kernels are fixed to ``impl``
    while it traces, under "highest" matmul precision.  One function per
    impl, so each has its own compiled program: flipping ``set_impl``
    after a compile would reuse the first trace.  A ``control_calls``
    list plants ``lower_precision`` on every kernel and collects the
    kernels traced."""
    import jax

    from repro.kernels import ops
    from repro.models import transformer as T

    kernels = {"quant_matmul": ops.quant_matmul, "ssd_scan": ops.ssd_scan}

    def run(cfg, params, tokens):
        ops.set_impl(impl)
        if control_calls is not None:
            for name, fn in kernels.items():
                setattr(ops, name, lower_precision(fn, control_calls))
        try:
            with jax.default_matmul_precision("highest"):
                logits, _ = T.prefill(cfg, params, {"tokens": tokens},
                                      max_len=tokens.shape[1])
        finally:
            ops.set_impl(None)
            for name, fn in kernels.items():
                setattr(ops, name, fn)
        return logits[..., :cfg.vocab_size]

    return jax.jit(run, static_argnums=0)


def depth_cut(cfg, params):
    """``cfg``/``params`` cut to their first ``DEPTH`` layers (every
    per-layer leaf is stacked on a leading layer axis)."""
    import dataclasses

    import jax

    layers = jax.tree.map(lambda a: a[:DEPTH], params["layers"])
    return (dataclasses.replace(cfg, num_layers=DEPTH),
            {**params, "layers": layers})


def max_rel_error(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return math.inf
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def kernel_errors(jax) -> dict:
    """Pallas kernels vs their f32 oracles at the served widths: the
    int8 matmul at mamba2-780m's ``ssm_in`` (prefill) and granite-3-2b's
    ``wd`` (decode), and ``ssd_scan`` at mamba2-780m's heads for a
    one-chunk and a three-chunk prompt."""
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    ks = iter(jax.random.split(jax.random.key(0), 16))

    def normal(shape, scale=1.0):
        return jax.random.normal(next(ks), shape) * scale

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    errs = {}
    for name, (M, K, N) in (("mamba2-780m ssm_in", (16, 1536, 6448)),
                            ("granite-3-2b wd", (1, 8192, 2048))):
        x = normal((M, K)).astype(jnp.bfloat16)
        wq, s = ref.quantize_weights(normal((K, N)), bits=8, group=32)
        got = jax.jit(lambda *a: ops.quant_matmul(
            *a, out_dtype=jnp.float32))(x, wq, s)
        errs[f"quant_matmul {name} M={M}"] = max_rel_error(got, oracle(
            lambda *a: ref.quant_matmul(*a, out_dtype=jnp.float32),
            x, wq, s))
    H, P, N = 48, 64, 128
    for S in (16, 600):
        args = (normal((1, S, H, P), 0.5).astype(jnp.bfloat16),
                jax.nn.softplus(normal((1, S, H))).astype(jnp.bfloat16),
                -jnp.exp(normal((H,), 0.5)),
                normal((1, S, 1, N), 0.3).astype(jnp.bfloat16),
                normal((1, S, 1, N), 0.3).astype(jnp.bfloat16),
                normal((H,)))
        y, state = jax.jit(lambda *a: ops.ssd_scan(
            *a, chunk=256, return_state=True))(*args)
        y_ref, state_ref = oracle(lambda *a: ref.ssd_scan(
            *(t.astype(jnp.float32) for t in a), return_state=True), *args)
        errs[f"ssd_scan S={S} y"] = max_rel_error(y, y_ref)
        errs[f"ssd_scan S={S} state"] = max_rel_error(state, state_ref)
    return errs


def serve_schedule(srv, prompts, log: CompileLog):
    """Serve ``SCHEDULE`` through ``srv`` (batch size 1), driving the
    arrival predictors before each request.  Returns one record per
    request: the result, the host-clock seconds of the serve call (it
    returns host tokens, so the device work is done), the compiles it
    ran and how many tenants changed variant."""
    records = []
    for t_ms, app in SCHEDULE:
        before = {a: tr.loaded_bits for a, tr in srv.tenants.items()}
        srv.predict_and_preload(t_ms)
        n0 = log.n
        t0 = time.perf_counter()
        r = srv.serve(app, prompts[app], max_new=MAX_NEW, now_ms=t_ms)
        wall = time.perf_counter() - t0
        after = {a: tr.loaded_bits for a, tr in srv.tenants.items()}
        records.append(dict(
            t_ms=t_ms, app=app, result=r, wall_s=wall,
            compiles=log.n - n0,
            moved=sum(after[a] is not None and after[a] != before[a]
                      for a in after)))
    return records


def say_memory(jax) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    say(bytes_limit=stats.get("bytes_limit"),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"))


def one_chip(jax, log: CompileLog) -> None:
    import numpy as np

    from repro.kernels import ops
    from repro.serving.api import (BatchingSpec, EdgeServer, ServingConfig,
                                   TenantSpec)

    t0 = time.perf_counter()
    srv = EdgeServer.build(ServingConfig(
        tenants=(TenantSpec(MAMBA, reduced=False),
                 TenantSpec(GRANITE, reduced=False)),
        batching=BatchingSpec(max_batch=1),
        kv_headroom_shape=(1, PROMPT_LEN + MAX_NEW),
        executor="real"))
    say(phase="build", seconds=f"{time.perf_counter() - t0:.1f}",
        budget_mb=f"{srv.budget_mb:.1f}")
    for name, tr in srv.tenants.items():
        say(tenant=name, **{f"int{v.bits}_mb" if v.bits < 16
                            else "bf16_mb": f"{v.size_mb:.1f}"
                            for v in tr.zoo.variants})
    if ops.resolve_impl() != "pallas":
        fail(f"kernel impl resolves to {ops.resolve_impl()!r}, not pallas")

    rng = np.random.default_rng(0)
    prompts = {a: rng.integers(0, tr.cfg.vocab_size, (1, PROMPT_LEN),
                               dtype=np.int32)
               for a, tr in srv.tenants.items()}
    n0, s0 = log.n, log.secs
    records = serve_schedule(srv, prompts, log)
    for i, rec in enumerate(records):
        r = rec["result"]
        say(request=i, t_ms=int(rec["t_ms"]), app=rec["app"], bits=r.bits,
            warm=r.warm, failed=r.failed, moved=rec["moved"],
            compiles=rec["compiles"], wall_ms=f"{rec['wall_s'] * 1e3:.2f}",
            exec_ms=f"{r.latency_s * 1e3:.2f}")
    say(serving_compiles=log.n - n0,
        serving_compile_s=f"{log.secs - s0:.1f}")
    # Latency after warm-up: the requests whose program was already
    # compiled (a serve that moved weights includes the transfer).
    for app, bits in sorted({(rc["app"], rc["result"].bits)
                             for rc in records}):
        warm = [rc for rc in records if rc["app"] == app
                and rc["result"].bits == bits and not rc["compiles"]]
        say(after_warmup=app, bits=bits, requests=len(warm),
            wall_ms=",".join(f"{rc['wall_s'] * 1e3:.2f}" for rc in warm),
            moved=",".join(str(rc["moved"]) for rc in warm))
    say_memory(jax)  # the serving peak: the checks below run after it

    n0, s0 = log.n, log.secs
    kernel_errs = kernel_errors(jax)
    for name, err in kernel_errs.items():
        say(kernel_error=name.replace(" ", "_"), max_rel=f"{err:.3e}",
            tol=KERNEL_TOL)
    # Each served variant's weights as the loader places them (from host
    # storage), cut to DEPTH layers, on the prompt it was served.
    pallas_fn, ref_fn = (prefill_logits_fn("pallas"),
                         prefill_logits_fn("reference"))
    checks = {}
    for app, bits in sorted({(rc["app"], rc["result"].bits)
                             for rc in records if not rc["result"].failed}):
        tr = srv.tenants[app]
        cfg, params = depth_cut(tr.cfg, tr.host[bits])
        params = jax.device_put(params, jax.devices()[0])
        tok = jax.numpy.asarray(prompts[app])
        want = ref_fn(cfg, params, tok)
        calls: list = []
        control_fn = prefill_logits_fn("pallas", calls)
        err = max_rel_error(pallas_fn(cfg, params, tok), want)
        control = max_rel_error(control_fn(cfg, params, tok), want)
        checks[(app, bits)] = (err, control if calls else None)
        say(logit_error=app, bits=bits, layers=DEPTH, max_rel=f"{err:.3e}",
            limit=LOGIT_LIMIT[(app, bits)],
            control=f"{control:.3e}" if calls else "no_kernel")
        del params
    say(check_compiles=log.n - n0, check_compile_s=f"{log.secs - s0:.1f}")
    srv.close()

    results = [rc["result"] for rc in records]
    served = {(rc["app"], rc["result"].bits) for rc in records
              if not rc["result"].failed}
    want = {(a, b) for a in (MAMBA, GRANITE) for b in (16, 8)}
    if len(results) != len(SCHEDULE) or any(r.failed for r in results):
        fail(f"{sum(r.failed for r in results)} of {len(SCHEDULE)} "
             "requests failed")
    for rc in records:
        toks = rc["result"].tokens
        vocab = srv.tenants[rc["app"]].cfg.vocab_size
        if toks.shape != (1, MAX_NEW) or toks.min() < 0 \
                or toks.max() >= vocab:
            fail(f"bad tokens from {rc['app']}: shape {toks.shape}")
    if served != want:
        fail(f"served pairs {sorted(served)}, want {sorted(want)}")
    if not sum(rc["moved"] for rc in records):
        fail("no variant load or downgrade after start()")
    if set(checks) != want:
        fail(f"logits checked for {sorted(checks)}, want {sorted(want)}")
    bad = {k: e for k, (e, _) in checks.items() if not e <= LOGIT_LIMIT[k]}
    if bad:
        fail(f"prefill logits off the reference: {bad}")
    # A variant that runs a kernel must fail its limit once the kernel is
    # one precision step worse; one that runs none matched exactly above.
    blind = {k: c for k, (_, c) in checks.items()
             if c is not None and not c > LOGIT_LIMIT[k]}
    if blind:
        fail(f"lower-precision controls pass the logit limit: {blind}")
    bad = {k: e for k, e in kernel_errs.items() if not e <= KERNEL_TOL}
    if bad:
        fail(f"kernels off their f32 oracles: {bad}")


def four_chips(jax, log: CompileLog) -> None:
    import numpy as np

    from repro.serving.api import (BatchingSpec, EdgeServer, LoaderSpec,
                                   ServingConfig, TenantSpec)
    from repro.serving.server import _generate_tokens

    t0 = time.perf_counter()
    srv = EdgeServer.build(ServingConfig(
        tenants=(TenantSpec(GRANITE, reduced=False),),
        batching=BatchingSpec(max_batch=1),
        loader=LoaderSpec(sharded=True, mesh_shape=(4,)),
        kv_headroom_shape=(1, PROMPT_LEN + MAX_NEW),
        executor="real"))
    say(phase="build", seconds=f"{time.perf_counter() - t0:.1f}",
        budget_mb=f"{srv.budget_mb:.1f}")
    tr = srv.tenants[GRANITE]
    mesh = srv.physical_mesh
    if mesh is None or mesh.size != 4 or tr.mesh is not mesh:
        fail(f"physical mesh not attached: {mesh}")
    prompt = np.random.default_rng(0).integers(
        0, tr.cfg.vocab_size, (1, PROMPT_LEN), dtype=np.int32)
    tok = jax.numpy.asarray(prompt)
    logits_fn = prefill_logits_fn("pallas")
    one = jax.devices()[0]
    n0, s0 = log.n, log.secs
    t1 = time.perf_counter()
    r = srv.serve(GRANITE, prompt, max_new=MAX_NEW, now_ms=0.0)
    say(request=0, app=GRANITE, bits=r.bits, failed=r.failed,
        wall_ms=f"{(time.perf_counter() - t1) * 1e3:.1f}")
    if r.failed:
        fail("sharded request failed")
    # The served variant, then the other one placed on the same mesh:
    # the int8 one runs quant_matmul per shard.
    checked = []
    for bits in (r.bits, *(v.bits for v in tr.zoo.variants
                           if v.bits != r.bits)):
        tr.set_variant(tr.zoo.by_bits(bits))
        size = tr.zoo.by_bits(bits).size_mb * 2 ** 20
        per_dev = {d.id: 0 for d in mesh.devices.flat}
        for leaf in jax.tree.leaves(tr.device_params):
            for sh in leaf.addressable_shards:
                per_dev[sh.device.id] += sh.data.nbytes
        fracs = [b / size for b in per_dev.values()]
        toks = tr.generate(prompt, MAX_NEW)
        single = jax.device_put(tr.host[bits], one)
        ref_toks = np.asarray(_generate_tokens(
            tr.cfg, single, tok, max_new=MAX_NEW,
            max_len=PROMPT_LEN + MAX_NEW))
        # Logits at DEPTH layers: the placed weights cut on their
        # (unsharded) layer axis, against the same cut on one device.
        cfg, placed = depth_cut(tr.cfg, tr.device_params)
        err = max_rel_error(logits_fn(cfg, placed, tok),
                            logits_fn(*depth_cut(tr.cfg, single), tok))
        del single, placed
        say(mesh_variant=bits, variant_mb=f"{size / 2 ** 20:.1f}",
            per_device_mb=",".join(f"{b / 2 ** 20:.1f}"
                                    for b in per_dev.values()),
            logit_error_vs_one_device=f"{err:.3e}", layers=DEPTH,
            limit=MESH_LIMIT[bits],
            token_agreement=f"{np.mean(ref_toks == toks):.3f}")
        checked.append((bits, fracs, err))
    say(compiles=log.n - n0, compile_s=f"{log.secs - s0:.1f}")
    say_memory(jax)
    srv.close()
    for bits, fracs, err in checked:
        # A quarter of the variant, plus the leaves the partition rules
        # replicate (norms, and row-parallel int8 scales).
        if not all(0.25 <= f <= 0.3 for f in fracs) \
                or max(fracs) - min(fracs) > 0.01:
            fail(f"{bits}-bit per-device weight fractions {fracs} "
                 "are not ~1/4")
        if not err <= MESH_LIMIT[bits]:
            fail(f"{bits}-bit sharded logits off one device: {err:.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        fail(f"no TPU: JAX backend is {jax.default_backend()!r}")
    if len(jax.devices()) < args.chips:
        fail(f"--chips {args.chips} but {len(jax.devices())} devices")
    from repro.launch.compile_cache import enable_compile_cache

    from repro.kernels import ops

    dev = jax.devices()[0]
    say(backend=jax.default_backend(), impl=ops.resolve_impl(),
        device_kind=dev.device_kind, device_count=len(jax.devices()),
        compile_cache=enable_compile_cache())
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(jax, log)
    say(total_s=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
