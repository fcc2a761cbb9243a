"""Whether what the timed path produced is correct.

Three things are compared, each against a limit of its own:

* ``unanswered``: requests due in the window that never got a reply
  within the drain (limit 0).  A request the engine answered with a
  failure is no fault here; it counts in ``failed``.
* ``budget_overrun``: 1 when the engine's own audit
  (``check_event_invariant(budget_mb)``) finds an event at which the
  weights, caches and in-flight loads exceed the budget (limit 0).
* ``gap.<tenant>.<variant>``: for every (tenant, variant) the window
  served, a sample drawn from the seed of the requests it finished, the
  longest among them.  The reference runs once over each served row
  (its prompt as the batch padded it, then the served tokens) and reads,
  at each served token, how far that token's logit lies below the
  reference's best.  The number is the widest such gap.  Greedy tokens of
  a correct program sit at or near the reference's best; a wrong token
  sits far below it.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Tuple

import numpy as np

VARIANT = {16: "bf16", 8: "int8"}
# The control: the reference one precision step below the variant.
BELOW = {"bf16": "fp8", "int8": "int4"}


def sample(session, seed: int, per_variant: int
           ) -> Dict[Tuple[str, int], list]:
    """Per served (tenant, bits): requests of the window, drawn from the
    seed, with the longest served row first."""
    rng = np.random.default_rng(seed)
    groups: Dict[Tuple[str, int], list] = {}
    for r in session.requests:
        if r.in_window and not r.failed and r.batch is not None:
            b = session.batches[r.batch]
            groups.setdefault((r.app, b.bits), []).append(r)
    out = {}
    for key in sorted(groups, key=str):
        rs = groups[key]
        width = np.array([session.batches[r.batch].prompts.shape[1]
                          for r in rs])
        longest = int(rng.choice(np.flatnonzero(width == width.max())))
        rest = [i for i in rng.permutation(len(rs)) if i != longest]
        out[key] = [rs[i] for i in [longest] + rest[:per_variant - 1]]
    return out


def rows_of(session, reqs) -> tuple:
    """Reference input rows (right-padded), and the positions whose
    logits predict each served token."""
    seqs, served, rows, cols = [], [], [], []
    for i, r in enumerate(reqs):
        b = session.batches[r.batch]
        p, t = b.prompts[r.row], b.tokens[r.row, :r.max_new]
        seqs.append(np.concatenate([p, t[:-1]]))
        served.append(t)
        rows.append(np.full(len(t), i))
        cols.append(len(p) - 1 + np.arange(len(t)))
    T = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), T), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    return (tokens, np.concatenate(rows), np.concatenate(cols),
            np.concatenate(served))


def widest_gap(ref, chosen) -> float:
    """Largest ``max(ref) - ref[chosen]`` over the positions."""
    import jax.numpy as jnp
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, jnp.asarray(chosen)[:, None], -1)[:, 0]
    return float(jnp.max(best - got))


def gap_readings(session, groups, tenants: Dict[str, dict],
                 control: bool = False) -> Dict[str, dict]:
    """``{"gap.<tenant>.<variant>": {"value", ["control"]}}``."""
    import jax.numpy as jnp

    from reference import models
    out = {}
    for (app, bits), reqs in groups.items():
        t = tenants[app]
        variant = VARIANT[bits]
        tokens, rows, cols, served = rows_of(session, reqs)
        ref = models.logits(t["model"], t["seed"], variant, tokens, rows,
                            cols)
        rec = {"value": widest_gap(ref, served), "tokens": len(served)}
        if control:
            low = models.logits(t["model"], t["seed"], BELOW[variant],
                                tokens, rows, cols)
            rec["control"] = widest_gap(ref, jnp.argmax(low, axis=-1))
        out[f"gap.{app}.{variant}"] = rec
        del ref
    return out


def invariant_overrun(session) -> int:
    try:
        session.srv.engine.check_event_invariant(
            session.cell.config["budget_mb"])
    except AssertionError as e:
        print(f"budget audit: {e}", file=sys.stderr, flush=True)
        return 1
    return 0


def verdict(session, readings: Dict[str, dict], limits: Dict[str, float],
            key: str = "value") -> List[dict]:
    """Every number compared, with its limit and whether it holds.  With
    ``key="control"`` the control's gap readings stand in the program's
    place: the lower-precision reference's first choices as the served
    tokens, at each position of the same prompts and tokens."""
    unanswered = sum(1 for r in session.requests
                     if r.in_window and not r.resolved)
    checks = [dict(name="unanswered", value=unanswered, limit=0),
              dict(name="budget_overrun", value=invariant_overrun(session),
                   limit=0)]
    for name, rec in readings.items():
        checks.append(dict(name=name, value=rec[key],
                           limit=limits.get(name)))
    for c in checks:
        c["ok"] = c["limit"] is not None and c["value"] <= c["limit"]
    return checks


def compare(session, seed: int, limits: Dict[str, float],
            per_variant: int) -> List[dict]:
    """The program's verdict on the window the seed drove."""
    tenants = {t["name"]: t for t in session.cell.config["tenants"]}
    groups = sample(session, seed, per_variant)
    return verdict(session, gap_readings(session, groups, tenants), limits)


def all_ok(checks: List[dict]) -> bool:
    return all(c["ok"] for c in checks)
