"""The device a run measures, and its published peaks.

A run that finds no accelerator, or fewer chips than the cell asks for,
fails before it measures anything: a number taken on the CPU is never
reported under a device metric's name.
"""
from __future__ import annotations

from .spec import BENCH_DIR, load_json


class NoAccelerator(RuntimeError):
    pass


def require_chips(jax, chips: int) -> dict:
    """The device record for the result line; raises ``NoAccelerator``
    when JAX sees no accelerator or too few of them."""
    devs = jax.devices()
    platform = devs[0].platform
    if platform == "cpu":
        raise NoAccelerator("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks(device_kind: str) -> dict:
    """Peak rates of ``device_kind`` from ``bench/peaks.json``; a device
    that is not in the table is an error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
