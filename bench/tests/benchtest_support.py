"""Runs a cell of the harness on the CPU at the program's reduced sizes,
skipping only the look for a chip."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
from harness import serve, spec  # noqa: E402
from harness.traffic import make_source  # noqa: E402

# Made-up rates for CPU runs: no device metric is read from them.
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11}


def fixture(name):
    return json.load(open(os.path.join(HERE, "fixtures", name)))


def tiny_cell(config="tiny.contended.json", traffic="tiny_switch.json"):
    b = spec.load_json(spec.ROOT / "BENCHMARK.json")
    e2e = spec._metrics(b["end_to_end"])
    layer = spec._metrics(b["per_layer"])
    return spec.Cell("tiny", 1, fixture(config), fixture(traffic),
                     tuple(e2e), tuple(layer))


def start(cell, trace=False):
    s = serve.Session(cell, trace=trace)
    s.build()
    s.warm_programs()
    return s


def drive(s, cell, seed, seconds=3.0):
    tr = cell.traffic
    warm_ms = tr["warmup_s"] * 1e3
    source = make_source(tr, {t["name"]: t["model"]["vocab_size"]
                              for t in cell.config["tenants"]},
                         seed, warm_ms + seconds * 1e3)
    s.drive(source, warm_ms, seconds * 1e3, tr["drain_s"] * 1e3)


def run_tiny(cell, seed, seconds=3.0, trace=False):
    s = start(cell, trace)
    drive(s, cell, seed, seconds)
    return run.measure(s, cell, seed, trace, CPU_PEAKS), s
