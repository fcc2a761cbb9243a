"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Invoke as
``PYTHONPATH=src python -m benchmarks.run`` (all) or with module names:
``python -m benchmarks.run fig5_6_8_policies roofline``.

``python -m benchmarks.run --list`` prints the available benchmark
names with what each measures and the row-name prefixes it emits —
useful for picking which rows to gate in
``benchmarks/BENCH_serving_baseline.json``.
"""
import inspect
import re
import sys
import traceback

from benchmarks import (engine_scale, fig4_multitenancy, fig5_6_8_policies,
                        fig7_pareto, fig9_10_fairness, perf_compare,
                        quant_fidelity, roofline, serving_throughput,
                        table1_load_vs_infer)
from repro.launch.compile_cache import enable_compile_cache

MODULES = {
    "table1_load_vs_infer": table1_load_vs_infer,
    "fig4_multitenancy": fig4_multitenancy,
    "fig5_6_8_policies": fig5_6_8_policies,
    "fig7_pareto": fig7_pareto,
    "fig9_10_fairness": fig9_10_fairness,
    "quant_fidelity": quant_fidelity,
    "serving_throughput": serving_throughput,
    "engine_scale": engine_scale,
    "roofline": roofline,
    "perf_compare": perf_compare,
}


def row_prefixes(module) -> list:
    """Row-name prefixes a benchmark emits, scraped from its source.

    Matches the first argument of each ``emit("...")`` call; f-string
    names are truncated at the first ``{`` so dynamic suffixes (policy
    names, model ids) collapse into one prefix.
    """
    src = inspect.getsource(module)
    names = re.findall(r'emit\(\s*f?"([^"{]+)', src)
    seen: dict = {}
    for n in names:
        seen.setdefault(n.rstrip("/"), None)
    return list(seen)


def list_benchmarks() -> None:
    """Print each benchmark name, its one-line summary, and the row
    prefixes it emits (the names gated by the baseline JSON)."""
    for name, module in MODULES.items():
        summary = (module.__doc__ or "").strip().splitlines()[0]
        print(f"{name}: {summary}")
        for prefix in row_prefixes(module):
            print(f"    {prefix}")


def main() -> None:
    if "--list" in sys.argv[1:]:
        list_benchmarks()
        return
    names = sys.argv[1:] or list(MODULES)
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            MODULES[name].run()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
