"""Benchmark harness — one entry per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run              # CI scale (CPU)
  PYTHONPATH=src python -m benchmarks.run --full       # paper scale
  PYTHONPATH=src python -m benchmarks.run --only tab2,fig8a

Prints ``name,us_per_call,derived`` CSV rows (common.emit) and saves the
structured results under results/bench_*.json.
"""
from __future__ import annotations

import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

from .common import save_json, scale   # noqa: E402


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (needs real hardware)")
    ap.add_argument("--only", default=None,
                    help="comma list: tab2,tab3,tab4,fig8a,fig8b,fig10a,"
                         "fig10b,kernels,encode,synth,serve,load,fed,"
                         "privacy,roofline")
    args = ap.parse_args()
    sc = scale(args.full)
    want = set(args.only.split(",")) if args.only else None

    def on(name):
        return want is None or name in want

    from . import (encode_bench, fed_bench, kernel_bench, load_bench,
                   privacy_bench, quality, roofline_table, serve_bench,
                   synth_bench, timing)

    print("name,us_per_call,derived")
    results = {}
    if on("tab2"):
        results["tab2"] = quality.table2_ideal_iid(sc)
    if on("tab3"):
        results["tab3"] = quality.table3_quantity_skew(sc)
    if on("tab4"):
        results["tab4"] = quality.table4_malicious_ablation(sc)
    if on("fig8a"):
        results["fig8a"] = timing.fig8a_phase_decomposition(sc)
    if on("fig8b"):
        results["fig8b"] = timing.fig8b_local_epochs(sc)
    if on("fig10a"):
        results["fig10a"] = timing.fig10a_client_scaling(sc)
    if on("fig10b"):
        results["fig10b"] = timing.fig10b_row_scaling(sc)
    if on("kernels"):
        kernel_bench.run_all()
    if on("encode"):
        results["encode"] = encode_bench.run_all()
    if on("synth"):
        results["synth"] = synth_bench.run_all()
    if on("serve"):
        results["serve"] = serve_bench.run_all()
    if on("load"):
        results["load"] = load_bench.run_all()
    if on("fed"):
        results["fed"] = fed_bench.run_all()
    if on("privacy"):
        results["privacy"] = privacy_bench.run_all(sc)
    if on("roofline"):
        roofline_table.run_all()
    save_json("results/bench_results.json", results)


if __name__ == "__main__":
    main()
