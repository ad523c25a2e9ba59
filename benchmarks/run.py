"""Benchmark harness — one module per paper table/figure.

Prints ``name,...,us_per_call,derived`` CSV rows (one block per figure).

  PYTHONPATH=src python -m benchmarks.run [--only fig9]
"""
import argparse
import sys
import traceback

from benchmarks import (bench_arch_energy, bench_attention, bench_chaos,
                        bench_design_grid, bench_drift_traces,
                        bench_energy_exact, bench_energy_relaxed,
                        bench_eta_esnr, bench_explorer,
                        bench_noise_tolerance, bench_output_range,
                        bench_roofline, bench_scenarios, bench_serving,
                        bench_td_vmm, bench_tdc, bench_tdmac_cell,
                        bench_throughput_area)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "fig3c": bench_eta_esnr,
    "fig4b": bench_tdmac_cell,
    "fig6": bench_output_range,
    "fig7": bench_tdc,
    "fig9": bench_energy_exact,
    "fig10": bench_noise_tolerance,
    "fig11": bench_energy_relaxed,
    "fig12": bench_throughput_area,
    "grid": bench_design_grid,
    "scenarios": bench_scenarios,
    "explorer": bench_explorer,
    "td_vmm": bench_td_vmm,
    "attention": bench_attention,
    "serving": bench_serving,
    "chaos": bench_chaos,
    "drift": bench_drift_traces,
    "roofline": bench_roofline,
    "arch_energy": bench_arch_energy,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite keys (default: all)")
    args = ap.parse_args()
    enable_compile_cache()
    keys = args.only.split(",") if args.only else list(SUITES)
    failed = []
    for k in keys:
        mod = SUITES[k]
        print(f"# === {k} ({mod.__name__}) ===")
        try:
            for row in mod.run():
                print(row)
        except Exception as e:  # noqa: BLE001
            failed.append(k)
            print(f"{k},ERROR,{e!r}")
            traceback.print_exc()
    if failed:
        print(f"# FAILED suites: {failed}")
        sys.exit(1)
    print("# all benchmark suites completed")


if __name__ == "__main__":
    main()
