"""Benchmark harness — one entry per paper table/figure + kernels +
roofline readout. Prints ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--only fig2_sqnr_approx,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback

SUITES = ["fig2_sqnr_approx", "fig3_bitwidth", "fig4_concentration",
          "fig5_alignment", "fig6_sqnr_layers", "table1_e2e",
          "kernels_bench", "dryrun_readout"]


def dryrun_readout() -> None:
    """Summarize cached dry-run/roofline artifacts as CSV rows."""
    import json
    import os
    from benchmarks.common import emit
    path = "results/dryrun.json"
    if not os.path.exists(path):
        emit("dryrun_readout", 0.0, "no results/dryrun.json (run "
             "python -m repro.launch.dryrun --all first)")
        return
    data = json.load(open(path))
    ok = [k for k, v in data.items() if "flops" in v]
    skip = [k for k, v in data.items() if "skip" in v]
    fail = [k for k, v in data.items() if "error" in v]
    emit("dryrun_cells", 0.0,
         f"ok={len(ok)} skip={len(skip)} fail={len(fail)}")
    mems = sorted((v["memory"]["argument_size_in_bytes"]
                   + v["memory"]["temp_size_in_bytes"], k)
                  for k, v in data.items() if "flops" in v)
    if mems:
        b, k = mems[-1]
        emit("dryrun_peak_mem", 0.0, f"{k}={b/2**30:.1f}GiB/dev")
    rl = "results/roofline.json"
    if os.path.exists(rl):
        rows = [r for r in json.load(open(rl)) if "error" not in r]
        if rows:
            import numpy as np
            fracs = sorted((r["roofline_fraction"], r["cell"])
                           for r in rows)
            emit("roofline_worst", 0.0,
                 f"{fracs[0][1]}={100*fracs[0][0]:.1f}%")
            emit("roofline_best", 0.0,
                 f"{fracs[-1][1]}={100*fracs[-1][0]:.1f}%")
            emit("roofline_median", 0.0,
                 f"{100*float(np.median([f for f, _ in fracs])):.1f}%")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()
    suites = args.only.split(",") if args.only else SUITES
    print("name,us_per_call,derived")
    failures = 0
    for name in suites:
        try:
            if name == "dryrun_readout":
                dryrun_readout()
                continue
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},nan,ERROR", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
