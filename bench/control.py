"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 --seconds 50

For each seed, in this one process, a whole run of the cell
(``run.run_cell``: set-up, the open loop's window of ``--seconds`` at the
cell's own load, the same sample of finished requests a run compares)
with the control in the program's place: the token that the reference
computed with 4-bit activations (the next precision below the
configuration's 8) puts first at each served position, read under the
8-bit reference. Each seed prints one JSON line: the control's widest
gap, which the run compares with the limit, whether the run came out
correct (it must not), and the program's own widest gap over the same
positions. The lower reading of the limit is the largest program gap
over the seeds, the upper one the smallest control gap (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import run

    bm, cell, conf, mix = run.load(args.workload)
    devs = run.chips(cell)
    if devs is None:
        return 2
    import check
    import registry

    peaks = registry.peaks(devs[0].device_kind)
    lim = check.limits(cell["name"])
    for seed in (int(s) for s in args.seeds.split(",")):
        got = {}
        out = run.run_cell(
            argparse.Namespace(seed=seed, seconds=args.seconds, trace=0),
            bm, cell, conf, mix, devs, peaks, lim, control=True,
            readings=got)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "control": got["control"],
                          "program": got["program"],
                          "limit": lim["max_gap"], "failed": got["failed"],
                          "requests": got["requests"],
                          "served_tokens": got["served_tokens"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
