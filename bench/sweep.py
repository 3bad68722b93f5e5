"""Find the highest arrival rate a cell's engine sustains, on the chip.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4,5 --seconds 30 \
        --seed 1 [--set n_slots=16 ...]

One process: the cell's set-up once, then for each rate, in increasing
order, the cell's traffic at that rate through the open loop (drained,
reset, settled as in a run) and a window of ``--seconds``. Each rate
prints one JSON line: the window's end-to-end metrics, the requests due
and retired in it, and the backlog at its close: requests due by then
that had no first token yet. A rate is sustained while the backlog stays
within a tenth of the requests due in the window (and at most 2 where
that is less); the sweep stops after the first rate that is not. A
cell's mix offers about four fifths of the highest sustained rate.
``--set`` overrides a key of the mix, to compare slot counts or budgets
before one is written into the mix.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON", help="override a key of the mix "
                    "(n_slots, max_batch_tokens, ...)")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import run

    _, cell, conf, mix = run.load(args.workload)
    for kv in args.set:
        key, val = kv.split("=", 1)
        mix = dict(mix, **{key: json.loads(val)})
    if run.chips(cell) is None:
        return 2
    import window
    from traffic import Traffic

    run.compile_cache()
    srv = run.Served(conf, mix, args.seed)
    srv.warm_up()
    for rate in sorted(float(r) for r in args.rates.split(",")):
        t0 = srv.start(Traffic(dict(mix, rate=rate), args.seed,
                               srv.cfg.vocab))
        t1 = t0 + args.seconds
        srv.loop.run_until(t1)
        recs = srv.loop.records.values()
        backlog = sum(1 for r in recs if r["submit"] <= t1
                      and not (r["times"] and r["times"][0] <= t1))
        due = sum(1 for r in recs if t0 <= r["submit"] <= t1)
        sustained = backlog <= max(2, due // 10)
        print(json.dumps({
            "rate": rate, "n_slots": srv.kw["n_slots"],
            "max_batch_tokens": srv.kw["max_batch_tokens"],
            "sustained": sustained, "due": due,
            "retired": sum(1 for r in recs if t0 <= r.get("done", -1) <= t1),
            "backlog": backlog, **window.metrics(srv.loop.records, t0, t1),
            **window.counts(srv.loop.records, t0, t1)}), flush=True)
        if not sustained:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
