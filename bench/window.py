"""End-to-end metrics of the measured window from per-request stamps.

A request's record is its submit time and the host time at which each of
its tokens was observed (the ``ServeEngine.step()`` return that delivered
it). Every number below is taken over all requests and all the time of
the window [t0, t1]:

- ``output_tok_per_s``: tokens observed in the window over its length;
- ``ttft_p95_ms``: 95th percentile, over the requests whose first token
  was observed in the window, of submit -> first token;
- ``itl_p50_ms`` / ``itl_p95_ms``: median and 95th percentile of every
  gap between two consecutive tokens of one request, both observed in
  the window.
"""
from __future__ import annotations

import numpy as np


def _in(t: float, t0: float, t1: float) -> bool:
    return t0 <= t <= t1


def output_tokens(records: dict, t0: float, t1: float) -> int:
    return sum(sum(1 for t in r["times"] if _in(t, t0, t1))
               for r in records.values())


def ttfts(records: dict, t0: float, t1: float) -> list:
    return [r["times"][0] - r["submit"] for r in records.values()
            if r["times"] and _in(r["times"][0], t0, t1)]


def itls(records: dict, t0: float, t1: float) -> list:
    out = []
    for r in records.values():
        ts = [t for t in r["times"] if _in(t, t0, t1)]
        out.extend(np.diff(ts).tolist())
    return out


def metrics(records: dict, t0: float, t1: float) -> dict:
    """-> {metric name: value} for the window, or raises if the window
    holds no first token or no gap (nothing to report)."""
    tt, gaps = ttfts(records, t0, t1), itls(records, t0, t1)
    if not tt or not gaps:
        raise RuntimeError(f"the window holds {len(tt)} first tokens and "
                           f"{len(gaps)} token gaps")
    return {"output_tok_per_s": output_tokens(records, t0, t1) / (t1 - t0),
            "ttft_p95_ms": 1e3 * float(np.percentile(tt, 95)),
            "itl_p50_ms": 1e3 * float(np.percentile(gaps, 50)),
            "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95))}


def counts(records: dict, t0: float, t1: float) -> dict:
    """How many samples stand behind each percentile."""
    gaps, tt = itls(records, t0, t1), ttfts(records, t0, t1)
    return {"first_tokens": len(tt), "gaps": len(gaps),
            "ttft_ms_p50_p90_p95_max": [1e3 * float(np.percentile(tt, q))
                                        for q in (50, 90, 95, 100)]
            if tt else [],
            "longest_gap_ms": 1e3 * max(gaps, default=0.0),
            "tokens": output_tokens(records, t0, t1)}
