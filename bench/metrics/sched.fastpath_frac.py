"""Scheduler: share of the traced window's dispatches that went to the
pure-decode fast path (``exec.decode_step``) rather than the unified
ragged step (``exec.step``), counted by wrapping both. Moves
``itl_p50_ms``."""


def read(run):
    n = run.counts["unified"] + run.counts["decode"]
    return 100.0 * run.counts["decode"] / n if n else None
