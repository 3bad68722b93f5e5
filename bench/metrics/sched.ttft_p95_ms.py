"""Scheduler (``launch/scheduler.py``): 95th percentile of due time ->
first observed token over the whole window, as ``window.py`` takes it
from the per-request stamps. A request waits here for a slot and for the
chunks of its prompt. It is no end-to-end metric because the host's
stalls of a second or more move it by more than any bound allows
(PERF.md). Moves ``itl_p95_ms``: the steps that carry a prefill chunk
set both."""


def read(run):
    return run.window.get("ttft_p95_ms")
