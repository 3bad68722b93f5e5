"""Scheduler (``launch/scheduler.py``): mean share of the step budget
``max_batch_tokens`` that a step's packed tokens fill, over the steps
dispatched in the traced window, from what ``ServeEngine.step()``
returns. Moves ``itl_p95_ms``: the budget sets how much prompt a step
that carries a chunk takes on."""


def read(run):
    fills = [s["packed_tokens"] / run.max_batch_tokens for s in run.steps
             if s.get("packed_tokens")]
    return 100.0 * sum(fills) / len(fills) if fills else None
