"""Host spans of the serving loop, on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler session runs (``jax.profiler.trace``, ``start_trace``, or an
XProf capture against ``jax.profiler.start_server``) each span lands on
the trace's ``/host:CPU`` plane, on the same clock as the device's
``XLA Ops`` line, so every instant the device idles can be given to the
host work that held it. With no session a span records nothing and costs
about a microsecond. ``meta`` shows beside the span in the trace viewer;
the event's name stays ``name``.

``SPANS`` lists every span the serving loop opens, outermost first, with
what each brackets; ``src/repro/launch/README.md`` says how to capture
them.
"""
from __future__ import annotations

import functools

import jax

SPANS = (
    "engine.step",        # one ServeEngine.step() cycle; step=<step_count>
    "sched.plan",         # TokenBudgetScheduler.plan: choose the cycle's rows
    "sched.pack",         # .pack: the ragged step's packed arrays
    "sched.pack_decode",  # .pack_decode: the fast path's slot-major arrays
    "exec.step",          # RaggedExecutor.step: dispatch the ragged step
    "exec.decode_step",   # .decode_step: dispatch the pure-decode step
    "engine.fetch",       # wait for a step's tokens and copy them to the
    #                       host; fetched=<cycle that dispatched the step>
    "sched.observe",      # .observe: append the tokens, retire, roll back
)


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name, **meta)


def traced(name: str):
    """Decorator: the function's every call runs inside ``span(name)``."""
    return functools.partial(jax.profiler.annotate_function, name=name)
