"""Model step (``models/dense.py`` through ``launch/executor.py``): share
of the traced window's device-busy time spent in XLA ``copy`` operations,
from the profiler trace. In the serving steps these are the relayouts of
the layers' KV pools that XLA places around the Pallas kernels.
Operations whose name less its numeric suffix is ``copy`` count
(``tracefile.base_name``); asynchronous ``copy-start`` / ``copy-done``
pairs and fusions do not. ``None`` without a trace. Moves
``itl_p50_ms``: in a decode step these copies grow with the KV pool."""

import tracefile


def read(run):
    if run.events is None or run.busy_s <= 0:
        return None
    copies = sum(t for name, t in tracefile.op_times(run.events).items()
                 if tracefile.base_name(name) == "copy")
    return 100.0 * copies / run.busy_s
