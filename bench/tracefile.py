"""Reduction of a profiler trace to the numbers the benchmark reports.

``load`` reads the newest ``.xplane.pb`` under a trace directory into
plain lists: the device's operation events (the ``XLA Ops`` line of the
first TPU plane), the host spans the benchmark annotated, and the traced
window (the ``bench.window`` span). Everything after that works on those
lists, so it is checked on a small recorded fixture
(``tests/data/trace_fixture.json``):

- ``busy_s``: the union of the device's operation intervals inside the
  window;
- ``idle_gaps``: the window's device-idle time, each instant attributed
  to the innermost host span open at that instant, summed per span name;
- ``op_times``: device self seconds per operation name as the trace
  prints it: an operation's events on the ``XLA Ops`` line nest (a
  ``while`` holds its body's operations), so each event's time less that
  of the events directly inside it;
- ``kernel_seconds``: device seconds of the operations whose name, less
  its numeric suffix, is one of a kernel's names.

The device line names each event by its HLO instruction
(``%fused_cat_gemv_w4.3 = f32[8,6144]{...} custom-call(...)``); ``load``
keeps the instruction's name alone (``fused_cat_gemv_w4.3``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
NO_SPAN = "(no host span)"


def load(logdir: str, span_names) -> dict:
    """-> {"device": [[name, start_ns, end_ns], ...], "host": [...],
    "window": [start_ns, end_ns]} from the newest trace under logdir."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(files[-1])
    dev = pd.find_plane_with_name(DEVICE_PLANE)
    if dev is None:
        raise RuntimeError(f"the trace has no {DEVICE_PLANE} plane: "
                           f"{[p.name for p in pd.planes]}")
    lines = {ln.name: ln for ln in dev.lines}
    if OPS_LINE not in lines:
        raise RuntimeError(f"{DEVICE_PLANE} has no {OPS_LINE!r} line: "
                           f"{sorted(lines)}")
    device = [[op_name(e.name), e.start_ns, e.end_ns]
              for e in lines[OPS_LINE].events]
    host, window = [], None
    wanted = set(span_names) | {WINDOW}
    for ln in pd.find_plane_with_name(HOST_PLANE).lines:
        for e in ln.events:
            if e.name == WINDOW:
                window = [e.start_ns, e.end_ns]
            elif e.name in wanted:
                host.append([e.name, e.start_ns, e.end_ns])
    if window is None:
        raise RuntimeError(f"the trace has no {WINDOW!r} span")
    return {"device": device, "host": host, "window": window}


def op_name(hlo: str) -> str:
    """``%copy.80 = f32[...] copy(...)`` -> ``copy.80``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _clip(intervals, lo, hi):
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def busy_intervals(events: dict) -> list:
    """Merged device-busy intervals inside the window."""
    lo, hi = events["window"]
    ivs = sorted(_clip(((s, e) for _, s, e in events["device"]), lo, hi))
    merged = []
    for s, e in ivs:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_s(events: dict) -> float:
    return sum(e - s for s, e in busy_intervals(events)) * 1e-9


def window_s(events: dict) -> float:
    lo, hi = events["window"]
    return (hi - lo) * 1e-9


def idle_intervals(events: dict) -> list:
    lo, hi = events["window"]
    out, t = [], lo
    for s, e in busy_intervals(events):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_gaps(events: dict, top: int = 10) -> list:
    """[[host span name, idle seconds], ...], largest first: each idle
    instant goes to the innermost (latest-opened) host span covering it,
    or to ``NO_SPAN``."""
    spans = sorted((s, -e, name) for name, s, e in events["host"])
    starts = [s for s, _, _ in spans]
    longest = max((-ne - s for s, ne, _ in spans), default=0)
    total = {}
    for g0, g1 in idle_intervals(events):
        # every span that can overlap the gap starts in this range
        j = bisect.bisect_left(starts, g0 - longest)
        k = bisect.bisect_right(starts, g1)
        live = [(s, -ne, n) for s, ne, n in spans[j:k] if -ne > g0]
        cuts = sorted({g0, g1} | {t for s, e, _ in live
                                  for t in (s, e) if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [(s, n) for s, e, n in live if s <= mid < e]
            name = max(cover)[1] if cover else NO_SPAN
            total[name] = total.get(name, 0.0) + (b - a) * 1e-9
    return sorted(([n, v] for n, v in total.items()),
                  key=lambda x: -x[1])[:top]


def op_times(events: dict) -> dict:
    """Device self seconds per operation name, inside the window."""
    lo, hi = events["window"]
    ivs = sorted((max(s, lo), -min(e, hi), name)
                 for name, s, e in events["device"] if min(e, hi) > max(s, lo))
    out, stack = {}, []     # stack: [end, name] of the open enclosing events
    for s, ne, name in ivs:
        e = -ne
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:   # time inside a parent is the child's, not the parent's
            pe, parent = stack[-1]
            out[parent] = out.get(parent, 0.0) - (min(e, pe) - s) * 1e-9
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
        stack.append([e, name])
    return out


def top_ops(events: dict, top: int = 10) -> list:
    return sorted(([n, v] for n, v in op_times(events).items()),
                  key=lambda x: -x[1])[:top]


_SUFFIX = re.compile(r"(\.\d+)+$")


def base_name(name: str) -> str:
    """``fused_cat_matmul_w4.36`` -> ``fused_cat_matmul_w4``."""
    return _SUFFIX.sub("", op_name(name))


def kernel_seconds(events: dict, names) -> float:
    names = set(names)
    return sum(v for n, v in op_times(events).items()
               if base_name(n) in names)
