"""The open loop that drives a ``ServeEngine``, and what it records.

``OpenLoop``: each request of the cell's traffic is submitted when it is
due (``traffic.arrival``), whether or not earlier ones have finished, and
its clock starts at that due time, so a stall also counts against the
requests that arrive during it. After every ``engine.step()`` the loop
stamps each token the step delivered with the host time, per request, so
the window's metrics (``window.py``) are read from the users' side.

``Dispatches``: wraps the executor's two step entries (the unified
ragged ``exec.step`` and the pure-decode ``exec.decode_step``) to count
them, and, while ``detail`` is on, to keep what each dispatch really
computed: the rows that belong to a request (not padding), each work
item's query rows and context length, and the logit rows.

``Spans``: while installed, names the engine's layers in the profiler's
trace with ``jax.profiler.TraceAnnotation``: ``engine.step`` around each
cycle, and inside it ``sched.plan``, ``sched.pack`` /
``sched.pack_decode``, ``exec.step`` / ``exec.decode_step`` (the
dispatch), ``engine.fetch`` (the wait for the previous step's tokens)
and ``sched.observe``.
"""
from __future__ import annotations

import time

import numpy as np

SPAN_NAMES = ("engine.step", "sched.plan", "sched.pack", "sched.pack_decode",
              "exec.step", "exec.decode_step", "engine.fetch",
              "sched.observe")


class OpenLoop:
    def __init__(self, engine, traffic):
        self.eng, self.traffic = engine, traffic
        self.records = {}   # rid -> {"submit", "times", "max_new"[, "done"]}
        self.late = {}      # rid -> seconds between due and submitted
        self.n_submitted = 0
        self.t_start = None
        self._events = 0

    def start(self, t: float, first: int = 0) -> None:
        """The schedule starts with request ``first``, due at ``t``; request
        i is due at ``t + arrival(i) - arrival(first)``."""
        self.n_submitted = first
        self.t_start = t - self.traffic.arrival(first)

    def due(self, i: int) -> float:
        return self.t_start + self.traffic.arrival(i)

    def _due(self) -> float:
        return self.due(self.n_submitted)

    def _submit_due(self) -> None:
        while self._due() <= time.perf_counter():
            due = self._due()
            prompt, max_new = self.traffic.request(self.n_submitted)
            self.n_submitted += 1
            rid = self.eng.submit(prompt, max_new)
            self.late[rid] = time.perf_counter() - due
            self.records[rid] = {"submit": due, "times": [],
                                 "max_new": max_new}

    def step(self, t_end: float = float("inf")) -> dict | None:
        """Submit what is due, then one engine cycle; with nothing to
        serve, wait for the next arrival (or ``t_end``) instead and
        return None."""
        self._submit_due()
        if self.eng.idle:
            time.sleep(max(0.0, min(self._due(), t_end)
                           - time.perf_counter()))
            return None
        out = self.eng.step()
        now = time.perf_counter()
        for seq in self.eng.sched.active.values():
            times = self.records[seq.req.rid]["times"]
            if len(times) < len(seq.generated):
                times.extend([now] * (len(seq.generated) - len(times)))
        events = self.eng.events
        while self._events < len(events):
            kind, rid, _, _ = events[self._events]
            self._events += 1
            if kind != "retire":
                continue
            res = self.eng.results[rid]
            rec = self.records[rid]
            n = len(res.tokens) - res.prompt_len
            rec["times"].extend([now] * (n - len(rec["times"])))
            rec["done"] = now
        out["now"] = now
        return out

    def run_until(self, t_end: float, steps: list | None = None) -> None:
        while time.perf_counter() < t_end:
            out = self.step(t_end)
            if out is not None and steps is not None:
                steps.append(out)

    def retired(self) -> int:
        return sum("done" in r for r in self.records.values())

    def finished(self, t0: float, t1: float) -> dict:
        """rid -> (prompt, served tokens) of the requests that retired in
        [t0, t1]."""
        out = {}
        for rid, rec in self.records.items():
            if t0 <= rec.get("done", -1.0) <= t1:
                res = self.eng.results[rid]
                toks = np.asarray(res.tokens)
                out[rid] = (toks[:res.prompt_len], toks[res.prompt_len:])
        return out

    def malformed(self, finished: dict, vocab: int) -> int:
        """Finished requests whose output is not exactly their
        max_new_tokens in-vocabulary tokens."""
        return sum(1 for rid, (_, out) in finished.items()
                   if len(out) != self.records[rid]["max_new"]
                   or not np.all((out >= 0) & (out < vocab)))

    def in_window(self, t0: float, t1: float) -> int:
        """Requests due by t1 that had not retired before t0."""
        return sum(1 for r in self.records.values()
                   if r["submit"] <= t1 and r.get("done", t1) >= t0)

    def late_max(self, t0: float, t1: float) -> float:
        """How late the generator ran: the longest wait between a
        request's due time and its submission, over the window."""
        return max((self.late[rid] for rid, r in self.records.items()
                    if t0 <= r["submit"] <= t1), default=0.0)


def _unified_record(packed: dict) -> dict:
    desc = packed.get("ragged_desc")
    if desc is None:
        # no kernel descriptors: one item per real row
        ptab, pos = packed["page_table"], packed["pos"]
        live = ptab[:, 0] > 0
        q_len, kv_len = np.ones(int(live.sum()), np.int64), pos[live] + 1
    else:
        n = (desc["qpos"] >= 0).sum(axis=1)
        keep = n > 0
        q_len, kv_len = n[keep], desc["lengths"][keep]
    return {"kind": "unified", "q_len": q_len.tolist(),
            "kv_len": np.asarray(kv_len).tolist(),
            "logits": int(packed["n_logits"])}


def _decode_record(pos: np.ndarray, table: np.ndarray) -> dict:
    live = np.asarray(table)[:, 0] > 0
    n = int(live.sum())
    return {"kind": "decode", "q_len": [1] * n,
            "kv_len": (np.asarray(pos)[live] + 1).tolist(), "logits": n}


class Dispatches:
    def __init__(self, executor):
        self.counts = {"unified": 0, "decode": 0}
        self.detail = False
        self.records: list = []
        step, decode_step = executor.step, executor.decode_step

        def wrapped_step(packed):
            self.counts["unified"] += 1
            if self.detail:
                self.records.append(_unified_record(packed))
            return step(packed)

        def wrapped_decode(tokens, pos, table, tok_src=None):
            self.counts["decode"] += 1
            if self.detail:
                self.records.append(_decode_record(pos, table))
            return decode_step(tokens, pos, table, tok_src)

        executor.step = wrapped_step
        executor.decode_step = wrapped_decode


class Spans:
    """Installs and removes the trace annotations on one engine."""

    def __init__(self, engine):
        self.eng = engine
        self._saved = []

    def _wrap(self, obj, attr: str, name: str) -> None:
        import jax
        orig = getattr(obj, attr)

        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return orig(*a, **kw)

        self._saved.append((obj, attr, orig))
        setattr(obj, attr, wrapped)

    def install(self) -> None:
        import jax
        eng = self.eng
        self._wrap(eng, "step", "engine.step")
        for attr in ("plan", "pack", "pack_decode", "observe"):
            self._wrap(eng.sched, attr, f"sched.{attr}")
        self._wrap(eng.exec, "step", "exec.step")
        self._wrap(eng.exec, "decode_step", "exec.decode_step")
        # the engine waits for the previous step's tokens through
        # jax.block_until_ready; name that wait while the spans are on
        self._wrap(jax, "block_until_ready", "engine.fetch")

    def remove(self) -> None:
        for obj, attr, orig in reversed(self._saved):
            setattr(obj, attr, orig)
        self._saved = []
