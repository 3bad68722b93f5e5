"""The open loop against a stand-in engine: requests go in when they are
due, their clocks start at the due time, and a stall counts against the
requests that arrive during it."""
import os
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from driver import OpenLoop  # noqa: E402
from traffic import Traffic  # noqa: E402

MIX = {"rate": 50.0, "pool": 8, "n_slots": 4, "max_batch_tokens": 64,
       "prompt": {"dist": "uniform", "min": 4, "max": 8},
       "output": {"dist": "uniform", "min": 2, "max": 3}}


class Engine:
    """Serves every submitted request at once, one token a step; a step
    takes ``step_s`` seconds."""

    def __init__(self, step_s=0.0):
        self.step_s = step_s
        self.sched = SimpleNamespace(active={})
        self.events, self.results, self.submitted = [], {}, []
        self._next = 0

    @property
    def idle(self):
        return not self.sched.active

    def submit(self, prompt, max_new):
        rid, self._next = self._next, self._next + 1
        self.submitted.append((rid, time.perf_counter()))
        self.sched.active[rid] = SimpleNamespace(
            req=SimpleNamespace(rid=rid), generated=[], prompt=prompt,
            max_new=max_new)
        return rid

    def step(self):
        time.sleep(self.step_s)
        for rid, seq in list(self.sched.active.items()):
            seq.generated.append(7)
            if len(seq.generated) == seq.max_new:
                del self.sched.active[rid]
                self.results[rid] = SimpleNamespace(
                    tokens=list(seq.prompt) + seq.generated,
                    prompt_len=len(seq.prompt))
                self.events.append(("retire", rid, 0, 0))
        return {"packed_tokens": 1}


def test_requests_are_submitted_when_due_and_timed_from_then():
    tr = Traffic(MIX, 2**31 + 3, 100)
    eng = Engine()
    loop = OpenLoop(eng, tr)
    t = time.perf_counter()
    loop.start(t, first=2)
    loop.run_until(t + 0.3)
    assert loop.n_submitted >= 10
    for rid, sent in eng.submitted:
        due = loop.records[rid]["submit"]
        assert due == pytest.approx(t + tr.arrival(rid + 2)
                                    - tr.arrival(2))
        assert sent >= due
    done = loop.finished(t, time.perf_counter())
    assert done and loop.malformed(done, 100) == 0
    assert all(len(loop.records[r]["times"]) == loop.records[r]["max_new"]
               for r in done)


def test_a_stall_counts_against_the_requests_due_during_it():
    tr = Traffic(MIX, 5, 100)
    eng = Engine(step_s=0.1)     # five arrivals due in each step
    loop = OpenLoop(eng, tr)
    t = time.perf_counter()
    loop.start(t)
    loop.run_until(t + 0.5)
    t1 = time.perf_counter()
    firsts = [r["times"][0] - r["submit"] for r in loop.records.values()
              if r["times"]]
    # requests due while a step ran waited for it: their first token is
    # late by up to a step and more, measured from the due time
    assert max(firsts) > 0.1
    assert loop.late_max(t, t1) > 0.05
    assert np.all(np.diff([r["submit"] for r in loop.records.values()]) > 0)
