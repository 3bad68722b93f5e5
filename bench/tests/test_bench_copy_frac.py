"""``step.copy_frac`` on one engine cycle recorded on a TPU v5e
(``data/trace_tpu_decode8.json``, the fixture ``test_bench_tracefile.py``
reads): the share of device-busy time in XLA ``copy`` operations."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

import registry  # noqa: E402
import tracefile  # noqa: E402


def _run(events):
    return argparse.Namespace(
        events=events, busy_s=tracefile.busy_s(events) if events else 0.0)


@pytest.fixture
def recorded():
    with open(os.path.join(HERE, "data", "trace_tpu_decode8.json")) as f:
        return json.load(f)


def test_copy_share_of_a_recorded_decode_cycle(recorded):
    read = registry.metric_reader("step.copy_frac").read
    # the cycle's 84 ``copy.<n>`` events, counted from the fixture: 2.816 ms
    # of 11.382 ms busy; copy-start/-done and copy fusions are left out
    copies = [(n, e - s) for n, s, e in recorded["device"]
              if tracefile.base_name(n) == "copy"]
    assert len(copies) == 84
    assert sum(d for _, d in copies) * 1e-9 == pytest.approx(0.00281643)
    assert read(_run(recorded)) == pytest.approx(
        100.0 * 0.00281643 / 0.011381826)


def test_no_trace_no_reading():
    assert registry.metric_reader("step.copy_frac").read(_run(None)) is None


def test_only_plain_copies_count(recorded):
    recorded["device"] = [d for d in recorded["device"]
                          if tracefile.base_name(d[0]) != "copy"]
    assert registry.metric_reader("step.copy_frac").read(
        _run(recorded)) == 0.0
