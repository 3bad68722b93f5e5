"""The step-kind readers ``step.decode_ms`` and ``step.chunk_ms`` on
hand-built ``step()`` records, and the span names the trace reduction
reads against the names the program opens."""
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import pytest  # noqa: E402

import driver  # noqa: E402
import registry  # noqa: E402

DECODE = registry.metric_reader("step.decode_ms")
CHUNK = registry.metric_reader("step.chunk_ms")


def rec(kind, fetched_kind=None, fetched_at=None, fetched_prefill=0):
    return {"kind": kind, "fetched_kind": fetched_kind,
            "fetched_at": fetched_at,
            "fetched_prefill_tokens": fetched_prefill}


def run_of(*steps):
    return SimpleNamespace(steps=list(steps))


def test_each_reader_takes_the_intervals_of_its_step_kind():
    """Arrivals at 0, 28, 141, 169, 197 ms: the chunk step fetched at
    141 ms took 113 ms, the decode steps 28 ms each."""
    run = run_of(rec("unified", "unified", 0.000, 64),
                 rec("unified", "decode", 0.028),
                 rec("decode", "unified", 0.141, 512),
                 rec("decode", "decode", 0.169),
                 rec(None, "decode", 0.197))
    assert DECODE.read(run) == pytest.approx(28.0)
    assert CHUNK.read(run) == pytest.approx(113.0)


def test_an_interval_needs_a_dispatch_and_a_fetch_before_it():
    """After a cycle that dispatched nothing the engine may have idled
    until the next arrival; after a cycle that fetched nothing there is
    no start to measure from. Neither interval counts."""
    run = run_of(rec(None, "decode", 0.0),
                 rec("decode", "decode", 5.0),      # after an idle cycle
                 rec("unified"),                     # fetched nothing
                 rec("decode", "unified", 5.2, 8),   # no start before it
                 rec("decode", "decode", 5.23))
    assert DECODE.read(run) == pytest.approx(30.0)
    assert CHUNK.read(run) is None


def test_a_unified_step_without_prompt_rows_is_no_chunk_step():
    """A unified step that carried only decode rows (say a copy-on-write
    cycle) is neither kind."""
    run = run_of(rec("unified", "decode", 1.0),
                 rec("decode", "unified", 1.04, 0))
    assert CHUNK.read(run) is None
    assert DECODE.read(run) is None


@pytest.mark.parametrize("steps", [
    [],
    [rec("decode", "decode", 1.0)],
    # records of a program that returns no such keys
    [{"packed_tokens": 8}, {"packed_tokens": 8}, {"packed_tokens": 512}],
], ids=["empty", "one-record", "no-record-keys"])
def test_nothing_to_read_gives_none(steps):
    assert DECODE.read(run_of(*steps)) is None
    assert CHUNK.read(run_of(*steps)) is None


def test_the_program_opens_every_span_the_reduction_reads():
    from repro.launch import tracing

    assert set(tracing.SPANS) == set(driver.SPAN_NAMES)
