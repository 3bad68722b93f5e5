"""The ragged-work reader ``attn.ragged_work_frac`` on hand-built
``step()`` records."""
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import pytest  # noqa: E402

import registry  # noqa: E402

WORK = registry.metric_reader("attn.ragged_work_frac")


def rec(kind, fetched_kind=None, fetched_at=None):
    return {"kind": kind, "fetched_kind": fetched_kind,
            "fetched_at": fetched_at, "fetched_prefill_tokens": 0}


def work(kind, read, table):
    return {"kind": kind, "ragged_work_read": read,
            "ragged_work_table": table}


def run_of(*steps):
    return SimpleNamespace(steps=list(steps))


def test_ragged_work_sums_the_unified_records_only():
    """Two chunk steps compute 81,920 and 153,600 of 8 x 1,024 rows x 308
    pages; a decode step's page counts and a cycle that dispatched
    nothing do not count."""
    table = 8 * 1024 * 308
    run = run_of(work("unified", 81920, table),
                 {"kind": "decode", "decode_pages_read": 120,
                  "decode_pages_table": 2464},
                 rec(None),
                 work("unified", 153600, table))
    assert WORK.read(run) == pytest.approx(100.0 * 235520 / (2 * table))


@pytest.mark.parametrize("steps", [
    [],
    [rec("decode", "unified", 1.0)],
    # unified records of a program that does not count its ragged work
    [rec("unified", "decode", 1.0), rec("unified", "unified", 1.3)],
], ids=["empty", "decode-only", "no-work-keys"])
def test_ragged_work_without_work_records_is_none(steps):
    assert WORK.read(run_of(*steps)) is None
