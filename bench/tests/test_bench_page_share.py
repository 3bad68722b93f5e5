"""The page-share reader ``attn.decode_pages_frac`` on hand-built
``step()`` records."""
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import pytest  # noqa: E402

import registry  # noqa: E402

PAGES = registry.metric_reader("attn.decode_pages_frac")


def rec(kind, fetched_kind=None, fetched_at=None):
    return {"kind": kind, "fetched_kind": fetched_kind,
            "fetched_at": fetched_at, "fetched_prefill_tokens": 0}


def pages(kind, read, table):
    return {"kind": kind, "decode_pages_read": read,
            "decode_pages_table": table}


def run_of(*steps):
    return SimpleNamespace(steps=list(steps))


def test_page_share_sums_the_decode_records_only():
    """Two decode steps read 120 and 200 of 8 x 320 table pages; the
    chunk step between them (its kernel walks no decode table) and a
    cycle that dispatched nothing do not count."""
    run = run_of(pages("decode", 120, 2560),
                 pages("unified", 2560, 2560),
                 rec(None),
                 pages("decode", 200, 2560))
    assert PAGES.read(run) == pytest.approx(100.0 * 320 / 5120)


@pytest.mark.parametrize("steps", [
    [],
    [rec("unified", "decode", 1.0)],
    # decode records of a program that does not count pages
    [rec("decode", "decode", 1.0), rec("decode", "decode", 1.03)],
], ids=["empty", "chunk-only", "no-page-keys"])
def test_page_share_without_page_records_is_none(steps):
    assert PAGES.read(run_of(*steps)) is None
