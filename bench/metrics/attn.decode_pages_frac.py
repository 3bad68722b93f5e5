"""Attention kernels: share of the page table that the decode attention
kernel fetches, ``100 x sum(decode_pages_read) / sum(decode_pages_table)``
over the ``step()`` records of the traced window that dispatched a
pure-decode step (``kind`` ``"decode"``). A record carries the pages its
step's rows fetch (``min(ceil(len / page), table width)`` each) and the
pages its table holds (rows x table width). ``None`` where no record has
them, as with a program whose kernel walks the whole table. Moves
``itl_p50_ms``: the pages left unread are the decode step's saved work."""


def read(run):
    recs = [r for r in run.steps if r.get("kind") == "decode"
            and r.get("decode_pages_table")]
    table = sum(r["decode_pages_table"] for r in recs)
    return 100.0 * sum(r["decode_pages_read"] for r in recs) / table \
        if table else None
