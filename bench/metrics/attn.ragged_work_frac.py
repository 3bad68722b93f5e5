"""Attention kernels: share of a whole-table walk that the ragged attention
kernel computes, ``100 x sum(ragged_work_read) / sum(ragged_work_table)``
over the ``step()`` records of the traced window that dispatched a unified
step (``kind`` ``"unified"``). A record carries the query rows x KV pages
its step's kernel computes (each visited item, query tile and KV block
counts its tile's rows times its block's pages) and the same for every
item's whole query block over its whole table. ``None`` where no record
has them, as with a program whose kernel walks every table whole. Moves
``itl_p95_ms``: the chunk step, which carries the ragged kernel, sets the
tail gap."""


def read(run):
    recs = [r for r in run.steps if r.get("kind") == "unified"
            and r.get("ragged_work_table")]
    table = sum(r["ragged_work_table"] for r in recs)
    return 100.0 * sum(r["ragged_work_read"] for r in recs) / table \
        if table else None
