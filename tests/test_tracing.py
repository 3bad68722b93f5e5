"""The serving loop's own profiler spans and its per-cycle record.

The unified engine opens ``repro.launch.tracing.SPANS`` itself, so a
profile of any process that serves shows the engine's layers on the
profiler's host plane, nested under ``engine.step``. Each unified
``step()`` returns the cycle's record: the kind of step it dispatched,
and the kind, prompt rows and arrival time of the step whose tokens it
fetched.
"""
import glob
import os

import jax
import numpy as np
import pytest

from golden import regenerate

from repro.data import request_workload
from repro.kernels.paged_attention import ragged_work
from repro.launch import tracing
from repro.launch.engine import ServeEngine


def _engine(pipeline: bool):
    """A small unified engine whose plans take both paths: chunked
    prefill through the ragged step, and pure-decode cycles through the
    fast path (``paged_kernel=True``)."""
    cfg, model, params = regenerate.build_case("int8_kv")
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=regenerate.MAX_LEN,
                      max_batch_tokens=8, paged_kernel=True, page_size=8,
                      pipeline=pipeline)
    assert eng.exec.supports_decode_step
    for r in reqs:
        eng.submit(r["tokens"], r["max_new_tokens"], rid=r["rid"])
    return eng, reqs


def _drain(eng) -> list:
    recs = []
    while not eng.idle:
        recs.append(eng.step())
    return recs


def _loop_spans(logdir: str) -> list:
    """[(name, start_ns, end_ns, {stat: value})] of the serving loop's
    spans on the trace's host plane."""
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    pd = jax.profiler.ProfileData.from_file(files[0])
    out = []
    for line in pd.find_plane_with_name("/host:CPU").lines:
        for e in line.events:
            if e.name in tracing.SPANS:
                out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return out


def test_the_loop_traces_itself_under_engine_step(tmp_path):
    """Every span name lands on /host:CPU with no harness wrapping the
    engine; each lies inside one ``engine.step``, the dispatch spans
    carry that cycle's ``step``, and each fetch names the cycle before
    (the pipelined loop fetches the step it dispatched one cycle
    earlier)."""
    eng, _ = _engine(pipeline=True)
    with jax.profiler.trace(str(tmp_path)):
        _drain(eng)
    spans = _loop_spans(str(tmp_path))
    assert {n for n, _, _, _ in spans} == set(tracing.SPANS)
    cycles = [(s, e, st["step"]) for n, s, e, st in spans
              if n == "engine.step"]
    assert len(cycles) == eng.step_count
    for name, s, e, st in spans:
        if name == "engine.step":
            continue
        outer = [c for c in cycles if c[0] <= s and e <= c[1]]
        assert len(outer) == 1, (name, s, e)
        step = outer[0][2]
        if name.startswith("exec."):
            assert st["step"] == step
        elif name == "engine.fetch":
            assert st["fetched"] == step - 1


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "sync"])
def test_step_record_invariants(pipeline):
    """The record of each cycle: in the pipelined loop cycle c fetches
    what cycle c-1 dispatched, in the sync loop what it dispatched
    itself; tokens arrive at strictly increasing times; with the prefix
    cache off every prompt token is packed exactly once."""
    eng, reqs = _engine(pipeline)
    recs = _drain(eng)
    kinds = [r["kind"] for r in recs]
    assert {"decode", "unified"} <= set(kinds)
    if pipeline:
        assert recs[0]["fetched_kind"] is None
        for prev, cur in zip(recs, recs[1:]):
            assert cur["fetched_kind"] == prev["kind"]
            assert cur["fetched_prefill_tokens"] == prev["prefill_tokens"]
    else:
        for r in recs:
            assert r["fetched_kind"] == r["kind"]
            assert r["fetched_prefill_tokens"] == r["prefill_tokens"]
    for r in recs:
        assert (r["fetched_at"] is None) == (r["fetched_kind"] is None)
        if r["kind"] == "decode":
            assert r["prefill_tokens"] == 0
    arrived = [r["fetched_at"] for r in recs if r["fetched_at"] is not None]
    assert len(arrived) == len(eng.metrics["step_s"])
    assert np.all(np.diff(arrived) > 0)
    assert sum(r["prefill_tokens"] for r in recs) \
        == sum(len(r["tokens"]) for r in reqs)
    assert all(r["packed_tokens"] >= r["prefill_tokens"] for r in recs)


def test_decode_records_count_the_pages_the_kernel_reads():
    """A fast-path record counts, for the positions its step shipped,
    each row's pages up to its last kv row (``ceil((pos + 1) / page)``,
    at most the table width) against rows x table width; a ragged step's
    record carries neither count."""
    eng, _ = _engine(pipeline=True)
    page, n_ptab = eng.pool.page_size, eng.tables.table.shape[1]
    shipped = []
    decode_step = eng.exec.decode_step

    def spy(tok, pos, table, src=None):
        shipped.append(np.array(pos))
        return decode_step(tok, pos, table, src)

    eng.exec.decode_step = spy
    recs = _drain(eng)
    decode = [r for r in recs if r["kind"] == "decode"]
    assert len(decode) == len(shipped) > 0
    for r, pos in zip(decode, shipped):
        want = sum(min(-(-(int(p) + 1) // page), n_ptab) for p in pos)
        assert r["decode_pages_read"] == want
        assert r["decode_pages_table"] == len(pos) * n_ptab
        assert 0 < r["decode_pages_read"] <= r["decode_pages_table"]
    assert not any("decode_pages_read" in r for r in recs
                   if r["kind"] != "decode")


def test_unified_records_count_the_ragged_work():
    """A dispatched unified step's record counts the query rows x KV pages
    its ragged attention computes, by the kernel's rule, for the
    descriptor it shipped (``ragged_work``), never more than the walk of
    every item's query block over its whole table; a fast-path record
    carries neither count."""
    eng, _ = _engine(pipeline=True)
    cfg = eng.model.cfg
    shipped = []
    step = eng.exec.step

    def spy(packed):
        desc = packed["ragged_desc"]
        shipped.append((np.array(desc["lengths"]), np.array(desc["qpos"])))
        return step(packed)

    eng.exec.step = spy
    recs = _drain(eng)
    unified = [r for r in recs if r["kind"] == "unified"]
    assert len(unified) == len(shipped) > 0
    for r, (lengths, qpos) in zip(unified, shipped):
        want = ragged_work(lengths, qpos, cfg.n_heads // cfg.n_kv_heads,
                           cfg.n_kv_heads, cfg.head_dim,
                           eng.pool.page_size, eng.tables.n_ptab)
        assert (r["ragged_work_read"], r["ragged_work_table"]) == want
        assert 0 < r["ragged_work_read"] <= r["ragged_work_table"]
    assert not any("ragged_work_read" in r for r in recs
                   if r["kind"] != "unified")
