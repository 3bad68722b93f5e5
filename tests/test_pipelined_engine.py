"""Pipelined (depth-1 asynchronous) unified serving loop.

The pipelined loop (``ServeEngine(pipeline=True)``, the unified-mode
default) packs and dispatches step N+1 while step N executes on device,
sampling greedily inside the jitted step so only (n_logits,) int32
tokens ever cross D2H. It must be **bitwise token-identical** to the
synchronous loop — and therefore to the legacy golden fixtures — across
every serving configuration it composes with:

  - plain unified (tight and loose budgets, prefill chunking)
  - prefix caching (COW page sharing + one-cycle-late registration)
  - speculative decoding (optimistic verify items, partial-accept
    rollback, deferred full-accept shrink)
  - tensor parallelism (in-shard argmax over replicated logits)

Mispredict rollback: a slot that retires on eos (or a speculative
verify that accepts fewer rows than planned) invalidates its
optimistically dispatched rows in the in-flight next step; the
scheduler must discard them and rewind page state so the trajectory —
tokens AND final pool/refcount state — equals a synchronous run's.

Runs via tests/_hypothesis_shim: property cases when hypothesis is
installed, the seeded deterministic ports always.
"""
import json

import jax
import numpy as np
import pytest

from _hypothesis_shim import given, settings, st

from golden import regenerate

from repro.data import request_workload
from repro.launch.engine import ServeEngine

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


def _golden(case):
    with open(regenerate.fixture_path(case)) as f:
        return json.load(f)["tokens"]


def _assert_pool_drained(eng):
    """After a drained run, every page is back on the free list and the
    pool invariant holds — optimistic allocation must have been fully
    rewound regardless of how many predictions failed."""
    assert eng.idle and eng._inflight is None
    assert eng.pool.in_use == 0
    assert eng.pool.available + eng.pool.in_use == eng.pool.n_pages - 1
    if eng.draft_pool is not None:
        assert eng.draft_pool.in_use == 0


# ------------------------------------------------------- golden identity

@pytest.mark.parametrize("case", sorted(regenerate.CASES))
@pytest.mark.parametrize("kw", [
    dict(max_batch_tokens=6),                     # tight: chunked admission
    dict(max_batch_tokens=8, prefill_chunk=4),    # chunk cap on top
], ids=["budget6", "budget8chunk4"])
def test_pipelined_matches_golden_bitwise(case, kw):
    got = regenerate.run_case(case, schedule="unified", page_size=8,
                              pipeline=True, **kw)
    golden = _golden(case)
    for rid, want in golden.items():
        assert got[rid] == want, (
            f"{case} {kw}: pipelined tokens for rid={rid} diverged from "
            f"the golden fixture")


def test_pipelined_summary_and_timing_spans():
    """Pipelined summary reports the pipeline, each cycle's record names
    the step it fetched (the one the cycle before dispatched), and the
    timing spans keep the blocked-loop invariants: one (step_s,
    device_s) pair per OBSERVED step with 0 < device_s <= step_s
    (device_s is the token-fetch wait, a subinterval of the cycle)."""
    cfg, model, params = regenerate.build_case("fp")
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=regenerate.MAX_LEN, schedule="unified",
                      page_size=8)
    assert eng.pipeline
    for r in reqs:
        eng.submit(r["tokens"], r["max_new_tokens"], rid=r["rid"])
    recs = []
    while not eng.idle:
        recs.append(eng.step())
    s = eng.summary()
    assert s["pipeline"] is True
    for prev, cur in zip([{"kind": None}] + recs, recs):
        assert cur["fetched_kind"] == prev["kind"]
    assert sum(r["fetched_at"] is not None for r in recs) \
        == len(eng.metrics["step_s"])
    assert s["mispredicts"] == 0          # max_new retirement is predicted
    step_s, dev_s = eng.metrics["step_s"], eng.metrics["device_s"]
    assert len(step_s) == len(dev_s) > 0
    for ss, d in zip(step_s, dev_s):
        assert 0.0 < d <= ss
    _assert_pool_drained(eng)


def test_pipelined_equals_sync_loop():
    """pipeline=True vs pipeline=False on the same config: identical
    tokens, and the sync run reports pipeline=False with zero overlap."""
    a = regenerate.run_case("fp", schedule="unified", page_size=8,
                            max_batch_tokens=6, pipeline=True)
    b = regenerate.run_case("fp", schedule="unified", page_size=8,
                            max_batch_tokens=6, pipeline=False)
    assert a == b


def test_sync_env_var_forces_synchronous(monkeypatch):
    """REPRO_SYNC_STEP=1 flips the unified default to the synchronous
    loop (profiling mode: honest blocked per-step spans)."""
    monkeypatch.setenv("REPRO_SYNC_STEP", "1")
    cfg, model, params = regenerate.build_case("fp")
    eng = ServeEngine(model, params, n_slots=2, max_len=24,
                      schedule="unified", page_size=8)
    assert eng.pipeline is False
    # an explicit pipeline=True still wins over the env default
    eng2 = ServeEngine(model, params, n_slots=2, max_len=24,
                       schedule="unified", page_size=8, pipeline=True)
    assert eng2.pipeline is True


def test_pipeline_needs_unified_schedule():
    cfg, model, params = regenerate.build_case("fp")
    with pytest.raises(ValueError, match="pipeline"):
        ServeEngine(model, params, n_slots=2, max_len=24, pipeline=True)


# -------------------------------------------------- prefix cache compose

def test_pipelined_prefix_cache_matches_golden():
    """Prefix caching under the pipelined loop: identical tokens on the
    cold pass AND on a warm rerun (shared pages + COW splits + the
    one-cycle-late prefix registration of optimistic tail pages)."""
    cfg, model, params = regenerate.build_case("int8_kv")
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    golden = _golden("int8_kv")
    eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=regenerate.MAX_LEN, schedule="unified",
                      page_size=8, prefix_cache=True)
    for label in ("cold", "warm"):
        res = eng.run(reqs)
        for r in reqs:
            assert (np.asarray(res[r["rid"]].tokens).tolist()
                    == golden[str(r["rid"])]), (label, r["rid"])
        eng.reset()     # keeps the trie warm, so pass 2 serves from hits


# --------------------------------------------------- speculative compose

@pytest.fixture(scope="module")
def spec_draft():
    from repro.launch.serve import build_draft_model
    return build_draft_model("catlm_60m", True, 0)


@pytest.mark.parametrize("k", [2, 4])
def test_pipelined_speculative_matches_golden(k, spec_draft):
    """Speculative decoding under the pipelined loop (draft base token
    injected from the in-flight target step's device vector; partial
    accepts roll the optimistic next step back): bitwise equal to the
    target-only golden fixture."""
    got = regenerate.run_case("fp", schedule="unified", page_size=8,
                              max_batch_tokens=12, speculative_k=k,
                              draft=spec_draft, pipeline=True)
    golden = _golden("fp")
    for rid, want in golden.items():
        assert got[rid] == want, f"k={k} rid={rid}"


def test_pipelined_speculative_eos_rollback(spec_draft):
    """eos retirement + short speculative accepts both mispredict; the
    pipelined trajectory must still match the synchronous one exactly
    and drain the pools completely."""
    cfg, model, params = regenerate.build_case("fp")
    reqs = request_workload(cfg, 4, gen=6, lengths=(6, 10), seed=11)
    base = ServeEngine(model, params, n_slots=2, max_len=24,
                       schedule="unified", page_size=8, speculative_k=2,
                       draft=spec_draft, pipeline=False).run(reqs)
    # an eos seen mid-stream in the no-eos run forces early retirement
    eos = int(base[0].tokens[base[0].prompt_len])
    runs = []
    for pipeline in (False, True):
        eng = ServeEngine(model, params, n_slots=2, max_len=24,
                          schedule="unified", page_size=8, speculative_k=2,
                          draft=spec_draft, eos_id=eos, pipeline=pipeline)
        runs.append(eng.run(reqs))
        _assert_pool_drained(eng)
    for r in reqs:
        np.testing.assert_array_equal(
            runs[1][r["rid"]].tokens, runs[0][r["rid"]].tokens,
            err_msg=f"rid={r['rid']}: pipelined spec+eos diverged")


# --------------------------------------------------------- tp=4 compose

@needs4
def test_pipelined_tp4_mha_matches_sync_solo():
    """tp=4 mesh (gather mode, MHA head-count override): the in-shard
    argmax runs over replicated logits, so pipelined mesh tokens equal
    the single-device synchronous run bitwise."""
    from repro.configs import get_config
    from repro.distributed.compat import make_mesh
    from repro.models import build

    cfg = get_config("catlm_60m").smoke().scaled(n_kv_heads=4)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = request_workload(cfg, 6, gen=5, lengths=(6, 10, 14), seed=3)
    solo = ServeEngine(model, params, n_slots=3, max_len=32,
                       schedule="unified", page_size=8,
                       pipeline=False).run(reqs)
    mesh = make_mesh((1, 4), ("data", "model"))
    eng = ServeEngine(model, params, n_slots=3, max_len=32, mesh=mesh,
                      schedule="unified", page_size=8, pipeline=True)
    meshed = eng.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            meshed[r["rid"]].tokens, solo[r["rid"]].tokens,
            err_msg=f"rid={r['rid']}: tp=4 pipelined diverged")
    assert eng.summary()["pipeline"] is True
    _assert_pool_drained(eng)


# -------------------------------------------------- mispredict rollback

def test_eos_mispredict_rolls_back_to_sync_trajectory():
    """Force mid-stream eos retirements: the optimistic next step was
    already dispatched for the retiring slot, so observe() must mark its
    rows stale, discard their tokens, and release the slot's pages —
    leaving output AND pool state equal to the synchronous run."""
    cfg, model, params = regenerate.build_case("fp")
    reqs = request_workload(cfg, 4, gen=6, lengths=(6, 10), seed=11)
    base = ServeEngine(model, params, n_slots=2, max_len=24,
                       schedule="unified", page_size=8,
                       pipeline=False).run(reqs)
    # the 2nd generated token of request 0 retires it 4 tokens early —
    # its slot is mid-decode, so the next step always has it packed
    eos = int(base[0].tokens[base[0].prompt_len + 1])
    runs, engines = [], []
    for pipeline in (False, True):
        eng = ServeEngine(model, params, n_slots=2, max_len=24,
                          schedule="unified", page_size=8, eos_id=eos,
                          pipeline=pipeline)
        runs.append(eng.run(reqs))
        engines.append(eng)
        _assert_pool_drained(eng)
    sync_eng, pipe_eng = engines
    for r in reqs:
        np.testing.assert_array_equal(
            runs[1][r["rid"]].tokens, runs[0][r["rid"]].tokens,
            err_msg=f"rid={r['rid']}: eos rollback diverged")
    assert pipe_eng.summary()["mispredicts"] > 0
    assert sync_eng.summary()["mispredicts"] == 0
    # generated-token accounting excludes discarded stale outputs
    assert (pipe_eng.metrics["generated_tokens"]
            == sync_eng.metrics["generated_tokens"])


@given(seed=st.integers(0, 40), which=st.integers(0, 3),
       depth=st.integers(0, 2))
@settings(max_examples=12, deadline=None)
def test_property_forced_retirement_equals_sync(seed, which, depth):
    """Property port of the rollback test: for random workloads and a
    random forced-eos choice, the pipelined trajectory (tokens, retire
    events, final pool state) equals the synchronous one."""
    _forced_retirement_case(seed, which, depth)


@pytest.mark.parametrize("seed,which,depth",
                         [(11, 0, 1), (3, 2, 0), (7, 1, 2)])
def test_forced_retirement_equals_sync_seeded(seed, which, depth):
    """Deterministic port of the property case (always runs)."""
    _forced_retirement_case(seed, which, depth)


def _forced_retirement_case(seed, which, depth):
    from test_scheduler_properties import _stub

    rng = np.random.default_rng(seed)
    reqs = [{"rid": i,
             "tokens": rng.integers(0, 64, int(p)).astype(np.int32),
             "max_new_tokens": int(g)}
            for i, (p, g) in enumerate(zip(rng.integers(1, 12, 4),
                                           rng.integers(2, 7, 4)))]
    base = ServeEngine(_stub(), {}, n_slots=2, max_len=24,
                       schedule="unified", page_size=4,
                       pipeline=False).run(reqs)
    rid = int(which) % len(reqs)
    gen = base[rid].tokens[base[rid].prompt_len:]
    eos = int(gen[min(int(depth), len(gen) - 1)])
    runs, engines = [], []
    for pipeline in (False, True):
        eng = ServeEngine(_stub(), {}, n_slots=2, max_len=24,
                          schedule="unified", page_size=4, eos_id=eos,
                          pipeline=pipeline)
        runs.append(eng.run(reqs))
        engines.append(eng)
        _assert_pool_drained(eng)
    for r in reqs:
        np.testing.assert_array_equal(
            runs[1][r["rid"]].tokens, runs[0][r["rid"]].tokens,
            err_msg=f"rid={r['rid']} seed={seed} eos={eos}")
    # every request retires exactly once in both modes (event ORDER may
    # differ: pipelined admission lags one cycle behind a retirement it
    # hasn't observed yet, which can land two retirements in different
    # cycles — tokens and pool state are what must match)
    for eng in engines:
        retires = sorted(e[1] for e in eng.events if e[0] == "retire")
        assert retires == sorted(r["rid"] for r in reqs)


# ------------------------------------------------------ reset mid-flight

def test_reset_mid_flight_refused_then_clean_after_drain():
    """reset() must refuse while a pipelined step is in flight (the
    engine is not idle), and a post-drain reset must clear the in-flight
    slot, the descriptor-ring parity, and the executor's previous-token
    vector so a rerun reproduces the first run exactly."""
    cfg, model, params = regenerate.build_case("fp")
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=regenerate.MAX_LEN, schedule="unified",
                      page_size=8)
    for r in reqs:
        eng.submit(r["tokens"], r["max_new_tokens"], rid=r.get("rid"))
    eng.step()
    eng.step()
    assert eng._inflight is not None and not eng.idle
    with pytest.raises(RuntimeError, match="idle"):
        eng.reset()
    while not eng.idle:
        eng.step()
    first = {r["rid"]: np.asarray(eng.results[r["rid"]].tokens).copy()
             for r in reqs}
    eng.reset()
    assert eng._inflight is None
    assert eng.sched._buf_parity == 0
    assert eng.exec._prev is None
    res = eng.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(res[r["rid"]].tokens,
                                      first[r["rid"]])


# ------------------------------------------- legacy executor regression

def test_legacy_decode_device_argmax_and_d2h_attribution():
    """LegacyExecutor.decode samples on device — the returned array is
    the (n_slots,) int32 token vector, and the (tiny) D2H copy is
    attributed to d2h_s / d2h_ms_mean instead of inflating the engine's
    compute span. Output stays pinned to the golden fixture."""
    cfg, model, params = regenerate.build_case("fp")
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=regenerate.MAX_LEN)
    toks = np.zeros((regenerate.N_SLOTS, 1), np.int32)
    pos = np.zeros((regenerate.N_SLOTS,), np.int32)
    out = eng.exec.decode(toks, pos)
    assert out.shape == (regenerate.N_SLOTS,) and out.dtype == np.int32
    assert eng.exec.d2h_s > 0.0
    res = eng.run(reqs)
    golden = _golden("fp")
    for r in reqs:
        assert (np.asarray(res[r["rid"]].tokens).tolist()
                == golden[str(r["rid"])]), r["rid"]
    s = eng.summary()
    assert "d2h_ms_mean" in s and s["d2h_ms_mean"] > 0.0
    assert s["device_ms_mean"] > 0.0
