"""Speculative decoding in the unified step: correctness properties.

The draft/verify cycle (``--speculative k``) proposes k tokens per
decoding slot from the int4-packed draft model, then the target verifies
all k+1 positions per slot in one ragged invocation with greedy
acceptance. Because every token that ``observe`` appends is a row of the
TARGET's argmax — accepted drafts merely matched it, the first mismatch
row is the target's correction, and the bonus row is the target's too —
the output is bitwise identical to target-only greedy decode regardless
of draft quality. These tests pin that identity against the golden
fixtures across k, quant configs, prefix-cache modes, and a tp=4 mesh,
plus the KV-rewind invariant (page tables and refcounts after a
rejection match a never-drafted run) and the retirement/timing edges the
feature exposed (``_finished`` guards, device-time attribution, TTFT
monotonicity).
"""
import json

import numpy as np
import pytest

from golden import regenerate

from repro.data import request_workload
from repro.launch.engine import ServeEngine
from repro.launch.paged import PagePool, SlotPageTables
from repro.launch.scheduler import Request, SeqState, TokenBudgetScheduler
from repro.launch.serve import build_draft_model

_DRAFTS = {}


def _draft(key=None, seed=0, **overrides):
    """Module-cached int4-packed draft (model, params) — quantizing the
    draft checkpoint is the slow part, and the same draft serves every
    target config with the same architecture shape."""
    if key not in _DRAFTS:
        _DRAFTS[key] = build_draft_model(
            "catlm_60m", True, seed, cfg_overrides=overrides or None)
    return _DRAFTS[key]


def _golden(case):
    with open(regenerate.fixture_path(case)) as f:
        return json.load(f)["tokens"]


@pytest.mark.parametrize("case", sorted(regenerate.CASES))
@pytest.mark.parametrize("k", [1, 2, 4])
def test_speculative_matches_golden_bitwise(case, k):
    """Accepted+corrected output == the target-only golden fixture for
    every quant config and draft depth (identity is structural — the
    draft only changes how many verify rows get accepted per cycle)."""
    got = regenerate.run_case(case, page_size=8,
                              max_batch_tokens=12, speculative_k=k,
                              draft=_draft())
    golden = _golden(case)
    for rid, want in golden.items():
        assert got[rid] == want, (
            f"{case} k={k}: speculative tokens for rid={rid} diverged "
            f"from the target-only golden fixture")


def test_adaptive_spec_matches_golden_bitwise():
    """adaptive_spec only changes how many drafts each slot packs per
    cycle — acceptance still appends target-argmax rows only, so the
    output must stay bitwise equal to the golden fixture."""
    case = sorted(regenerate.CASES)[0]
    got = regenerate.run_case(case, page_size=8,
                              max_batch_tokens=12, speculative_k=4,
                              draft=_draft(), adaptive_spec=True)
    golden = _golden(case)
    for rid, want in golden.items():
        assert got[rid] == want, (
            f"{case} adaptive: tokens for rid={rid} diverged")


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["prefix_off", "prefix_on"])
def test_speculative_shared_prefix_identity(prefix_cache):
    """Random shared-prefix workload: speculative output must equal the
    non-speculative unified engine's, with the prefix cache off and on —
    and with it on, every page still live after the drain must be held
    by the prefix trie (no verify-row growth may leak past a
    rejection); the draft pool, which never shares prefix pages, drains
    to zero."""
    cfg, model, params = regenerate.build_case("int8_kv")
    reqs = request_workload(cfg, 6, gen=5, lengths=(6, 10), seed=11,
                            shared_prefix=6)
    kw = dict(n_slots=2, max_len=24,
              max_batch_tokens=12, page_size=8, prefix_cache=prefix_cache)
    base_eng = ServeEngine(model, params, **kw)
    base = base_eng.run(reqs)
    spec_eng = ServeEngine(model, params, speculative_k=3, draft=_draft(),
                           **kw)
    spec = spec_eng.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            spec[r["rid"]].tokens, base[r["rid"]].tokens,
            err_msg=f"rid={r['rid']} prefix_cache={prefix_cache}")
    # (the two engines' pools are sized differently — spec_k pads
    # _kv_len — so absolute retention can differ via LRU eviction; what
    # must hold is that nothing BUT the trie keeps pages alive)
    trie = spec_eng.sched.prefix
    if prefix_cache:
        assert spec_eng.pool.in_use == trie.resident, \
            "pages leaked past the prefix trie after the drain"
        assert spec_eng.draft_pool.in_use == 0
    else:
        assert spec_eng.pool.in_use == 0
        assert spec_eng.draft_pool.in_use == 0


def test_speculative_tp4_token_identical():
    """tp=4 mesh on the MHA override (same convention as the unified
    mesh test): the draft always runs plain single-device jit, only the
    target verify is shard_mapped — output must equal the solo
    engine's."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 local devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    from repro.configs import get_config
    from repro.distributed.compat import make_mesh
    from repro.models import build

    cfg = get_config("catlm_60m").smoke().scaled(n_kv_heads=4,
                                                 kv_quant_bits=8)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = request_workload(cfg, 5, gen=4, lengths=(6, 10), seed=3)
    solo = ServeEngine(model, params, n_slots=2, max_len=24).run(reqs)
    mesh = make_mesh((1, 4), ("data", "model"))
    spec = ServeEngine(model, params, n_slots=2, max_len=24, mesh=mesh,
                       max_batch_tokens=12,
                       page_size=8, speculative_k=2,
                       draft=_draft(key="mha4", n_kv_heads=4)).run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(spec[r["rid"]].tokens,
                                      solo[r["rid"]].tokens,
                                      err_msg=f"rid={r['rid']}")


def test_speculative_kv_rewind_invariant():
    """After every step, each decoding slot's page coverage — in BOTH
    pools — equals ``pages_for(prompt + generated - 1)``, which is
    exactly what a never-drafted run holds after its own observe: the
    rejected verify rows' pages are shrunk back the same cycle they were
    grown. The workload pairs an int8 target with the int4 draft so
    rejections actually happen, and the drained pools must balance."""
    cfg, model, params = regenerate.build_case("int8_kv")
    reqs = request_workload(cfg, regenerate.N_REQUESTS,
                            gen=regenerate.GEN, lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    # a draft from a DIFFERENT seed proposes essentially random tokens,
    # guaranteeing rejections (identity and rewind are structural — the
    # draft's quality only sets the acceptance rate). The per-step
    # coverage check below is a SYNC-loop property: the pipelined loop
    # legitimately holds extra pages for the in-flight ahead plan
    # between steps (its drained-pool equality lives in
    # test_pipelined_engine.py), so pin the synchronous loop here.
    eng = ServeEngine(model, params, n_slots=2, max_len=24,
                      max_batch_tokens=12, page_size=8,
                      speculative_k=2, draft=_draft(key="seed1", seed=1),
                      pipeline=False)
    for r in reqs:
        eng.submit(r["tokens"], r["max_new_tokens"], rid=r["rid"])
    sched = eng.sched
    while not eng.idle:
        eng.step()
        for slot, seq in sched.active.items():
            if not seq.decoding:
                continue
            valid = seq.prompt_len + len(seq.generated) - 1
            want = sched.tables.pages_for(valid)
            assert sched.tables.n_owned(slot) == want, (
                f"target pool coverage {sched.tables.n_owned(slot)} != "
                f"never-drafted {want} pages for slot {slot}")
            assert sched.draft_tables.n_owned(slot) == want, (
                f"draft pool coverage {sched.draft_tables.n_owned(slot)} "
                f"!= never-drafted {want} pages for slot {slot}")
    assert sched.spec_drafted > sched.spec_accepted, \
        "workload produced no rejections — the invariant went untested"
    for pool in (eng.pool, eng.draft_pool):
        assert pool.in_use == 0, "drained engine must free all pages"
        assert pool.allocs == pool.frees


def test_speculative_engine_validation():
    cfg, model, params = regenerate.build_case("fp")
    with pytest.raises(ValueError, match="speculative_k"):
        ServeEngine(model, params, n_slots=2, max_len=24,
                    speculative_k=-1, draft=_draft())
    with pytest.raises(ValueError, match="draft"):
        ServeEngine(model, params, n_slots=2, max_len=24,
                    max_batch_tokens=12,
                    speculative_k=2)
    # every running slot packs k+1 verify rows, so the budget floor
    # scales with spec_k
    with pytest.raises(ValueError, match="max_batch_tokens"):
        ServeEngine(model, params, n_slots=2, max_len=24,
                    max_batch_tokens=4,
                    speculative_k=2, draft=_draft())


# --------------------------------------------------- satellite regressions


def _mini_sched(eos_id=None):
    pool = PagePool(8, 8)
    tables = SlotPageTables(pool, 2, 4)
    return TokenBudgetScheduler(2, 8, pool=pool, tables=tables,
                                eos_id=eos_id)


def _seq(generated, max_new=8):
    return SeqState(req=Request(rid=0, prompt=np.zeros(4, np.int32),
                                max_new_tokens=max_new),
                    slot=0, prefill_done=4, generated=list(generated))


def test_finished_empty_generated_with_eos():
    """Regression: ``generated[-1]`` on an empty list raised IndexError
    when an eos_id was set and a slot was consulted before its first
    token (the speculative observe path does exactly that)."""
    sched = _mini_sched(eos_id=5)
    assert sched._finished(_seq([])) is False
    assert sched._finished(_seq([], max_new=0)) is True


def test_finished_eos_none_vs_token_zero():
    """Regression: eos_id=None must never match token 0 (or any token) —
    the check is structural, not an accident of ``None == 0`` being
    False."""
    assert _mini_sched(eos_id=None)._finished(_seq([0])) is False
    assert _mini_sched(eos_id=0)._finished(_seq([0])) is True
    assert _mini_sched(eos_id=5)._finished(_seq([3, 5])) is True
    assert _mini_sched(eos_id=5)._finished(_seq([5, 3])) is False


def test_device_time_within_step_time():
    """Device-time attribution: the timed span now blocks on the step
    output (``block_until_ready`` inside the span), so device_s measures
    execution, not enqueue — and it can never exceed the enclosing
    step_s span."""
    cfg, model, params = regenerate.build_case("fp")
    reqs = request_workload(cfg, 4, gen=4, lengths=(6, 10), seed=2)
    for kw in (dict(max_batch_tokens=12, page_size=8),
               dict()):
        eng = ServeEngine(model, params, n_slots=2, max_len=24, **kw)
        eng.run(reqs)
        step_s = eng.metrics["step_s"]
        dev_s = eng.metrics["device_s"]
        assert len(step_s) == len(dev_s) > 0
        for d, s in zip(dev_s, step_s):
            assert 0.0 < d <= s, f"device span {d} outside step span {s}"
        assert eng.summary()["device_ms_mean"] > 0


def test_ttft_non_negative_and_asserted():
    """TTFT is a perf_counter difference end-to-end; summary() refuses to
    report a negative one (a mixed-clock regression guard)."""
    cfg, model, params = regenerate.build_case("fp")
    reqs = request_workload(cfg, 3, gen=2, lengths=(6,), seed=0)
    eng = ServeEngine(model, params, n_slots=2, max_len=16,
                      max_batch_tokens=8, page_size=8)
    res = eng.run(reqs)
    assert all(r.ttft_s >= 0 for r in res.values())
    assert eng.summary()["ttft_s_mean"] >= 0
    res[reqs[0]["rid"]].ttft_s = -1e-3
    with pytest.raises(AssertionError, match="TTFT"):
        eng.summary()
