"""Property tests for the unified token-budget scheduler
(``repro.launch.scheduler.TokenBudgetScheduler``), driven two ways:

1. **Pure-host simulation** — the scheduler is plain python over the page
   allocator, so its plan/observe loop runs without any model: the test
   plays executor, feeding each logit consumer the token a
   position-faithful stub rule predicts. Invariants under random request
   lengths / budgets / slot counts / eos:

   - every step's packed token count <= ``max_batch_tokens`` (and the
     packed arrays really hold that many rows)
   - FIFO admission order is the submission order
   - no slot is both prefilling and decoding in one step
   - every admitted request retires exactly once, with exactly the
     trajectory the per-request simulation predicts (scheduler
     independence: packing must not leak between requests)
   - prefill chunks are contiguous, in-order, and cover each prompt
     exactly once; drained pools return every page

2. **Engine integration** — a ragged-contract stub model through
   ``ServeEngine``, asserting the engine reproduces the per-request
   simulation exactly.

Runs via tests/_hypothesis_shim: property cases when hypothesis is
installed, the seeded deterministic ports always."""
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_shim import given, settings, st

from repro.launch.engine import ServeEngine
from repro.launch.paged import PagePool, SlotPageTables
from repro.launch.scheduler import Request, TokenBudgetScheduler

_V = 64          # stub vocab


def _next_token(tok, pos):
    """Pure next-token rule: mixes token and absolute position so any
    packing bug (wrong offset, leaked row, stale page) changes output."""
    return (tok * 7 + pos * 13 + 1) % _V


def _simulate(prompt, max_new, eos_id):
    """The per-request ground truth the scheduler loop must reproduce."""
    toks = list(prompt)
    tok, pos = int(prompt[-1]), len(prompt) - 1
    for _ in range(max_new):
        tok = _next_token(tok, pos)
        toks.append(tok)
        pos += 1
        if tok == eos_id:
            break
    return toks


def _make_sched(n_slots, max_batch_tokens, max_len, page_size=4,
                prefill_chunk=0, eos_id=None, **kw):
    kv_len = -(-max_len // page_size) * page_size
    n_ptab = kv_len // page_size
    pool = PagePool(1 + n_slots * n_ptab, page_size)
    tables = SlotPageTables(pool, n_slots, n_ptab)
    return TokenBudgetScheduler(n_slots, max_batch_tokens, pool=pool,
                                tables=tables, prefill_chunk=prefill_chunk,
                                eos_id=eos_id, **kw)


def _drive(lengths, budgets, n_slots, max_batch_tokens, eos_id=None,
           prefill_chunk=0):
    """Run the scheduler's plan/observe loop with a python executor;
    returns (scheduler, per-rid token lists, step records)."""
    rng = np.random.default_rng(hash((tuple(lengths), n_slots)) % 2**32)
    reqs = [Request(rid, rng.integers(0, _V, p).astype(np.int32), g)
            for rid, (p, g) in enumerate(zip(lengths, budgets))]
    max_len = max(len(r.prompt) + r.max_new_tokens for r in reqs) + 1
    sched = _make_sched(n_slots, max_batch_tokens, max_len,
                        prefill_chunk=prefill_chunk, eos_id=eos_id)
    for r in reqs:
        sched.queue.append(r)
    done, steps = {}, []
    slot_rid = {}                       # current occupant per slot
    chunks = {r.rid: [] for r in reqs}  # rid -> [(offset, q_len)]
    guard = 0
    while not sched.idle:
        guard += 1
        assert guard < 10_000, "scheduler failed to drain"
        plan = sched.plan(guard)
        for rid, slot in plan.admitted:
            slot_rid[slot] = rid
        for slot, off, n, _toks in plan.prefill:
            chunks[slot_rid[slot]].append((off, n))
        # ---- invariants checked per plan
        assert plan.n_tokens <= max_batch_tokens
        dec_slots = {s for s, _, _ in plan.decode}
        pre_slots = [s for s, _, _, _ in plan.prefill]
        assert not dec_slots & set(pre_slots), (
            "slot both prefilling and decoding in one step")
        assert len(pre_slots) == len(set(pre_slots)), (
            "slot prefills twice in one step")
        packed = sched.pack(plan)
        assert packed["tokens"].shape == (max_batch_tokens, 1)
        assert packed["n_logits"] == len(plan.logit_consumers) <= n_slots
        # executor stand-in: each logit row's argmax from the stub rule
        toks = []
        for (kind, slot), row in zip(plan.logit_consumers,
                                     packed["logit_rows"]):
            fed = int(packed["tokens"][row, 0])
            pos = int(packed["pos"][row])
            toks.append(_next_token(fed, pos))
        steps.append((plan.n_tokens, sorted(dec_slots), pre_slots,
                      [rid for rid, _ in plan.admitted]))
        for seq in sched.observe(plan, np.asarray(toks), now=0.0):
            assert seq.req.rid not in done, "retired twice"
            done[seq.req.rid] = (list(seq.req.prompt) + seq.generated,
                                 seq.slot)
    return sched, reqs, done, steps, chunks


def _check_invariants(lengths, budgets, n_slots, max_batch_tokens,
                      eos_id=None, prefill_chunk=0):
    sched, reqs, done, steps, chunks = _drive(lengths, budgets, n_slots,
                                              max_batch_tokens, eos_id,
                                              prefill_chunk)
    # exactly-once retirement, FIFO admission order == submission order
    admitted = [rid for *_, rids in steps for rid in rids]
    assert admitted == [r.rid for r in reqs]
    assert sorted(done) == sorted(r.rid for r in reqs)
    # drained: all slots free, every page returned, reservations dropped
    assert sorted(sched.free) == list(range(n_slots))
    assert sched.pool.in_use == 0
    assert sched.tables.reserved_unallocated == 0
    # scheduler independence: trajectories match the per-request sim
    for r in reqs:
        want = _simulate(r.prompt, r.max_new_tokens, eos_id)
        got = done[r.rid][0]
        assert got == want, (r.rid, got, want)
    # the packed-token invariant held on every step (belt & braces: the
    # scheduler's own log agrees with what the driver saw)
    assert [t for t, *_ in sched.plan_log] == [t for t, *_ in steps]
    assert max(t for t, *_ in steps) <= max_batch_tokens
    # prefill chunks are contiguous, in order, and cover each prompt
    # exactly once (the chunked-admission state machine never re-reads
    # or skips prompt tokens)
    for r in reqs:
        offs = chunks[r.rid]
        assert offs[0][0] == 0
        assert sum(n for _, n in offs) == len(r.prompt)
        nxt = 0
        for off, n in offs:
            assert off == nxt and n >= 1
            if prefill_chunk:
                assert n <= prefill_chunk
            nxt = off + n


# --------------------------------------------------------------- property

@settings(max_examples=20, deadline=None)
@given(
    lens_budgets=st.lists(
        st.tuples(st.integers(1, 20), st.integers(1, 6)),
        min_size=1, max_size=12),
    n_slots=st.integers(1, 4),
    budget_extra=st.integers(0, 12),
    eos_id=st.integers(-1, _V - 1),
    prefill_chunk=st.integers(0, 5),
)
def test_property_scheduler_invariants(lens_budgets, n_slots, budget_extra,
                                       eos_id, prefill_chunk):
    lengths = [p for p, _ in lens_budgets]
    budgets = [g for _, g in lens_budgets]
    _check_invariants(lengths, budgets, n_slots, n_slots + budget_extra,
                      eos_id if eos_id >= 0 else None, prefill_chunk)


# ---------------------------------------------- deterministic seeded ports

@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
@pytest.mark.parametrize("n_slots", [1, 3])
def test_scheduler_invariants_ports(seed, n_slots):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 12))
    lengths = rng.integers(1, 21, n).tolist()
    budgets = rng.integers(1, 7, n).tolist()
    budget = n_slots + int(rng.integers(0, 13))
    eos_id = int(rng.integers(0, _V)) if seed % 2 else None
    chunk = int(rng.integers(0, 6)) if seed % 3 else 0
    _check_invariants(lengths, budgets, n_slots, budget, eos_id, chunk)


def test_tight_budget_still_makes_progress():
    """budget == n_slots: decode saturates the budget whenever all slots
    run, yet prefill always gets through eventually (a prefilling slot
    never decodes, freeing at least one token of headroom)."""
    _check_invariants([12, 12, 12, 12], [5, 5, 5, 5], n_slots=2,
                      max_batch_tokens=2)


def test_undersized_pool_head_of_line_waits_fifo():
    """A pool too small for concurrent admissions must queue the head
    (never skip to a smaller younger request) and still drain with every
    invariant intact."""
    rng = np.random.default_rng(11)
    reqs = [Request(rid, rng.integers(0, _V, p).astype(np.int32), g)
            for rid, (p, g) in enumerate([(8, 4), (8, 4), (2, 2), (8, 4)])]
    page_size = 4
    # 3 allocatable pages: exactly one (8+4)-token request fits at a time
    pool = PagePool(1 + 3, page_size)
    tables = SlotPageTables(pool, n_slots=3, n_ptab=3)
    sched = TokenBudgetScheduler(3, 16, pool=pool, tables=tables)
    for r in reqs:
        sched.queue.append(r)
    admitted, done = [], {}
    for step in range(200):
        if sched.idle:
            break
        plan = sched.plan(step)
        admitted += [rid for rid, _ in plan.admitted]
        packed = sched.pack(plan)
        toks = [_next_token(int(packed["tokens"][row, 0]),
                            int(packed["pos"][row]))
                for row in packed["logit_rows"][:packed["n_logits"]]]
        for seq in sched.observe(plan, np.asarray(toks), now=0.0):
            done[seq.req.rid] = True
    assert sched.idle
    assert admitted == [0, 1, 2, 3], "FIFO broken by head-of-line wait"
    assert sorted(done) == [0, 1, 2, 3]
    assert pool.in_use == 0
    # concurrency really was capped: at most one 12-token resident
    assert pool.peak_in_use <= 3


def test_long_prompt_interleaves_with_decode():
    """A 17-token prompt under budget 5 must take multiple steps while
    the short request decodes alongside — the head-of-line decoupling
    the unified schedule exists for."""
    sched, reqs, done, steps, chunks = _drive([3, 17], [4, 2], n_slots=2,
                                              max_batch_tokens=5)
    assert len(chunks[1]) >= 4          # 17 tokens through <=5/step
    mixed = [s for s in steps if s[1] and s[2]]   # decode AND prefill
    assert mixed, "expected steps mixing decode tokens and prefill chunks"


# ------------------------------------------------- engine integration stub

class _RaggedStubModel:
    """Dense-family stand-in honoring the engine's model contract: a
    paged cache (so the engine's pool bookkeeping runs for real) and the
    ragged step (the greedy token at packed ``logit_rows``, a pure
    function of the fed token and its position)."""

    def __init__(self, cfg):
        self.cfg = cfg

    def init_paged_cache(self, n_pages, page_size):
        return {"k": jnp.zeros((1, n_pages, page_size, 1, 1), jnp.float32),
                "v": jnp.zeros((1, n_pages, page_size, 1, 1), jnp.float32)}

    def ragged_step(self, params, tokens, cache, logit_rows, greedy=False,
                    **kw):
        import jax.nn
        fed = jnp.take(tokens[:, 0], logit_rows)
        pos = jnp.take(cache["pos"], logit_rows)
        nxt = _next_token(fed, pos)
        if greedy:      # device-resident sampling (models.dense contract)
            return nxt.astype(jnp.int32), dict(cache)
        return (jax.nn.one_hot(nxt, _V)[:, None, :],
                dict(cache))


_STUB = None


def _stub():
    global _STUB
    if _STUB is None:
        from repro.configs import get_config
        _STUB = _RaggedStubModel(get_config("catlm_60m").smoke())
    return _STUB


@pytest.mark.parametrize("budget,chunk", [(3, 0), (8, 0), (5, 4)])
def test_unified_engine_matches_legacy_on_stub(budget, chunk):
    """The engine over the stub reproduces the per-request simulation
    under budgets that reshuffle packing and a chunk cap."""
    rng = np.random.default_rng(7)
    reqs = [{"rid": i, "tokens": rng.integers(0, _V, p).astype(np.int32),
             "max_new_tokens": g}
            for i, (p, g) in enumerate([(5, 3), (11, 2), (1, 4), (8, 1),
                                        (13, 5)])]
    uni = ServeEngine(_stub(), {}, n_slots=3, max_len=24,
                      max_batch_tokens=budget, prefill_chunk=chunk,
                      page_size=4)
    ures = uni.run(reqs)
    for r in reqs:
        want = _simulate(r["tokens"], r["max_new_tokens"], None)
        assert ures[r["rid"]].tokens.tolist() == want, r["rid"]
    # engine-level mirrors of the scheduler invariants
    assert max(t for t, *_ in uni.sched.plan_log) <= budget
    admits = [e[1] for e in uni.events if e[0] == "admit"]
    assert admits == [r["rid"] for r in reqs]
    retires = sorted(e[1] for e in uni.events if e[0] == "retire")
    assert retires == sorted(r["rid"] for r in reqs)
    assert uni.idle and uni.pool.in_use == 0


# --------------------------------------------- hot-loop regression tests

@given(want=st.integers(1, 64), budget=st.integers(1, 64),
       chunk=st.integers(0, 16))
@settings(max_examples=200, deadline=None)
def test_property_chunk_never_zero(want, budget, chunk):
    """Budget-remainder audit: for every (want >= 1, budget >= 1) a
    caller can reach ``_chunk`` with, the sliced chunk is >= 1 — a slot
    can never stall a cycle on a 0-token chunk while budget remains."""
    sched = _make_sched(2, max(budget, 2), 32, prefill_chunk=chunk)
    n = sched._chunk(want, budget)
    assert 1 <= n <= min(want, budget)
    if chunk:
        assert n <= chunk


def test_plan_log_is_a_capped_ring():
    """The per-step plan log must not grow without bound on a long-lived
    engine; the running counters keep reporting over evicted steps."""
    sched = _make_sched(2, 6, 64, plan_log_cap=8)
    assert sched.plan_log.maxlen == 8
    rng = np.random.default_rng(3)
    for rid in range(12):
        sched.queue.append(Request(
            rid, rng.integers(0, _V, 3).astype(np.int32), 2))
    guard = 0
    while not sched.idle:
        guard += 1
        assert guard < 1000
        plan = sched.plan(guard)
        sched.pack(plan)
        toks = np.asarray([1] * len(plan.logit_consumers))
        for seq in sched.observe(plan, toks, now=0.0):
            pass
    assert len(sched.plan_log) <= 8
    assert sched.n_plans == guard > 8          # counted past the cap
    assert 0 < sched.packed_tokens_max <= 6    # tracked outside the ring


def test_pack_reuses_descriptor_buffers():
    """pack() reuses a fixed ring of host descriptor buffers across
    steps (no per-step allocation in the hot loop). The ring is 2 deep —
    the pipelined loop may still hold step N's descriptors (aliased by a
    possibly-unmaterialized ``jnp.asarray``) while step N+1 packs — so
    consecutive packs alternate buffer sets and packs two steps apart
    return the SAME objects, refilled correctly (packing the same plan
    repeatedly gives equal contents)."""
    sched = _make_sched(2, 6, 32)
    rng = np.random.default_rng(5)
    for rid in range(2):
        sched.queue.append(Request(
            rid, rng.integers(0, _V, 4).astype(np.int32), 2))
    plan = sched.plan(0)
    packs = [sched.pack(plan) for _ in range(4)]
    snap = {k: np.array(v, copy=True) for k, v in packs[0].items()
            if isinstance(v, np.ndarray)}

    def _same_buf(a, b):
        return (b.base is not None or b is a or
                b.__array_interface__["data"] ==
                a.__array_interface__["data"])

    for step in (2, 3):         # ring period 2: step k aliases step k-2
        for k, v in packs[step].items():
            if isinstance(v, np.ndarray):
                assert _same_buf(packs[step - 2][k], v), (step, k)
    for k, v in packs[1].items():   # adjacent steps must NOT alias —
        if isinstance(v, np.ndarray) and v.size:    # that is the ring's
            assert (v.__array_interface__["data"][0]  # reason to exist
                    != packs[0][k].__array_interface__["data"][0]), k
    for p in packs:
        for k, v in p.items():
            if isinstance(v, np.ndarray):
                np.testing.assert_array_equal(v, snap[k])


# ------------------------------------------- adaptive speculative depth

def _make_spec_sched(n_slots, max_batch_tokens, max_len, spec_k,
                     adaptive=False, page_size=4):
    kv_len = -(-(max_len + spec_k + 1) // page_size) * page_size
    n_ptab = kv_len // page_size
    pool = PagePool(1 + n_slots * n_ptab, page_size)
    tables = SlotPageTables(pool, n_slots, n_ptab)
    dpool = PagePool(1 + n_slots * n_ptab, page_size)
    dtables = SlotPageTables(dpool, n_slots, n_ptab)
    return TokenBudgetScheduler(n_slots, max_batch_tokens, pool=pool,
                                tables=tables, spec_k=spec_k,
                                draft_tables=dtables,
                                adaptive_spec=adaptive)


def _drive_spec(lengths, budgets, n_slots, max_batch_tokens, spec_k,
                adaptive, accept_p, seed=0):
    """Spec-mode plan/observe loop with a python draft+target executor.
    Drafts are the stub rule's correct continuation with probability
    ``accept_p`` per position (chain-fed: a wrong draft derails the
    rest, like a real draft model). Returns (sched, reqs, done tokens,
    every per-slot k' the planner chose)."""
    rng = np.random.default_rng(seed)
    reqs = [Request(rid, rng.integers(0, _V, p).astype(np.int32), g)
            for rid, (p, g) in enumerate(zip(lengths, budgets))]
    max_len = max(len(r.prompt) + r.max_new_tokens for r in reqs) + 1
    sched = _make_spec_sched(n_slots, max_batch_tokens, max_len, spec_k,
                             adaptive=adaptive)
    page_size = sched.tables.pool.page_size
    for r in reqs:
        sched.queue.append(r)
    done, k_seen, guard = {}, [], 0
    while not sched.idle:
        guard += 1
        assert guard < 10_000, "spec scheduler failed to drain"
        plan = sched.plan(guard)
        for slot, tok, p in plan.spec:
            kx = plan.spec_k_of[slot]
            k_seen.append(kx)
            # ---- the budget/reservation math k' must never exceed
            assert 1 <= kx <= spec_k
            assert plan.spec_rows(slot) == kx + 1 <= spec_k + 1
            # target pages cover the last verify position p+k', the
            # draft pool the full worst case p+spec_k (the draft scan
            # always runs spec_k steps regardless of k')
            assert sched.tables.table[slot, (p + kx) // page_size] != 0
            assert sched.draft_tables.table[
                slot, (p + spec_k) // page_size] != 0
            fed, drafts = tok, []
            for j in range(spec_k):
                c = _next_token(fed, p + j)
                d = c if rng.random() < accept_p else (c + 1) % _V
                drafts.append(d)
                fed = d
            plan.spec_drafts[slot] = np.asarray(drafts, np.int32)
        assert plan.n_tokens <= max_batch_tokens
        packed = sched.pack(plan)
        toks = [_next_token(int(packed["tokens"][row, 0]),
                            int(packed["pos"][row]))
                for row in packed["logit_rows"][:packed["n_logits"]]]
        for seq in sched.observe(plan, np.asarray(toks), now=0.0):
            done[seq.req.rid] = list(seq.req.prompt) + seq.generated
    assert sched.pool.in_use == 0
    assert sched.draft_tables.pool.in_use == 0
    return sched, reqs, done, k_seen


@settings(max_examples=15, deadline=None)
@given(
    lens_budgets=st.lists(
        st.tuples(st.integers(1, 12), st.integers(1, 8)),
        min_size=1, max_size=6),
    n_slots=st.integers(1, 3),
    spec_k=st.integers(1, 4),
    adaptive=st.booleans(),
    accept_pct=st.integers(0, 100),
)
def test_property_adaptive_spec_invariants(lens_budgets, n_slots, spec_k,
                                           adaptive, accept_pct):
    """Adaptive draft depth never breaks the budget/reservation math
    (asserted inside the drive) and never changes the output: every
    appended token is still a target argmax, so trajectories match the
    per-request simulation at ANY acceptance rate and either mode."""
    lengths = [p for p, _ in lens_budgets]
    budgets = [g for _, g in lens_budgets]
    budget = n_slots * (spec_k + 1) + 2
    _, reqs, done, k_seen = _drive_spec(lengths, budgets, n_slots, budget,
                                        spec_k, adaptive,
                                        accept_pct / 100.0)
    for r in reqs:
        want = _simulate(r.prompt, r.max_new_tokens, None)
        assert done[r.rid] == want, (r.rid, done[r.rid], want)
    if not adaptive:
        assert all(k == spec_k for k in k_seen)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("adaptive", [False, True])
def test_adaptive_spec_invariants_ports(seed, adaptive):
    """Deterministic port of the property (runs without hypothesis)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 7))
    lengths = rng.integers(1, 13, n).tolist()
    budgets = rng.integers(1, 9, n).tolist()
    n_slots = int(rng.integers(1, 4))
    spec_k = int(rng.integers(1, 5))
    accept_p = float(rng.random())
    budget = n_slots * (spec_k + 1) + 2
    _, reqs, done, k_seen = _drive_spec(lengths, budgets, n_slots, budget,
                                        spec_k, adaptive, accept_p,
                                        seed=seed)
    for r in reqs:
        want = _simulate(r.prompt, r.max_new_tokens, None)
        assert done[r.rid] == want, (r.rid, done[r.rid], want)
    if not adaptive:
        assert all(k == spec_k for k in k_seen)


def test_adaptive_spec_depth_tracks_acceptance():
    """Direction check: all-rejected drafts drive a slot's k' down to 1
    after its first cycle; all-accepted drafts keep k' at the cap. A
    fresh occupant of a reused slot starts back at the cap (the EMA is
    cleared on retire — no inherited pessimism)."""
    spec_k = 4
    _, _, _, k_low = _drive_spec([4, 4, 4], [8, 8, 8], 1, 2 * (spec_k + 1),
                                 spec_k, True, accept_p=0.0)
    # slot reuse: each request's FIRST cycle is optimistic (k' = cap),
    # every later cycle has EMA 0 -> k' = 1
    assert k_low.count(spec_k) == 3 and set(k_low) == {1, spec_k}
    _, _, _, k_high = _drive_spec([4, 4], [8, 8], 2, 2 * (spec_k + 1),
                                  spec_k, True, accept_p=1.0)
    assert all(k == spec_k for k in k_high)


def test_scheduler_reset_reuses_engine():
    """reset() returns a drained scheduler to its initial state: a second
    identical workload must produce identical plans and tokens."""
    def drain(sched, reqs):
        for r in reqs:
            sched.queue.append(r)
        toks_out, guard = {}, 0
        while not sched.idle:
            guard += 1
            assert guard < 1000
            plan = sched.plan(guard)
            packed = sched.pack(plan)
            toks = [_next_token(int(packed["tokens"][row, 0]),
                                int(packed["pos"][row]))
                    for _, row in zip(plan.logit_consumers,
                                      packed["logit_rows"])]
            for seq in sched.observe(plan, np.asarray(toks), now=0.0):
                toks_out[seq.req.rid] = list(seq.generated)
        return toks_out

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, _V, p).astype(np.int32) for p in (5, 3, 9)]
    sched = _make_sched(2, 5, 32)
    first = drain(sched, [Request(i, p, 3)
                          for i, p in enumerate(prompts)])
    sched.reset()
    assert sched.pool.in_use == 0 and not sched.plan_log
    second = drain(sched, [Request(i, p, 3)
                           for i, p in enumerate(prompts)])
    assert first == second
