"""Property tests for the serve engine's slot allocator, driven through a
deterministic stub model (next token is a pure function of the fed token
and its position, so every request's full trajectory is computable in
python without running a transformer). Invariants under random request
lengths / decode budgets / eos positions:

  - FIFO admission order is the submission order
  - no slot is double-booked; every slot returns to the free list
  - every request retires exactly once, with exactly the tokens the
    position-faithful python simulation predicts (scheduler independence:
    batching/slot reuse must not leak between requests)

The stub (``test_scheduler_properties._stub``) honours the engine's
model contract: a paged cache and a ragged step whose token at each
logit row is the stub rule applied to that row's fed token and position.

Runs via tests/_hypothesis_shim: property cases when hypothesis is
installed, the seeded deterministic ports always."""
import numpy as np
import pytest

from _hypothesis_shim import given, settings, st
from test_scheduler_properties import _V, _simulate, _stub

from repro.launch.engine import ServeEngine


def _check_invariants(lengths, budgets, n_slots, eos_id):
    reqs = []
    rng = np.random.default_rng(hash((tuple(lengths), n_slots)) % 2**32)
    for rid, (p, g) in enumerate(zip(lengths, budgets)):
        reqs.append({"rid": rid,
                     "tokens": rng.integers(0, _V, p).astype(np.int32),
                     "max_new_tokens": g})
    max_len = max(len(r["tokens"]) + r["max_new_tokens"] for r in reqs) + 1
    engine = ServeEngine(_stub(), {}, n_slots=n_slots, max_len=max_len,
                         eos_id=eos_id)
    results = engine.run(reqs)

    # exactly-once retirement, FIFO admission
    admits = [e for e in engine.events if e[0] == "admit"]
    retires = [e for e in engine.events if e[0] == "retire"]
    assert [a[1] for a in admits] == [r["rid"] for r in reqs]
    assert sorted(r[1] for r in retires) == sorted(r["rid"] for r in reqs)
    assert sorted(results) == sorted(r["rid"] for r in reqs)

    # no double-booking; every slot freed
    occupied = set()
    for kind, _rid, slot, _step in engine.events:
        if kind == "admit":
            assert slot not in occupied, f"slot {slot} double-booked"
            occupied.add(slot)
        else:
            occupied.discard(slot)
    assert not occupied
    assert engine.idle
    assert sorted(engine._free) == list(range(n_slots))

    # scheduler independence: engine tokens == per-request simulation
    for r in reqs:
        want = _simulate(r["tokens"], r["max_new_tokens"], eos_id)
        got = results[r["rid"]].tokens.tolist()
        assert got == want, (r["rid"], got, want)


# --------------------------------------------------------------- property

@settings(max_examples=15, deadline=None)
@given(
    lens_budgets=st.lists(
        st.tuples(st.integers(1, 12), st.integers(1, 6)),
        min_size=1, max_size=10),
    n_slots=st.integers(1, 4),
    eos_id=st.integers(-1, _V - 1),
)
def test_property_slot_allocator_invariants(lens_budgets, n_slots, eos_id):
    lengths = [p for p, _ in lens_budgets]
    budgets = [g for _, g in lens_budgets]
    _check_invariants(lengths, budgets, n_slots,
                      eos_id if eos_id >= 0 else None)


# ---------------------------------------------- deterministic seeded ports

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("n_slots", [1, 3])
def test_slot_allocator_invariants_ports(seed, n_slots):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 10))
    lengths = rng.integers(1, 13, n).tolist()
    budgets = rng.integers(1, 7, n).tolist()
    # eos drawn from the small stub vocab so some requests genuinely stop
    # early and others never see it
    eos_id = int(rng.integers(0, _V)) if seed % 2 else None
    _check_invariants(lengths, budgets, n_slots, eos_id)


def test_eos_on_prefill_token_retires_without_decode():
    """A request whose very first (prefill-emitted) token is eos must
    retire before ever joining a decode batch: the synchronous loop
    dispatches no decode; the pipelined loop dispatches one before it
    has seen the eos, and discards it as a mispredict."""
    prompt = np.asarray([3, 5], np.int32)
    first = _simulate(prompt, 1, None)[-1]
    for pipeline in (False, True):
        engine = ServeEngine(_stub(), {}, n_slots=2, max_len=16,
                             eos_id=first, pipeline=pipeline)
        out = engine.run([{"rid": 0, "tokens": prompt,
                           "max_new_tokens": 5}])
        assert out[0].tokens.tolist() == [3, 5, first]
        assert engine.metrics["generated_tokens"] == 1
        assert engine.metrics["decode_steps"] == int(pipeline)
        assert engine.summary()["mispredicts"] == int(pipeline)
