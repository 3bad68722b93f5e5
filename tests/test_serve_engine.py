"""Continuous-batching serve engine: scheduler invariants (FIFO admission,
no slot leaks, exactly-once retirement) and the token-equality contract —
every request decoded by the engine matches a solo static greedy_generate
run of the same model/params/max_len, bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import request_workload
from repro.launch.engine import ServeEngine
from repro.launch.serve import greedy_generate

GEN = 6
MAX_LEN = 14 + GEN + 8          # longest workload prompt + gen + slack


@pytest.fixture(scope="module")
def served(tiny_cfg):
    """Tiny model with the serving-default int8 KV cache."""
    from repro.models import build
    cfg = tiny_cfg.scaled(kv_quant_bits=8)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def drained(served):
    """8 mixed-length requests through 3 slots (queue deeper than slots)."""
    cfg, model, params = served
    reqs = request_workload(cfg, 8, gen=GEN, lengths=(6, 10, 14), seed=3)
    engine = ServeEngine(model, params, n_slots=3, max_len=MAX_LEN)
    results = engine.run(reqs)
    return engine, reqs, results


# ---------------------------------------------------------------- equality

def test_engine_tokens_match_solo_oracle(served, drained):
    _, model, params = served
    engine, reqs, results = drained
    assert engine.quantized_kv
    for r in reqs:
        want = np.asarray(greedy_generate(
            model, params, jnp.asarray(r["tokens"])[None], r["max_new_tokens"],
            MAX_LEN))[0]
        got = results[r["rid"]].tokens
        np.testing.assert_array_equal(got, want, err_msg=f"rid={r['rid']}")
        assert results[r["rid"]].prompt_len == len(r["tokens"])


def test_engine_fp_cache_also_matches_oracle(tiny_cfg):
    """The slot machinery is cache-dtype agnostic: fp cache path too."""
    from repro.models import build
    model = build(tiny_cfg)
    params = model.init(jax.random.PRNGKey(1))
    reqs = request_workload(tiny_cfg, 4, gen=4, lengths=(6, 10), seed=5)
    engine = ServeEngine(model, params, n_slots=2, max_len=MAX_LEN)
    assert not engine.quantized_kv
    results = engine.run(reqs)
    for r in reqs:
        want = np.asarray(greedy_generate(
            model, params, jnp.asarray(r["tokens"])[None],
            r["max_new_tokens"], MAX_LEN))[0]
        np.testing.assert_array_equal(results[r["rid"]].tokens, want)


# --------------------------------------------------------------- scheduler

def test_no_slot_leaks_after_drain(drained):
    engine, _, _ = drained
    assert engine.idle
    assert sorted(engine._free) == list(range(engine.n_slots))
    assert not engine._active


def test_every_request_retired_exactly_once(drained):
    engine, reqs, results = drained
    admits = [e for e in engine.events if e[0] == "admit"]
    retires = [e for e in engine.events if e[0] == "retire"]
    rids = [r["rid"] for r in reqs]
    assert sorted(r[1] for r in retires) == sorted(rids)
    assert sorted(a[1] for a in admits) == sorted(rids)
    assert sorted(results) == sorted(rids)
    for rid in rids:
        assert results[rid].retire_step >= results[rid].admit_step


def test_fifo_admission_order(drained):
    engine, reqs, _ = drained
    admit_order = [e[1] for e in engine.events if e[0] == "admit"]
    assert admit_order == [r["rid"] for r in reqs]


def test_slots_reused_and_never_double_booked(drained):
    engine, _, _ = drained
    occupied = set()
    per_slot_admits = {}
    for kind, rid, slot, _step in engine.events:
        if kind == "admit":
            assert slot not in occupied, f"slot {slot} double-booked"
            occupied.add(slot)
            per_slot_admits[slot] = per_slot_admits.get(slot, 0) + 1
        else:
            occupied.remove(slot)
    assert not occupied
    # 8 requests through 3 slots forces reuse
    assert max(per_slot_admits.values()) >= 2


def test_metrics_and_backpressure(drained):
    engine, reqs, results = drained
    s = engine.summary()
    assert s["n_requests"] == len(reqs) and s["n_slots"] == 3
    assert s["tok_per_s"] > 0 and s["wall_s"] > 0
    assert 0 < s["occupancy_mean"] <= 1.0
    # queue was deeper than the slot count at the start
    assert s["queue_depth_max"] >= len(reqs) - engine.n_slots
    assert s["generated_tokens"] == sum(r["max_new_tokens"] for r in reqs)
    for r in results.values():
        assert r.ttft_s > 0


# ------------------------------------------------------------------- edges

def test_single_token_request_retires_from_prefill(served):
    """max_new_tokens=1: the prefill step's token is the whole output —
    one device dispatch, no decode step."""
    _, model, params = served
    engine = ServeEngine(model, params, n_slots=2, max_len=MAX_LEN)
    rid = engine.submit(np.arange(1, 9, dtype=np.int32), 1)
    while not engine.idle:
        engine.step()
    assert rid in engine.results
    assert len(engine.results[rid].tokens) == 9
    assert engine.metrics["decode_steps"] == 0
    assert engine.exec.n_dispatch == 1


def test_submit_rejects_overflow_empty_dup_and_zero_budget(served):
    _, model, params = served
    engine = ServeEngine(model, params, n_slots=1, max_len=16)
    with pytest.raises(ValueError):
        engine.submit(np.arange(10, dtype=np.int32), 10)
    with pytest.raises(ValueError):
        engine.submit(np.zeros((0,), np.int32), 4)
    with pytest.raises(ValueError):
        engine.submit(np.arange(4, dtype=np.int32), 0)
    engine.submit(np.arange(4, dtype=np.int32), 2, rid=7)
    with pytest.raises(ValueError):
        engine.submit(np.arange(4, dtype=np.int32), 2, rid=7)


def test_unsupported_family_rejected_up_front():
    """Per-token positions in a paged pool are a dense-family contract;
    ssm/hybrid models must fail loudly at construction, not decode
    garbage."""
    from repro.configs import get_config
    from repro.models import build
    model = build(get_config("rwkv6_7b").smoke())
    with pytest.raises(NotImplementedError):
        ServeEngine(model, None, n_slots=1, max_len=16)


def test_eos_early_retirement(served):
    """With eos_id covering the whole vocab the request stops after one
    decode regardless of max_new_tokens budget."""
    _, model, params = served
    prompt = np.arange(2, 10, dtype=np.int32)
    probe = ServeEngine(model, params, n_slots=1, max_len=MAX_LEN)
    first = int(probe.run([{"rid": 0, "tokens": prompt,
                            "max_new_tokens": 1}])[0].tokens[-1])
    engine = ServeEngine(model, params, n_slots=1, max_len=MAX_LEN,
                         eos_id=first)
    out = engine.run([{"rid": 0, "tokens": prompt, "max_new_tokens": GEN}])
    assert len(out[0].tokens) == len(prompt) + 1
    assert out[0].tokens[-1] == first


def test_serve_benchmark_contract():
    """serve.py stays a thin CLI over the engine with the old contract."""
    from repro.launch.serve import serve_benchmark
    out = serve_benchmark(arch="catlm_60m", batch=2, prompt_len=8, gen=4,
                          transform="fp", kv_bits=8)
    assert out["tokens"].shape == (2, 12)
    assert out["tok_per_s"] > 0
    assert out["engine"]["n_requests"] == 2
