"""Unified token-budget scheduler: end-to-end engine correctness.

The unified step packs decode tokens and prefill chunks into ONE ragged
model invocation (``launch/scheduler.py`` + ``launch/executor.py`` +
``models.dense.ragged_step``). Per-row numerics do not depend on what
else a step packs, so decoded tokens must be **bitwise identical** to the
checked-in golden fixtures — across budgets (which reshuffle step packing
arbitrarily), with and without a prefill-chunk cap, and at tensor
parallelism — and to ``greedy_generate``'s solo static decode. The
paged-attention kernel path is rtol-level and is pinned at >= 0.9 token
agreement.
"""
import json

import numpy as np
import pytest

from golden import regenerate

from repro.data import request_workload
from repro.launch.engine import ServeEngine


def _golden(case):
    with open(regenerate.fixture_path(case)) as f:
        return json.load(f)["tokens"]


@pytest.mark.parametrize("case", sorted(regenerate.CASES))
@pytest.mark.parametrize("kw", [
    dict(max_batch_tokens=6),                     # tight: chunked admission
    dict(max_batch_tokens=64),                    # loose: whole prompts fit
    dict(max_batch_tokens=8, prefill_chunk=4),    # chunk cap on top
], ids=["budget6", "budget64", "budget8chunk4"])
def test_unified_matches_golden_bitwise(case, kw):
    got = regenerate.run_case(case, page_size=8, **kw)
    golden = _golden(case)
    for rid, want in golden.items():
        assert got[rid] == want, (
            f"{case} {kw}: unified tokens for rid={rid} diverged from the "
            f"golden fixture")


def test_unified_matches_golden_at_tp2():
    """tp=2 mesh (gather mode) on the exact golden config: unified-step
    output stays bitwise equal to the single-device golden fixture
    (column slices of a matmul are exact; smoke catlm's n_kv_heads=2
    caps whole-head splits at tp=2)."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 local devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count)")
    from repro.distributed.compat import make_mesh

    cfg, model, params = regenerate.build_case("fp")
    mesh = make_mesh((1, 2), ("data", "model"))
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=regenerate.MAX_LEN,
                      max_batch_tokens=6, mesh=mesh)
    res = eng.run(reqs)
    golden = _golden("fp")
    for r in reqs:
        assert np.asarray(res[r["rid"]].tokens).tolist() \
            == golden[str(r["rid"])], f"tp=2 diverged for rid={r['rid']}"


def test_unified_mesh_tp4_token_identical():
    """tp=4 on an MHA override (same convention as test_paged_cache):
    the unified mesh engine must be token-identical to the solo engine
    at another budget. Also pins that dp meshes are rejected loudly."""
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
    uni = ServeEngine(model, params, n_slots=2, max_len=24, mesh=mesh,
                      max_batch_tokens=6, page_size=8).run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(uni[r["rid"]].tokens,
                                      solo[r["rid"]].tokens,
                                      err_msg=f"rid={r['rid']}")
    with pytest.raises(NotImplementedError, match="tensor-parallel only"):
        ServeEngine(model, params, n_slots=2, max_len=24,
                    mesh=make_mesh((2, 2), ("data", "model")),
                    max_batch_tokens=6)


@pytest.mark.parametrize("kw", [
    dict(max_batch_tokens=7),
    # prefill_chunk also caps the kernel's query-block width (narrower
    # than the packed width — the inv_* maps must stay packed-wide)
    dict(max_batch_tokens=8, prefill_chunk=4, page_size=8),
], ids=["budget7", "budget8chunk4"])
def test_unified_ragged_kernel_token_agreement(kw):
    """paged_kernel=True routes the whole mixed batch through the ragged
    Pallas kernel (pages stream once per work item) — rtol-level, so pin
    agreement instead of bitwise equality."""
    cfg, model, params = regenerate.build_case("int8_kv")
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=regenerate.MAX_LEN,
                      paged_kernel=True, **kw)
    res = eng.run(reqs)
    golden = _golden("int8_kv")
    agree = np.mean([
        (np.asarray(res[r["rid"]].tokens)
         == np.asarray(golden[str(r["rid"])])).mean() for r in reqs])
    assert agree >= 0.9, f"token agreement {agree:.2f} < 0.9"


def test_pure_decode_fast_path_engages_and_agrees():
    """Steps whose plan is pure decode (no prefill/spec/cow) must
    dispatch through ``RaggedExecutor.decode_step`` — the compact
    slot-major batch the fused decode layer wants — and the engine's
    output stays pinned against the golden fixture like the ragged
    kernel path (rtol-level kernels, so >= 0.9 agreement)."""
    cfg, model, params = regenerate.build_case("int8_kv")
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=regenerate.MAX_LEN,
                      max_batch_tokens=8, paged_kernel=True, page_size=8)
    assert eng.exec.supports_decode_step
    calls = {"n": 0}
    orig = eng.exec.decode_step

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    eng.exec.decode_step = counted
    res = eng.run(reqs)
    assert calls["n"] > 0, "pure-decode fast path never engaged"
    golden = _golden("int8_kv")
    agree = np.mean([
        (np.asarray(res[r["rid"]].tokens)
         == np.asarray(golden[str(r["rid"])])).mean() for r in reqs])
    assert agree >= 0.9, f"token agreement {agree:.2f} < 0.9"
    s = eng.summary()
    assert s["launches_per_token"] > 0
    # host dispatches can't exceed one per engine step on this path
    assert s["dispatch_per_step"] <= 1.0 + 1e-9


def test_unified_eos_and_single_token_budgets():
    """eos retirement and max_new=1 requests decode exactly the solo
    ``greedy_generate`` trajectory, cut after its first eos, under a
    budget that forces multi-step prefill."""
    import jax.numpy as jnp

    from repro.launch.serve import greedy_generate

    cfg, model, params = regenerate.build_case("fp")
    reqs = request_workload(cfg, 5, gen=3, lengths=(6, 10), seed=3)
    reqs[1]["max_new_tokens"] = 1
    solo = {r["rid"]: np.asarray(greedy_generate(
        model, params, jnp.asarray(r["tokens"])[None], r["max_new_tokens"],
        24))[0].tolist() for r in reqs}
    eos = solo[0][len(reqs[0]["tokens"])]           # first generated token

    def want(r):
        p = len(r["tokens"])
        gen = solo[r["rid"]][p:]
        return solo[r["rid"]][:p] + (gen[:gen.index(eos) + 1]
                                     if eos in gen else gen)

    for n_slots, budget in ((2, 4), (3, 16)):
        u2 = ServeEngine(model, params, n_slots=n_slots, max_len=24,
                         eos_id=eos, max_batch_tokens=budget, page_size=8)
        ur = u2.run(reqs)
        for r in reqs:
            assert ur[r["rid"]].tokens.tolist() == want(r), (
                n_slots, budget, r["rid"])
    assert u2.pool.in_use == 0, "drained unified engine must free all pages"


def test_unified_summary_and_validation():
    cfg, model, params = regenerate.build_case("fp")
    reqs = request_workload(cfg, 3, gen=2, lengths=(6,), seed=0)
    eng = ServeEngine(model, params, n_slots=2, max_len=16,
                      max_batch_tokens=8, page_size=8)
    eng.run(reqs)
    s = eng.summary()
    assert s["max_batch_tokens"] == 8
    assert s["packed_tokens_max"] <= 8
    assert s["itl_p95_s"] >= s["itl_p50_s"] > 0
    assert 0 < s["resident_kv_bytes_peak"] <= s["kv_capacity_bytes"]
    with pytest.raises(ValueError, match="max_batch_tokens"):
        ServeEngine(model, params, n_slots=4, max_len=16,
                    max_batch_tokens=2)
    with pytest.raises(ValueError, match="schedule"):
        ServeEngine(model, params, n_slots=2, max_len=16,
                    schedule="sjf")
