"""Prefix-cache bitwise correctness against the golden fixtures.

Serving a cached prefix page must be indistinguishable from recomputing
it: attention always reads the *stored* (post-quantization) page
content, and identical tokens at identical positions produce identical
codes/scales, so prefix-cache-on decoded tokens must match the
checked-in fixtures **bit for bit** — for all three serving configs
(fp, int8 KV, int4-packed weights), under three token budgets and
chunk caps, single-device and on a tp=4 mesh.

The differential tests then force the sharing machinery to actually
fire: a shared-system-prompt workload (real hits, cache-on vs cache-off
token equality) and an adversarial mid-page divergence pair (the COW
boundary lands inside a page, so a wrong or missing device page copy
changes tokens).
"""
import json

import numpy as np
import pytest

from golden import regenerate

from repro.data import request_workload
from repro.launch.engine import ServeEngine

# step geometries the prefix cache rides on; page_size 8 keeps the
# fixture prompts (6/10 tokens) spanning a full + partial page
SCHEDULES = [
    ("chunk8", dict(page_size=8, prefill_chunk=8)),
    ("budget64", dict(page_size=8, max_batch_tokens=64)),
    ("budget8", dict(page_size=8, max_batch_tokens=8)),
]


def _golden(case):
    with open(regenerate.fixture_path(case)) as f:
        return json.load(f)["tokens"]


@pytest.mark.parametrize("case", sorted(regenerate.CASES))
@pytest.mark.parametrize("sched_kw", SCHEDULES,
                         ids=[n for n, _ in SCHEDULES])
def test_prefix_cache_on_matches_golden_bitwise(case, sched_kw):
    """Cache-on output equals the (cache-off) golden fixture exactly,
    for every step geometry the cache rides on."""
    _, kw = sched_kw
    got = regenerate.run_case(case, prefix_cache=True, **kw)
    for rid, want in _golden(case).items():
        assert got[rid] == want, (
            f"{case}: prefix-cache-on tokens for rid={rid} diverged from "
            f"the golden fixture under {kw}")


@pytest.mark.parametrize("sched_kw", SCHEDULES,
                         ids=[n for n, _ in SCHEDULES])
def test_shared_prefix_on_vs_off_identical(sched_kw):
    """A workload sharing a 12-token system prompt (full page + mid-page
    partial at page_size 8): the cache really hits AND the decoded
    tokens stay identical to the cache-off engine."""
    _, kw = sched_kw
    cfg, model, params = regenerate.build_case("int8_kv")
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS, seed=regenerate.SEED,
                            shared_prefix=12)
    off = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=40, **kw).run(reqs)
    on_eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                         max_len=40, prefix_cache=True, **kw)
    on = on_eng.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            on[r["rid"]].tokens, off[r["rid"]].tokens,
            err_msg=f"rid={r['rid']} under {kw}")
    stats = on_eng.summary()
    assert stats["prefix_hits"] > 0, "shared-prefix workload never hit"
    assert stats["prefix_hit_tokens"] > 0
    assert on_eng.pool.in_use == on_eng.prefix.resident  # drained to trie


@pytest.mark.parametrize("sched_kw", SCHEDULES,
                         ids=[n for n, _ in SCHEDULES])
def test_midpage_divergence_cow_boundary_exact(sched_kw):
    """Adversarial COW: the second prompt repeats the first for 10 of 16
    tokens, diverging INSIDE the second page (page_size 8). The hit ends
    mid-page, so admission must COW-split that page — a missing or
    misordered device page copy corrupts rows [8, 10) and changes
    tokens. Served one slot at a time so the second admission sees the
    first's registered pages."""
    _, kw = sched_kw
    cfg, model, params = regenerate.build_case("int8_kv")
    rng = np.random.default_rng(5)
    a = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    b = np.concatenate([a[:10],
                        rng.integers(0, cfg.vocab, 6)]).astype(np.int32)
    assert (a[:10] == b[:10]).all() and a[10] != b[10]
    reqs = [{"rid": 0, "tokens": a, "max_new_tokens": 4},
            {"rid": 1, "tokens": b, "max_new_tokens": 4}]
    off = ServeEngine(model, params, n_slots=1, max_len=32, **kw).run(reqs)
    on_eng = ServeEngine(model, params, n_slots=1, max_len=32,
                         prefix_cache=True, **kw)
    on = on_eng.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            on[r["rid"]].tokens, off[r["rid"]].tokens,
            err_msg=f"rid={r['rid']} under {kw}")
    stats = on_eng.summary()
    assert stats["cow_copies"] >= 1, (
        "mid-page divergence admitted without a COW split")
    assert stats["prefix_hit_tokens"] >= 10


def test_prefix_cache_matches_golden_at_tp4():
    """Shared-prefix workload on a (1, 4) tensor-parallel mesh with the
    cache on vs a single-device cache-off engine: the COW device page
    copy runs over head-sharded pools and must stay token-identical."""
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
    reqs = request_workload(cfg, 5, gen=4, lengths=(6, 10), seed=3,
                            shared_prefix=12)
    solo = ServeEngine(model, params, n_slots=2, max_len=40).run(reqs)
    mesh = make_mesh((1, 4), ("data", "model"))
    on_eng = ServeEngine(model, params, n_slots=2, max_len=40, mesh=mesh,
                         max_batch_tokens=8,
                         page_size=8, prefix_cache=True)
    on = on_eng.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(on[r["rid"]].tokens,
                                      solo[r["rid"]].tokens,
                                      err_msg=f"rid={r['rid']}")
    assert on_eng.summary()["prefix_hits"] > 0
