"""Paged KV cache: equivalence, allocator, and kernel coverage.

- **Golden equivalence across pool geometries**: the engine (page 8
  under a 4-token budget, page 4 with 8-token prefill chunks, page 8 at
  the default budget) must reproduce the checked-in golden token
  fixtures *bitwise* for fp, int8-KV, and w4-packed configs; the
  gathered logical view is the same tensor whatever the page size, so
  this is equality, not tolerance. The tp=4 mesh variant pins token
  identity against the solo engine (the golden cfg is GQA n_kv_heads=2,
  which tp=4 correctly rejects — same MHA-override convention as
  tests/test_tp_serve.py).
- **Allocator properties** (``repro.launch.paged``): no double
  allocation, exactly-once free, null page never handed out, and
  fragmentation bounded — any free page satisfies any request, so
  ``available`` pages are always all allocatable. Hypothesis drives
  random op sequences when installed; seeded deterministic ports always
  run (tests/_hypothesis_shim).
- **Paged-attention kernel**: Pallas kernel vs the jnp oracle at rtol
  1e-5 including ragged last pages and null-page table entries; the
  gather fallback matches exactly; ops dispatch routes fp pools to the
  fallback.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_shim import HAVE_HYPOTHESIS, given, settings, st
from golden import regenerate
from repro.data import request_workload
from repro.launch.engine import ServeEngine
from repro.launch.paged import NULL_PAGE, PagePool, SlotPageTables

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")

_BUILT = {}


def built(case):
    if case not in _BUILT:
        _BUILT[case] = regenerate.build_case(case)
    return _BUILT[case]


def drain_paged(case, **engine_kw):
    cfg, model, params = built(case)
    reqs = request_workload(cfg, regenerate.N_REQUESTS, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    eng = ServeEngine(model, params, n_slots=regenerate.N_SLOTS,
                      max_len=regenerate.MAX_LEN, **engine_kw)
    results = eng.run(reqs)
    got = {str(r["rid"]): np.asarray(results[r["rid"]].tokens).tolist()
           for r in reqs}
    return got, eng


def golden_tokens(case):
    with open(regenerate.fixture_path(case)) as f:
        return json.load(f)["tokens"]


# ------------------------------------------------ golden bitwise equivalence

PAGED_VARIANTS = {
    "paged8": dict(page_size=8, max_batch_tokens=4),
    "chunked": dict(page_size=4, prefill_chunk=8),
    "unbucketed": dict(page_size=8),
}


@pytest.mark.parametrize("case", sorted(regenerate.CASES))
@pytest.mark.parametrize("variant", sorted(PAGED_VARIANTS))
def test_paged_engine_matches_golden(case, variant):
    """Every pool geometry decodes the exact fixture tokens."""
    got, eng = drain_paged(case, **PAGED_VARIANTS[variant])
    want = golden_tokens(case)
    assert set(got) == set(want)
    for rid in want:
        assert got[rid] == want[rid], (
            f"{case}/{variant}: paged engine diverged from the golden "
            f"fixture for rid={rid}")
    assert eng.pool.in_use == 0, "drained engine must return every page"


def test_paged_resident_bytes_below_slot_cache():
    """The economics: on the mixed-length workload the paged pool's mean
    resident KV bytes sit well under the flat reservation a slot cache
    makes (every slot holds a whole page table's rows up front)."""
    got, eng = drain_paged("int8_kv", page_size=4)
    slot_bytes = regenerate.N_SLOTS * eng.tables.n_ptab * eng._page_bytes
    s = eng.summary()
    assert s["resident_kv_bytes_mean"] > 0
    assert s["resident_kv_bytes_mean"] < slot_bytes
    assert s["resident_kv_bytes_peak"] <= s["kv_capacity_bytes"]


@needs_mesh
@pytest.mark.parametrize("quantize", [False, True],
                         ids=["int8_kv", "w4_packed"])
def test_paged_mesh_tp4_token_identical(quantize):
    """Engine on a (1, 4) tp mesh: sharded page pool (heads on 'model',
    pages whole, table replicated) decodes token-identically to the
    single-device engine."""
    from repro.configs import get_config
    from repro.distributed.compat import make_mesh
    from repro.models import build

    base = get_config("catlm_60m").smoke().scaled(n_kv_heads=4)
    model_fp = build(base)
    params = model_fp.init(jax.random.PRNGKey(0))
    if quantize:
        from repro.core.pipeline import QuantizeConfig, quantize_model
        from repro.data import calibration_batches
        params = quantize_model(
            model_fp, params,
            QuantizeConfig(w_bits=4, a_bits=4, transform="cat",
                           cat_block=16),
            calibration_batches(base, n_seqs=2, seq_len=16, batch=2))
    cfg = base.scaled(kv_quant_bits=8)
    model = build(cfg)
    mesh = make_mesh((1, 4), ("data", "model"))
    reqs = request_workload(cfg, 5, gen=4, lengths=(6, 10), seed=3)
    solo = ServeEngine(model, params, n_slots=2, max_len=24).run(reqs)
    meshed = ServeEngine(model, params, n_slots=2, max_len=24, mesh=mesh,
                         page_size=8, prefill_chunk=8).run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(meshed[r["rid"]].tokens,
                                      solo[r["rid"]].tokens,
                                      err_msg=f"rid={r['rid']}")


@needs_mesh
def test_paged_mesh_rejects_dp():
    """The page pool is global, so its writes can't shard over 'data' —
    a (2, 2) mesh must fail loudly at construction."""
    from repro.configs import get_config
    from repro.distributed.compat import make_mesh
    from repro.models import build

    cfg = get_config("catlm_60m").smoke().scaled(n_kv_heads=4)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="tensor-parallel only"):
        ServeEngine(model, params, n_slots=2, max_len=24,
                    mesh=make_mesh((2, 2), ("data", "model")))


def test_paged_kernel_engine_agrees_with_golden():
    """paged_kernel=True streams int8 pages through the Pallas kernel —
    rtol-level numerics, so assert high token agreement, not equality."""
    got, _ = drain_paged("int8_kv", page_size=8, paged_kernel=True)
    want = golden_tokens("int8_kv")
    agree = np.mean([np.mean(np.asarray(got[rid]) == np.asarray(want[rid]))
                     for rid in want])
    assert agree >= 0.9, agree


# ------------------------------------------------------ engine validation

def test_paged_engine_validation():
    cfg, model, params = built("int8_kv")
    make = lambda **kw: ServeEngine(model, params, n_slots=2, max_len=24,
                                    **kw)  # noqa: E731
    with pytest.raises(ValueError, match="page_size"):
        make(page_size=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        make(page_size=8, prefill_chunk=-8)
    # a request that could never fit the (shrunken) pool fails at submit
    eng = make(page_size=8, n_pages=2)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(np.arange(1, 10), 8)


def test_paged_pool_exhaustion_waits_not_corrupts():
    """With a pool too small for all slots at once, admission head-of-line
    waits (FIFO preserved) and every request still finishes correctly."""
    cfg, model, params = built("fp")
    reqs = request_workload(cfg, 4, gen=regenerate.GEN,
                            lengths=regenerate.LENGTHS,
                            seed=regenerate.SEED)
    # 3 allocatable pages of 8: budgets (prompt+gen) need 2 pages each,
    # so at most one request's reservation fits at a time
    eng = ServeEngine(model, params, n_slots=2, max_len=24, page_size=8,
                      n_pages=4)
    results = eng.run(reqs)
    assert len(results) == 4
    admits = [e for e in eng.events if e[0] == "admit"]
    assert [e[1] for e in admits] == sorted(e[1] for e in admits), "FIFO"
    assert eng.pool.in_use == 0


# ------------------------------------------------- allocator property tests

def _churn(pool_pages, ops):
    """Deterministic allocator churn: ops drive alloc/free; invariants
    checked after every step."""
    pool = PagePool(pool_pages, page_size=8)
    held = []
    for op in ops:
        if op % 2 == 0 and pool.available:
            page = pool.alloc()
            assert page != NULL_PAGE, "null page must never be allocated"
            assert page not in held, "page handed out twice"
            held.append(page)
        elif held:
            pool.free(held.pop(op % len(held)))
        assert pool.available + pool.in_use == pool.n_pages - 1
        assert pool.in_use == len(held)
    # fragmentation bound: every remaining free page is allocatable
    extra = [pool.alloc() for _ in range(pool.available)]
    assert len(set(extra + held)) == pool.n_pages - 1
    assert pool.available == 0


@pytest.mark.parametrize("seed", range(4))
def test_page_pool_invariants_ports(seed):
    rng = np.random.default_rng(seed)
    _churn(int(rng.integers(2, 20)), rng.integers(0, 97, size=200).tolist())


if HAVE_HYPOTHESIS:
    @given(st.integers(2, 24),
           st.lists(st.integers(0, 96), max_size=300))
    @settings(max_examples=50, deadline=None)
    def test_page_pool_invariants(pool_pages, ops):
        _churn(pool_pages, ops)
else:
    @given()
    def test_page_pool_invariants():
        pass  # skipped via shim


def test_page_pool_double_free_and_foreign_free_raise():
    pool = PagePool(4, 8)
    page = pool.alloc()
    pool.free(page)
    with pytest.raises(RuntimeError, match="double free|not allocated"):
        pool.free(page)
    with pytest.raises(RuntimeError, match="not allocated"):
        pool.free(3)
    with pytest.raises(RuntimeError, match="exhausted"):
        for _ in range(99):
            pool.alloc()


def test_slot_tables_lifecycle():
    pool = PagePool(1 + 2 * 3, page_size=8)
    tables = SlotPageTables(pool, n_slots=2, n_ptab=3)
    tables.admit(0, 9)                      # 2 pages for 9 tokens
    assert tables.n_owned(0) == 2 and pool.in_use == 2
    assert (tables.table[0, :2] > 0).all() and tables.table[0, 2] == 0
    tables.ensure(0, 15)                    # still page 1
    assert tables.n_owned(0) == 2
    tables.ensure(0, 16)                    # crosses into page 2
    assert tables.n_owned(0) == 3
    tables.admit(1, 1)
    assert pool.in_use == 4
    assert set(tables.table[0][tables.table[0] > 0]).isdisjoint(
        tables.table[1][tables.table[1] > 0]), "slots share a page"
    with pytest.raises(RuntimeError, match="exceeds"):
        tables.ensure(0, 24)
    tables.release(0)
    assert pool.in_use == 1 and (tables.table[0] == NULL_PAGE).all()
    tables.release(1)
    assert pool.in_use == 0


# ------------------------------------------------- kernel vs oracle

def _rand_paged(seed, b=3, kvh=2, g=2, hd=16, page=8, n_ptab=3,
                quantized_normal=False):
    """Random pool, table and lengths. Codes and scales are uniform, or
    with ``quantized_normal`` the int8 quantization (scale = amax / 127
    per token and head) of unit-normal K and V, as the pool holds them."""
    from repro.kernels import ref
    rng = np.random.default_rng(seed)
    n_pages = 1 + b * n_ptab
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)).astype(np.float32))

    def quantized():
        x = rng.normal(size=(n_pages, page, kvh, hd)).astype(np.float32)
        sc = np.abs(x).max(-1, keepdims=True) / 127
        return (jnp.asarray(np.round(x / sc).astype(np.int8)),
                jnp.asarray(sc))

    if quantized_normal:
        (kp, ks), (vp, vs) = quantized(), quantized()
    else:
        mk = lambda: jnp.asarray(rng.integers(  # noqa: E731
            -127, 128, size=(n_pages, page, kvh, hd)).astype(np.int8))
        ms = lambda: jnp.asarray(rng.uniform(  # noqa: E731
            0.01, 0.1, size=(n_pages, page, kvh, 1)).astype(np.float32))
        kp, vp, ks, vs = mk(), mk(), ms(), ms()
    table = np.zeros((b, n_ptab), np.int32)
    lengths = np.zeros((b,), np.int32)
    for i in range(b):
        # ragged: lengths deliberately include 1, partial pages, full
        lengths[i] = int(rng.integers(1, n_ptab * page + 1))
        n_owned = -(-int(lengths[i]) // page)
        table[i, :n_owned] = 1 + i * n_ptab + np.arange(n_owned)
    args = (q, kp, ks, vp, vs, jnp.asarray(table), jnp.asarray(lengths))
    return args, ref.paged_attention_decode(*args)


@pytest.mark.parametrize("seed", range(3))
def test_paged_attention_kernel_vs_oracle(seed):
    from repro.kernels import ops
    args, want = _rand_paged(seed)
    got = ops.paged_attention(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


WIDTHS = [(4, 2, 64), (8, 4, 128), (1, 8, 128)]   # (KVH, g, hd)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("kvh,g,hd", WIDTHS,
                         ids=[f"kvh{k}g{g}hd{d}" for k, g, d in WIDTHS])
def test_paged_attention_kernel_vs_oracle_widths(kvh, g, hd, seed):
    """The cell's widths, Mistral-NeMo's (8 KV heads of 128) and MQA's,
    over a table several blocks long whose width is no multiple of the
    block."""
    from repro.kernels import ops
    from repro.kernels.paged_attention import _pages_per_block
    ppb = _pages_per_block(16, kvh, hd, 64)
    n_ptab = 2 * ppb + ppb // 2 + 1
    assert n_ptab % ppb
    args, want = _rand_paged(seed, b=4, kvh=kvh, g=g, hd=hd, page=16,
                             n_ptab=n_ptab, quantized_normal=True)
    got = ops.paged_attention(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _block_lengths(kvh=4, g=2, hd=64, page=16, seed=5):
    """Rows of 1 kv row, one short of a block, exactly a block, one past
    a block and the whole table, over a table whose width is no multiple
    of the block; (args, page table, block rows)."""
    from repro.kernels.paged_attention import _pages_per_block
    ppb = _pages_per_block(page, kvh, hd, 64)
    n_ptab = 2 * ppb + ppb // 2 + 1
    rows = ppb * page
    lengths = [1, rows - 1, rows, rows + 1, n_ptab * page]
    (q, kp, ks, vp, vs, _, _), _ = _rand_paged(
        seed, b=len(lengths), kvh=kvh, g=g, hd=hd, page=page, n_ptab=n_ptab,
        quantized_normal=True)
    table = np.zeros((len(lengths), n_ptab), np.int32)
    for i, n in enumerate(lengths):
        n_owned = -(-n // page)
        table[i, :n_owned] = 1 + i * n_ptab + np.arange(n_owned)
    return (q, kp, ks, vp, vs, table, jnp.asarray(lengths, jnp.int32))


def test_paged_attention_block_edge_lengths_vs_oracle():
    from repro.kernels import ops, ref
    q, kp, ks, vp, vs, table, lengths = _block_lengths()
    args = (q, kp, ks, vp, vs, jnp.asarray(table), lengths)
    np.testing.assert_allclose(np.asarray(ops.paged_attention(*args)),
                               np.asarray(ref.paged_attention_decode(*args)),
                               rtol=1e-5, atol=1e-5)


def test_paged_attention_dead_blocks_are_never_read():
    """Every table entry past a row's live pages names a page of extreme
    codes and NaN scales: the output is bitwise the clean run's, so no
    dead page is fetched or weighted."""
    from repro.kernels import ops
    q, kp, ks, vp, vs, table, lengths = _block_lengths()
    base = ops.paged_attention(q, kp, ks, vp, vs, jnp.asarray(table),
                               lengths)
    poison = kp.shape[0]                 # one page past the pool
    pad = lambda x, val: jnp.concatenate(  # noqa: E731
        [x, jnp.full((1,) + x.shape[1:], val, x.dtype)])
    poisoned = np.where(table == 0, poison, table)
    got = ops.paged_attention(q, pad(kp, 127), pad(ks, jnp.nan),
                              pad(vp, -127), pad(vs, jnp.nan),
                              jnp.asarray(poisoned), lengths)
    assert (poisoned == poison).sum() > table.shape[1]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_paged_attention_ragged_last_page_exact_zero_weight():
    """A position past lengths[b] must contribute *exactly* nothing:
    poisoning masked rows with huge codes cannot move the output."""
    from repro.kernels import ops
    (q, kp, ks, vp, vs, table, lengths), _ = _rand_paged(7)
    base = ops.paged_attention(q, kp, ks, vp, vs, table, lengths)
    page = kp.shape[1]
    poisoned_k, poisoned_v = np.array(kp), np.array(vp)
    for b in range(q.shape[0]):
        n = int(lengths[b])
        idx, row = n // page, n % page    # first masked position
        if idx < table.shape[1] and int(table[b, idx]) > 0:
            poisoned_k[int(table[b, idx]), row:] = 127
            poisoned_v[int(table[b, idx]), row:] = -127
    got = ops.paged_attention(q, jnp.asarray(poisoned_k), ks,
                              jnp.asarray(poisoned_v), vs, table, lengths)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_paged_attention_fp_pool_routes_to_fallback():
    """An fp pool (no scales) through ops dispatch must equal the
    quantized oracle on equivalent inputs: dequantizing the pool outside
    (codes·scale in f32) is the exact same op the oracle runs inside."""
    from repro.kernels import ops
    (q, kp, ks, vp, vs, table, lengths), want = _rand_paged(13)
    kf = kp.astype(jnp.float32) * ks
    vf = vp.astype(jnp.float32) * vs
    got = ops.paged_attention(q, kf, None, vf, None, table, lengths)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------- sharding spec checks

def test_tp_cache_specs_paged_pool():
    """Pool leaves shard heads on 'model' congruently (codes AND scales);
    the page axis stays whole; page_table/pos replicate."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shlib
    from repro.distributed.compat import abstract_mesh

    mesh = abstract_mesh((1, 2), ("data", "model"))
    L, n_pages, G, KV, hd = 2, 9, 8, 4, 16
    cache = {
        "k": jax.ShapeDtypeStruct((L, n_pages, G, KV, hd), jnp.int8),
        "k_scale": jax.ShapeDtypeStruct((L, n_pages, G, KV, 1),
                                        jnp.float32),
        "v": jax.ShapeDtypeStruct((L, n_pages, G, KV, hd), jnp.int8),
        "v_scale": jax.ShapeDtypeStruct((L, n_pages, G, KV, 1),
                                        jnp.float32),
        "page_table": jax.ShapeDtypeStruct((3, 3), jnp.int32),
        "pos": jax.ShapeDtypeStruct((3,), jnp.int32),
    }
    specs = shlib.tp_cache_specs(cache, mesh, axis="model")
    for key in ("k", "k_scale", "v", "v_scale"):
        assert specs[key] == P(None, None, None, "model", None), key
    assert specs["page_table"] == P(None, None)
    assert specs["pos"] == P(None)
    # MQA-ish: heads don't divide -> whole tree replicates (congruent)
    cache["k"] = jax.ShapeDtypeStruct((L, n_pages, G, 3, hd), jnp.int8)
    cache["k_scale"] = jax.ShapeDtypeStruct((L, n_pages, G, 3, 1),
                                            jnp.float32)
    specs = shlib.tp_cache_specs(cache, mesh, axis="model")
    assert specs["k"] == P(None, None, None, None, None)
    assert specs["k_scale"] == P(None, None, None, None, None)
