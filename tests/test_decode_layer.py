"""Two-launch decode: the fused QKV-prologue kernel + its routing.

Covers the PR's pieces end to end:

- ``kernels.decode_layer.decode_qkv_prologue`` (interpret mode) vs the
  eager ``ref.decode_qkv_prologue`` oracle: rope'd q at rtol 1e-5 (XLA
  FMA-contracts the kernel's fused f32 chains, so bitwise is out of
  reach by construction), scattered int8 KV codes **bitwise**, scale
  pools at rtol — packed and unpacked weights, with/without the
  block-CAT stage, multi-tile N and K grids, padded batches (B < 8)
- the in-kernel RoPE + KV-quantize + paged scatter vs the
  ``models.layers`` composition (``rope`` + ``quantize_kv`` +
  ``paged_cache_update_quantized``): bitwise, including ragged last
  pages and rows straddling a page boundary
- null-page parking: padded rows and explicit null-page targets leave
  every real page untouched (page 0 is outside the pool contract)
- the COW write guard: ``SlotPageTables.assert_writable`` rejects
  scatters into refcount>1 shared pages until ``ensure_writable`` splits
  them — the host-side invariant that makes the kernel's in-place pool
  writes safe under prefix caching
- the ``REPRO_PALLAS_INTERPRET`` / ``REPRO_DECODE_FUSED`` env switches
  (``ops.default_interpret`` / ``ops.use_fused_decode``) so kernel tests
  run (not skip) on CPU CI and the fused layer path stays opt-in off-TPU
- model-level routing: with ``REPRO_DECODE_FUSED=1`` every decode layer
  dispatches the prologue exactly once; numerics follow the
  integer-accumulation route (``qlinear`` route 3 == the TPU kernel
  route), so tokens are compared against the route-3 expectation, not
  the portable bf16 path
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizers import pack_int4
from repro.kernels import ops, ref
from repro.kernels.decode_layer import decode_qkv_prologue
from repro.kernels.paged_attention import scale_lanes

HD = 8          # head_dim
N_Q = 32        # 4 q heads
N_KV = 16       # 2 kv heads
PAGE = 4        # page_size
PAGES = 10      # pool pages (page 0 = null)
LAYERS = 2      # stacked pool layers; the prologue writes LAYER
LAYER = 1


def _factor(d):
    a = int(np.sqrt(d))
    while d % a:
        a -= 1
    return a, d // a


def _operands(b, d, seed, n_blocks=0):
    """Random prologue operands + a pre-populated paged pool."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((b, d)), jnp.float32)
    blocks = None
    if n_blocks:
        bk = d // n_blocks
        blocks = jnp.asarray(
            r.standard_normal((n_blocks, bk, bk)) * 0.3 + np.eye(bk),
            jnp.float32)
    a, bb = _factor(d)
    ha = jnp.asarray(r.standard_normal((a, a)) / np.sqrt(a), jnp.float32)
    hb = jnp.asarray(r.standard_normal((bb, bb)) / np.sqrt(bb), jnp.float32)
    sign = jnp.asarray(r.integers(0, 2, d) * 2 - 1, jnp.float32)
    n = N_Q + 2 * N_KV
    qw = jnp.asarray(r.integers(-8, 8, (d, n)), jnp.int8)
    sw = jnp.asarray(r.uniform(0.01, 0.1, (1, n)), jnp.float32)
    kvh = N_KV // HD
    codes = (LAYERS, PAGES, PAGE, N_KV)             # init_paged_cache's
    scales = (LAYERS, PAGES, kvh, scale_lanes(PAGE))
    pools = (jnp.asarray(r.integers(-128, 128, codes), jnp.int8),
             jnp.asarray(r.uniform(0.01, 1.0, scales), jnp.float32),
             jnp.asarray(r.integers(-128, 128, codes), jnp.int8),
             jnp.asarray(r.uniform(0.01, 1.0, scales), jnp.float32))
    return x, blocks, ha, hb, sign, qw, sw, pools


def _run_both(b, d, seed, n_blocks=0, packed=True, pids=None, rows=None,
              positions=None, **kernel_kw):
    x, blocks, ha, hb, sign, qw, sw, pools = _operands(b, d, seed, n_blocks)
    if pids is None:
        pids = np.arange(1, 1 + b, dtype=np.int32)
    if rows is None:
        rows = np.full(b, 1, np.int32)
    if positions is None:
        positions = np.arange(3, 3 + b, dtype=np.int32)
    pids = jnp.asarray(pids, jnp.int32)
    rows = jnp.asarray(rows, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    qw_store = pack_int4(np.asarray(qw), axis=0) if packed else qw
    kw = dict(n_q=N_Q, head_dim=HD, rope_theta=1e4, kv_bits=8, act_bits=8,
              packed=packed)
    got = decode_qkv_prologue(x, blocks, ha, hb, sign, jnp.asarray(qw_store),
                              sw, *pools, LAYER, pids, rows, positions,
                              interpret=True, **kw, **kernel_kw)
    want = ref.decode_qkv_prologue(x, blocks, ha, hb, sign,
                                   jnp.asarray(qw_store), sw, *pools, LAYER,
                                   pids, rows, positions, **kw)
    return got, want, pools, (pids, rows)


def _assert_pools_match(got, want):
    """Pools equal outside the null page, every layer: codes bitwise,
    scales rtol."""
    for g, w, name in ((got[1], want[1], "k"), (got[3], want[3], "v")):
        np.testing.assert_array_equal(np.asarray(g)[:, 1:],
                                      np.asarray(w)[:, 1:],
                                      err_msg=f"{name} codes")
    for g, w, name in ((got[2], want[2], "k_scale"),
                       (got[4], want[4], "v_scale")):
        np.testing.assert_allclose(np.asarray(g)[:, 1:], np.asarray(w)[:, 1:],
                                   rtol=1e-5, atol=1e-8, err_msg=name)


@pytest.mark.parametrize("n_blocks", [0, 3])
@pytest.mark.parametrize("packed", [True, False])
def test_kernel_matches_oracle(n_blocks, packed):
    got, want, _, _ = _run_both(8, 24, seed=0, n_blocks=n_blocks,
                                packed=packed)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    _assert_pools_match(got, want)


@pytest.mark.parametrize("block_n,block_k", [(32, 512), (256, 8), (32, 8)])
def test_kernel_matches_oracle_multi_tile(block_n, block_k):
    """gn > 1 / gk > 1 grids: the accumulator add path and the
    park-until-last-flush pool index maps."""
    got, want, _, _ = _run_both(8, 24, seed=1, n_blocks=3,
                                block_n=block_n, block_k=block_k)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    _assert_pools_match(got, want)


@pytest.mark.parametrize("b", [1, 3, 8])
def test_padded_batch(b):
    """B < 8 rows are padded internally; padding lands on the null page
    and every real page matches the oracle."""
    got, want, _, _ = _run_both(b, 24, seed=2, n_blocks=3)
    assert got[0].shape == (b, N_Q)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    _assert_pools_match(got, want)


def test_ragged_last_page():
    """Rows on a ragged last page: one row at the final slot of a page,
    one on the first slot of the next — scatter targets stay exact."""
    pids = np.array([1, 2, 3], np.int32)
    rows = np.array([PAGE - 1, 0, 2], np.int32)
    positions = np.array([PAGE - 1, PAGE, 2], np.int32)
    got, want, pools, (jp, jr) = _run_both(
        3, 24, seed=3, n_blocks=3, pids=pids, rows=rows, positions=positions)
    _assert_pools_match(got, want)
    # the targeted rows really changed vs the pre-existing pool content
    kp0 = np.asarray(pools[0])
    kp1 = np.asarray(got[1])
    for p, r in zip(pids, rows):
        assert not np.array_equal(kp1[LAYER, p, r], kp0[LAYER, p, r])
    # the other layer is left as it was
    np.testing.assert_array_equal(kp1[1 - LAYER], kp0[1 - LAYER])


def test_untouched_pages_and_null_target():
    """Aliased pool rows the grid never targets keep their content
    bitwise — including when a real row explicitly targets the null
    page (an engine padding row): no real page may change at all."""
    pids = np.array([0, 0, 0], np.int32)     # all rows -> null page
    got, _, pools, _ = _run_both(3, 24, seed=4, n_blocks=3, pids=pids,
                                 rows=np.zeros(3, np.int32))
    for g, orig in ((got[1], pools[0]), (got[2], pools[1]),
                    (got[3], pools[2]), (got[4], pools[3])):
        np.testing.assert_array_equal(np.asarray(g)[:, 1:],
                                      np.asarray(orig)[:, 1:])


def test_oracle_matches_layers_composition():
    """Satellite: the oracle's RoPE + KV-quant + scatter epilogue is
    bitwise identical to the ``models.layers`` composition the composed
    decode path runs (``rope`` + ``quantize_kv`` +
    ``paged_cache_update_quantized``)."""
    from repro.models.layers import (_paged_indices,
                                     paged_cache_update_quantized, rope)

    b, d = 3, 24
    x, blocks, ha, hb, sign, qw, sw, pools = _operands(b, d, seed=5,
                                                       n_blocks=3)
    n_ptab = 3
    table = jnp.asarray(
        np.arange(1, 1 + b * n_ptab, dtype=np.int32).reshape(b, n_ptab))
    pos = jnp.asarray([PAGE - 1, PAGE, 2], jnp.int32)   # ragged last pages
    pids, rows = _paged_indices(table, pos, b, 1, PAGE)
    qw_p = jnp.asarray(pack_int4(np.asarray(qw), axis=0))
    q, kp, ks, vp, vs = ref.decode_qkv_prologue(
        x, blocks, ha, hb, sign, qw_p, sw, *pools, LAYER, pids, rows, pos,
        n_q=N_Q, head_dim=HD, rope_theta=1e4)

    # the same y rows through the layers composition
    q8, sx, zx = ref.kernel_transform_quant(x, blocks, ha, hb, sign)
    y = ref.quant_matmul(q8, sx, zx, ref.unpack_int4(qw_p, d), sw)
    kvh = N_KV // HD
    k = rope(y[:, N_Q:N_Q + N_KV].reshape(b, 1, kvh, HD), pos[:, None],
             theta=1e4)
    v = y[:, N_Q + N_KV:].reshape(b, 1, kvh, HD)
    kp2, ks2, vp2, vs2 = paged_cache_update_quantized(
        *pools, k, v, table, pos, LAYER, 8)
    np.testing.assert_array_equal(kp, kp2)
    np.testing.assert_array_equal(vp, vp2)
    np.testing.assert_array_equal(ks, ks2)
    np.testing.assert_array_equal(vs, vs2)


def test_cow_guard_rejects_shared_pages():
    """The kernel scatters in place, so the host-side COW guard is what
    keeps prefix-cache shared pages safe: a slot mapped onto refcount>1
    pages must fail ``assert_writable`` until ``ensure_writable``
    splits, after which the scatter window is accepted."""
    from repro.launch.paged import PagePool, SlotPageTables

    pool = PagePool(n_pages=16, page_size=PAGE)
    tables = SlotPageTables(pool, n_slots=2, n_ptab=4)
    tables.admit(0, 2 * PAGE)                  # slot 0 owns two pages
    shared = [int(p) for p in tables.table[0, :2]]
    for p in shared:
        pool.incref(p)
    tables.admit_prefix(1, shared, 2 * PAGE, 2 * PAGE + 1)
    with pytest.raises(RuntimeError,
                       match="read-only until COW-split"):
        tables.assert_writable(1, 0, PAGE - 1)
    cow = tables.ensure_writable(1, 0)
    assert len(cow) == 1 and cow[0][0] == shared[0]
    tables.assert_writable(1, 0, PAGE - 1)     # now exclusively owned


def test_env_switches(monkeypatch):
    """Satellite: REPRO_PALLAS_INTERPRET forces interpret mode on or off
    regardless of backend; REPRO_DECODE_FUSED opts the fused decode
    layer in/out (default: TPU only)."""
    on_tpu = jax.default_backend() == "tpu"
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_DECODE_FUSED", raising=False)
    assert ops.default_interpret() is (not on_tpu)
    assert ops.use_fused_decode() is on_tpu
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.default_interpret() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.default_interpret() is False
    monkeypatch.setenv("REPRO_DECODE_FUSED", "1")
    assert ops.use_fused_decode() is True
    monkeypatch.setenv("REPRO_DECODE_FUSED", "off")
    assert ops.use_fused_decode() is False


@pytest.mark.slow
def test_fused_layer_routing(monkeypatch):
    """REPRO_DECODE_FUSED=1 routes every decode layer through the
    prologue exactly once; pages outside each slot's table (and the
    null page) stay bitwise identical to the composed path's."""
    from repro.launch.serve import build_served_model
    from repro.models import dense

    cfg, model, params, _ = build_served_model("catlm_60m", "cat", 4, 4, 8,
                                               smoke=True, seed=0)
    msp = dense.make_serving_params(cfg, params)
    b, n_ptab = 3, 4
    cache0 = dense.init_paged_cache(cfg, n_pages=32, page_size=PAGE)
    table = jnp.asarray(
        np.arange(1, 1 + b * n_ptab, dtype=np.int32).reshape(b, n_ptab))
    tok = jnp.asarray([[5], [7], [11]], jnp.int32)

    calls = {"n": 0}
    real = ops.decode_qkv_prologue

    def counted(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(ops, "decode_qkv_prologue", counted)

    def step(fused):
        monkeypatch.setenv("REPRO_DECODE_FUSED", "1" if fused else "0")
        calls["n"] = 0
        c = dict(cache0)
        c["pos"] = jnp.int32(2)
        c["page_table"] = table
        logits, c = dense.decode(cfg, msp, tok, c, paged_kernel=True,
                                 unroll=True)
        return logits, c, calls["n"]

    logits_c, cache_c, n_c = step(False)
    logits_f, cache_f, n_f = step(True)
    assert n_c == 0 and n_f == cfg.n_layers
    assert bool(jnp.all(jnp.isfinite(logits_f)))
    assert logits_f.shape == logits_c.shape
    # pages owned by no slot stay bitwise equal across the two routes
    used = set(np.asarray(table).ravel().tolist()) | {0}
    mask = np.array([p not in used for p in range(32)])
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache_c[key])[:, mask],
                                      np.asarray(cache_f[key])[:, mask])
