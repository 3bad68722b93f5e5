"""Fused CAT→quant→W4A8 serving path.

Covers the PR's hot-path pieces end to end:

- the single-launch Pallas kernel (``kernels/fused_cat_matmul.py``) vs
  the pure-jnp oracle (``ref.fused_cat_matmul_w4``) at rtol 1e-5 —
  packed and unpacked weights, with and without the block-CAT stage,
  odd K (padded nibble) and K not a multiple of the CAT block
- the composed ``ops.cat_transform_matmul`` across the M ∈ {7, 8, 9}
  GEMV-vs-tiled dispatch boundary (``_GEMV_M`` = 8)
- ``ops.fused_transform_operands`` decomposition (Scale folds into the
  Hadamard sign; undecomposable transforms return None)
- the per-shape block-size autotune cache (``kernels/autotune.py``)
- fused-vs-unfused ServeEngine token identity on a quantized smoke model
  (the golden fixtures pin the same property against stored tokens;
  this pins it against a live unfused engine)
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import transforms as T
from repro.core.quantizers import pack_int4
from repro.kernels import autotune, ops, ref
from repro.kernels.fused_cat_matmul import (fused_cat_gemv_w4,
                                            fused_cat_matmul_w4)


def _factor(d):
    """(a, b) with a·b = d, near sqrt — mirrors the Kronecker split."""
    a = int(np.sqrt(d))
    while d % a:
        a -= 1
    return a, d // a


def _operands(m, d, n, seed, n_blocks=0):
    """Random fused-kernel operands. ha/hb are arbitrary Kronecker
    factors (the kernel contract needs no true Hadamard structure and
    arbitrary d — e.g. odd — must work)."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((m, d)), jnp.float32)
    blocks = None
    if n_blocks:
        assert d % n_blocks == 0
        bk = d // n_blocks
        blocks = jnp.asarray(
            r.standard_normal((n_blocks, bk, bk)) * 0.3 + np.eye(bk),
            jnp.float32)
    a, b = _factor(d)
    ha = jnp.asarray(r.standard_normal((a, a)) / np.sqrt(a), jnp.float32)
    hb = jnp.asarray(r.standard_normal((b, b)) / np.sqrt(b), jnp.float32)
    sign = jnp.asarray(r.integers(0, 2, d) * 2 - 1, jnp.float32)
    qw = jnp.asarray(r.integers(-8, 8, (d, n)), jnp.int8)
    sw = jnp.asarray(r.uniform(0.01, 0.1, (1, n)), jnp.float32)
    return x, blocks, ha, hb, sign, qw, sw


def _check_fused_matches_oracle(m, d, n, seed, n_blocks=0, packed=True,
                                act_bits=8, **kw):
    x, blocks, ha, hb, sign, qw, sw = _operands(m, d, n, seed, n_blocks)
    w = pack_int4(qw, axis=0) if packed else qw
    run = fused_cat_gemv_w4 if m <= ops._GEMV_M else fused_cat_matmul_w4
    got = run(x, blocks, ha, hb, sign, w, sw, act_bits=act_bits,
              packed=packed, interpret=True, **kw)
    want = ref.fused_cat_matmul_w4(x, blocks, ha, hb, sign, w, sw,
                                   act_bits=act_bits, packed=packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- kernel vs oracle

@pytest.mark.parametrize("m", [7, 8, 9])
def test_fused_kernel_gemv_tiled_boundary(m):
    """M ∈ {7, 8, 9} straddles the GEMV/tiled dispatch; both kernels must
    agree with the oracle (with the block-CAT stage active)."""
    _check_fused_matches_oracle(m, 64, 96, seed=m, n_blocks=4)


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("m,d,n,n_blocks", [
    (5, 64, 96, 0),        # no block stage (bare Hadamard transform)
    (17, 96, 80, 6),       # tiled, blocks, K not a multiple of block_k
    (33, 63, 40, 0),       # odd K: padded nibble must stay inert
    (3, 63, 40, 7),        # odd K through the GEMV path, with blocks
])
def test_fused_kernel_matches_oracle(packed, m, d, n, n_blocks):
    _check_fused_matches_oracle(m, d, n, seed=m * 100 + d, packed=packed,
                                n_blocks=n_blocks)


def test_fused_kernel_small_block_sizes():
    """Explicit tiny block sizes force multi-step grids in every dim."""
    _check_fused_matches_oracle(19, 96, 72, seed=3, n_blocks=6,
                                block_m=8, block_n=32, block_k=32)


@pytest.mark.parametrize("act_bits", [4, 8])
def test_fused_kernel_act_bits(act_bits):
    _check_fused_matches_oracle(9, 64, 48, seed=act_bits, n_blocks=4,
                                act_bits=act_bits)


# ----------------------------------------- composed path dispatch boundary

@pytest.mark.parametrize("m", [7, 8, 9])
@pytest.mark.parametrize("d,n,n_blocks", [(64, 96, 4), (63, 40, 0)])
def test_cat_transform_matmul_gemv_boundary(m, d, n, n_blocks):
    """The composed serving linear around the same M boundary, including
    odd K — GEMV and tiled routes must be interchangeable."""
    x, blocks, ha, hb, sign, qw, sw = _operands(m, d, n, seed=m,
                                                n_blocks=n_blocks)
    if blocks is None:
        blocks = jnp.eye(d, dtype=jnp.float32)[None]
    wp = pack_int4(qw, axis=0)
    got = ops.cat_transform_matmul(x, blocks, ha, hb, sign, wp, sw,
                                   act_bits=8, packed_int4=True)
    xf = ref.block_diag_matmul(x.astype(jnp.float32), blocks)
    q, s, zp = ref.fused_hadamard_quant(xf, ha, hb, sign, bits=8)
    want = ref.quant_matmul_w4(q, s, zp, wp, sw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------- operand decomposition

def test_fused_transform_operands_decomposes_cat():
    r = np.random.default_rng(0)
    t = T.make_cat_block(jnp.eye(64) * 2.0, jnp.eye(64), k=16, rng=r)
    blocks, ha, hb, sign = ops.fused_transform_operands(t)
    assert blocks is not None and blocks.shape[0] == 4
    assert ha.shape[0] * hb.shape[0] == 64
    x = jnp.asarray(r.standard_normal((3, 64)), jnp.float32)
    want = T.apply(t, x)
    got = ref.hadamard_transform(
        ref.block_diag_matmul(x, blocks) * sign[None, :], ha, hb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_fused_transform_operands_folds_scale_into_sign():
    r = np.random.default_rng(1)
    had = T.make_hadamard(32, r)
    s = jnp.asarray(r.uniform(0.5, 2.0, 32), jnp.float32)
    t = T.Compose((T.Scale(s), had))
    blocks, ha, hb, sign = ops.fused_transform_operands(t)
    assert blocks is None
    np.testing.assert_allclose(np.asarray(sign), np.asarray(had.sign * s))
    x = jnp.asarray(r.standard_normal((2, 32)), jnp.float32)
    got = ref.hadamard_transform(x * sign[None, :], ha, hb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(T.apply(t, x)),
                               rtol=1e-5, atol=1e-6)


def test_fused_transform_operands_rejects_dense():
    r = np.random.default_rng(2)
    assert ops.fused_transform_operands(T.make_rotation(16, r)) is None
    assert ops.fused_transform_operands(T.Identity()) is None


# --------------------------------------------------------- autotune cache

def test_autotune_heuristic_fits_budget():
    for m, d, n, packed in [(1, 64, 512, True), (256, 4096, 11008, True),
                            (8, 2048, 2048, False)]:
        tm, tn, tk = autotune.heuristic_blocks(m, d, n, packed)
        assert autotune._fused_working_set(tm, tn, tk, d, packed) \
            <= autotune.VMEM_BUDGET
        assert tm % 8 == 0 and tn % 8 == 0


def test_autotune_pick_memoizes():
    autotune.cache_clear()
    key = ("test", 8, 64, 96, True, True)
    first = autotune.pick(key, 8, 64, 96, True)
    assert autotune.pick(key, 8, 64, 96, True) is first
    assert key in autotune.cache_info()
    autotune.cache_clear()
    assert key not in autotune.cache_info()


def test_autotune_pick_raises_when_every_candidate_fails(monkeypatch):
    """Measured tuning skips refused candidates but must not hide a
    kernel that compiles at no block size behind the heuristic."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
    autotune.cache_clear()
    seen = []

    def refuse(cand):
        seen.append(cand)
        raise ValueError("refused")

    key = ("test-refused", 256, 512, 1024, True, False)
    with pytest.raises(RuntimeError, match="every block-size candidate"):
        autotune.pick(key, 256, 512, 1024, True, run=refuse)
    assert seen and key not in autotune.cache_info()

    def ok_unless_small(cand):
        if cand[0] < 256:
            raise ValueError("refused")

    assert autotune.pick(key, 256, 512, 1024, True,
                         run=ok_unless_small)[0] >= 256
    autotune.cache_clear()


# ------------------------------------------------- engine token identity

@pytest.mark.slow
def test_fused_engine_matches_unfused():
    """ServeEngine(fused=True) — QKV/GU concat + w_eff serving params —
    must be token-identical to the unfused engine on a w4-packed CAT
    model (the golden fixtures pin the same tokens against disk)."""
    from repro.data import request_workload
    from repro.launch.engine import ServeEngine
    from repro.launch.serve import build_served_model

    cfg, model, params, _ = build_served_model(
        "catlm_60m", "cat", 4, 4, 8, smoke=True, seed=0)
    reqs = request_workload(cfg, 3, gen=4, lengths=(6, 10), seed=1)
    outs = {}
    for fused in (True, False):
        eng = ServeEngine(model, params, n_slots=2, max_len=24,
                          fused=fused)
        outs[fused] = eng.run(reqs)
        assert eng.summary()["fused"] is fused
    for r in reqs:
        rid = r["rid"]
        np.testing.assert_array_equal(outs[True][rid].tokens,
                                      outs[False][rid].tokens)
