"""The serving kernels' block and tile choices at ``catlm_60m``'s widths
(d_model 512, 8/4 heads of 64, d_ff 1536; 8 slots of 320 pages of 16, a
512-token step budget) are pinned: the VMEM limits the kernels take from
their shapes at Mistral-NeMo-12B's widths leave the narrow model's
kernels as they were. Each kernel is traced (``jax.eval_shape``, nothing
runs) through the serving dispatch with ``interpret=False``, and its
``pallas_call`` is recorded."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.core.hadamard import hadamard_factors
from repro.kernels import autotune, ops
from repro.kernels import paged_attention as pa

D, D_FF, N_Q, N_KV, HD, KVH = 512, 1536, 512, 256, 64, 4
PAGE, N_PTAB, SLOTS, BUDGET = 16, 320, 8, 512
N_PAGES = 1 + SLOTS * N_PTAB


@pytest.fixture
def calls(monkeypatch):
    """-> the (name, grid, block shapes, compiler params) of every
    ``pallas_call`` traced while the test runs."""
    from jax.experimental import pallas as pl
    out, orig = [], pl.pallas_call

    def spy(kernel, **kw):
        gs = kw.get("grid_spec")
        specs = kw["in_specs"] if gs is None else gs.in_specs
        out.append((kw["name"], kw["grid"] if gs is None else gs.grid,
                    [s.block_shape for s in specs],
                    kw.get("compiler_params")))
        return orig(kernel, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)
    jax.clear_caches()      # a cached trace would not reach the spy
    return out


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _linear(m, d, n):
    ha, hb = hadamard_factors(d)
    return [_s((m, d), jnp.bfloat16), _s((d // 32, 32, 32), jnp.float32),
            _s(ha.shape, jnp.float32), _s(hb.shape, jnp.float32),
            _s((d,), jnp.float32), _s((d // 2, n), jnp.int8),
            _s((1, n), jnp.float32)]


# (rows, d, n) -> grid; the weight block is (256 packed rows, 256) and the
# activation tile 256 rows (8 for decode rows) in every case
@pytest.mark.parametrize("m,d,n,grid", [
    (8, D, N_Q + 2 * N_KV, (4, 1)), (8, D, 2 * D_FF, (12, 1)),
    (8, N_Q, D, (2, 1)), (8, D_FF, D, (2, 3)),
    (BUDGET, D, N_Q + 2 * N_KV, (2, 4, 1)), (BUDGET, D, 2 * D_FF, (2, 12, 1)),
    (BUDGET, N_Q, D, (2, 2, 1)), (BUDGET, D_FF, D, (2, 2, 3))],
    ids=["gemv-wqkv", "gemv-wgu", "gemv-wo", "gemv-wd",
         "matmul-wqkv", "matmul-wgu", "matmul-wo", "matmul-wd"])
def test_fused_cat_linears_keep_their_tiles(calls, m, d, n, grid):
    jax.eval_shape(lambda *a: ops.fused_cat_matmul(
        *a, act_bits=8, packed=True, interpret=False), *_linear(m, d, n))
    (name, got_grid, blocks, params), = calls
    assert name == ("fused_cat_gemv_w4" if m == 8 else "fused_cat_matmul_w4")
    assert got_grid == grid
    assert blocks[0] == (8 if m == 8 else 256, d)
    assert blocks[-2:] == [(256, 256), (1, 256)]
    assert params is None       # the default scoped VMEM, as before


def test_block_heuristics_at_catlm_widths():
    for d, n in ((D, N_Q + 2 * N_KV), (D, 2 * D_FF), (N_Q, D), (D_FF, D)):
        assert autotune.heuristic_blocks(BUDGET, d, n, True) == (256, 256,
                                                                  512)
        assert autotune.gemv_blocks(d, n, True) == (256, 512)
    assert autotune.prologue_blocks(D, N_Q + 2 * N_KV, N_KV, True) == (
        256, 512)
    assert pa._pages_per_block(PAGE, KVH, HD, N_PTAB) == 8


def test_ragged_attention_keeps_its_blocks(calls):
    lanes = pa.scale_lanes(PAGE)
    pool = [_s((8, N_PAGES, PAGE, KVH * HD), jnp.int8),
            _s((8, N_PAGES, KVH, lanes), jnp.float32)] * 2
    jax.eval_shape(
        lambda q, *a: pa.paged_attention_ragged(q, *a, interpret=False),
        _s((SLOTS, BUDGET, KVH, N_Q // N_KV, HD), jnp.bfloat16), *pool,
        _s((), jnp.int32), _s((SLOTS, N_PTAB), jnp.int32),
        _s((SLOTS,), jnp.int32),
        _s((SLOTS, BUDGET), jnp.int32))
    (name, grid, blocks, params), = calls
    assert name == "paged_attention_ragged"
    # a 128-row query tile against a block of 8 pages a grid step; the
    # grid is one dimension, its length the live steps
    tile, ppb = 128, 8
    assert pa._ragged_blocks(2 * BUDGET, 2, KVH, HD, PAGE, N_PTAB) == (
        tile, ppb)
    assert len(grid) == 1
    assert blocks == ([(1, KVH, tile, HD), (1, tile, 1)]
                      + ([(pl.squeezed, 1, PAGE, KVH * HD)] * ppb
                         + [(pl.squeezed, 1, KVH, lanes)] * ppb) * 2)
    # at most every slot's 8 tiles over 40 blocks of its table
    _, work, blk, _ = pa._ragged_grid(
        jnp.full((SLOTS, 2 * BUDGET), -1), jnp.zeros((SLOTS,), jnp.int32),
        tile, PAGE, N_PTAB, ppb)
    assert work.shape == blk.shape == (SLOTS * 8 * 40,)
    # its blocks and values fit the default 16 MiB of scoped VMEM
    assert pa._ragged_vmem_bytes(KVH, tile, HD, PAGE, ppb, 2, 1, 4) \
        <= autotune.DEFAULT_SCOPED_VMEM
    assert params is None
