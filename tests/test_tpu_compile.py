"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies through
XLA:CPU and accepts shapes, casts and dot types that Mosaic, the TPU
kernel compiler, refuses. Here each kernel on the main serving path is
lowered with ``interpret=False`` for one chip of a described (not
attached) ``v5e:2x2`` topology at ``catlm_60m``'s published widths
(d_model 512, 8/4 heads of 64, d_ff 1536, page size 16) and compiled;
the compiled text must hold the Mosaic kernel (``tpu_custom_call``).
Nothing executes, so this says nothing about results or speed.

The two serving step programs are compiled whole as well, at both
models' KV shapes, and must copy no part of the KV pool: the pool is
stored in the layout the kernels read and rides the layer loop in place
(``models.dense.init_paged_cache``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and test
workers import every test module.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_attention import scale_lanes

D, D_FF, N_Q, N_KV, HD = 512, 1536, 512, 256, 64
KVH, G = N_KV // HD, N_Q // N_KV
PAGE, N_PAGES, N_PTAB = 16, 297, 37     # 8 slots x 592 rows + null page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache without the chip; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, shapes, sharding):
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=sharding)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _cat_shapes(m, d):
    return [((m, d), jnp.bfloat16), ((d // 32, 32, 32), jnp.float32),
            ((32, 32), jnp.float32), ((d // 32, d // 32), jnp.float32),
            ((d,), jnp.float32)]


@pytest.mark.parametrize("m", [8, 256], ids=["gemv", "matmul"])
@pytest.mark.parametrize("d,n", [(D, N_Q + 2 * N_KV), (D, 2 * D_FF),
                                 (N_Q, D), (D_FF, D)],
                         ids=["wqkv", "wgu", "wo", "wd"])
def test_fused_cat_matmul_compiles(one_chip, no_cache, m, d, n):
    """The four serving linears of a layer, through the serving dispatch
    (block sizes from ``autotune``): decode rows take the GEMV kernel,
    a 256-token step budget the tiled kernel."""
    from repro.kernels import ops

    def f(x, blocks, ha, hb, sign, qw, sw):
        return ops.fused_cat_matmul(x, blocks, ha, hb, sign, qw, sw,
                                    act_bits=8, packed=True,
                                    interpret=False)

    _compile(f, _cat_shapes(m, d) + [((d // 2, n), jnp.int8),
                                     ((1, n), jnp.float32)], one_chip)


def test_hadamard_transform_compiles(one_chip, no_cache):
    from repro.kernels.hadamard import hadamard_transform
    _compile(lambda x, ha, hb, s: hadamard_transform(x, ha, hb, s,
                                                     interpret=False),
             [((256, D), jnp.float32), ((32, 32), jnp.float32),
              ((16, 16), jnp.float32), ((D,), jnp.float32)], one_chip)


def test_block_diag_matmul_compiles(one_chip, no_cache):
    from repro.kernels.block_matmul import block_diag_matmul
    _compile(lambda x, b: block_diag_matmul(x, b, interpret=False),
             [((256, D), jnp.float32), ((D // 32, 32, 32), jnp.float32)],
             one_chip)


def test_dynamic_quant_compiles(one_chip, no_cache):
    from repro.kernels.dynamic_quant import dynamic_quant
    _compile(lambda x: dynamic_quant(x, bits=8, interpret=False),
             [((256, D), jnp.float32)], one_chip)


def _qx_shapes(m, k, n, packed):
    return [((m, k), jnp.int8), ((m, 1), jnp.float32), ((m, 1), jnp.float32),
            ((k // 2 if packed else k, n), jnp.int8), ((1, n), jnp.float32)]


@pytest.mark.parametrize("name", ["quant_matmul", "quant_matmul_w4",
                                  "quant_gemv_w4"])
def test_quant_matmul_compiles(one_chip, no_cache, name):
    from repro.kernels import quant_matmul, quant_matmul_w4
    fn = {"quant_matmul": quant_matmul.quant_matmul,
          "quant_matmul_w4": quant_matmul_w4.quant_matmul_w4,
          "quant_gemv_w4": quant_matmul_w4.quant_gemv_w4}[name]
    m = 8 if name == "quant_gemv_w4" else 256
    _compile(lambda *a: fn(*a, interpret=False),
             _qx_shapes(m, D_FF, D, packed=name != "quant_matmul"),
             one_chip)


def _pool(n_pages, kvh, hd, layers=1):
    """Pool leaves (k, k_scale, v, v_scale) as ``init_paged_cache``
    stores them, followed by the layer index."""
    return [((layers, n_pages, PAGE, kvh * hd), jnp.int8),
            ((layers, n_pages, kvh, scale_lanes(PAGE)), jnp.float32)] * 2 \
        + [((), jnp.int32)]


def _pool_shapes():
    return _pool(N_PAGES, KVH, HD)


def test_decode_qkv_prologue_compiles(one_chip, no_cache):
    from repro.kernels.ops import decode_qkv_prologue
    n = N_Q + 2 * N_KV

    def f(x, blocks, ha, hb, sign, qw, sw, kp, ks, vp, vs, lyr, pid, rid,
          pos):
        return decode_qkv_prologue(
            x, blocks, ha, hb, sign, qw, sw, kp, ks, vp, vs, lyr, pid, rid,
            pos, n_q=N_Q, head_dim=HD, rope_theta=1e4, interpret=False)

    _compile(f, _cat_shapes(8, D) + [((D // 2, n), jnp.int8),
                                     ((1, n), jnp.float32)]
             + _pool_shapes() + [((8,), jnp.int32)] * 3, one_chip)


def test_paged_attention_decode_compiles(one_chip, no_cache):
    from repro.kernels.paged_attention import paged_attention_decode
    _compile(lambda q, *a: paged_attention_decode(q, *a, interpret=False),
             [((8, KVH, G, HD), jnp.bfloat16)] + _pool_shapes()
             + [((8, N_PTAB), jnp.int32), ((8,), jnp.int32)], one_chip)


def test_paged_attention_decode_compiles_nemo_widths(one_chip, no_cache):
    """Mistral-NeMo's widths (8 KV heads of 128, 32 query heads) over the
    cell's 320-page tables: the block of pages one grid step fetches,
    sized from the shapes, fits the default scoped VMEM."""
    from repro.kernels.paged_attention import paged_attention_decode
    kvh, hd, n_ptab = 8, 128, 320
    pool = _pool(1 + 8 * n_ptab, kvh, hd)
    _compile(lambda q, *a: paged_attention_decode(q, *a, interpret=False),
             [((8, kvh, 4, hd), jnp.bfloat16)] + pool
             + [((8, n_ptab), jnp.int32), ((8,), jnp.int32)], one_chip)


def test_paged_attention_ragged_compiles(one_chip, no_cache):
    from repro.kernels.paged_attention import paged_attention_ragged
    nq = 256          # the smoke budget: one item may fill the step
    _compile(lambda q, *a: paged_attention_ragged(q, *a, interpret=False),
             [((8, nq, KVH, G, HD), jnp.bfloat16)] + _pool_shapes()
             + [((8, N_PTAB), jnp.int32), ((8,), jnp.int32),
                ((8, nq), jnp.int32)], one_chip)


def test_compile_helper_finds_no_kernel_in_plain_xla(one_chip, no_cache):
    """The kernel check is not vacuous: plain XLA has no custom call."""
    text = jax.jit(lambda x: x * 2).lower(
        jax.ShapeDtypeStruct((8, 128), jnp.float32,
                             sharding=one_chip)).compile().as_text()
    assert "tpu_custom_call" not in text


# Mistral-NeMo-12B's widths: d_model 5120, 32 query heads and 8 KV heads
# of 128 (q_dim 4096, groups of 4), d_ff 14336; the ``nemo8.azconv``
# cell's 512-token step budget and 8 slots of 320 pages.
NEMO_D, NEMO_FF, NEMO_Q, NEMO_KV, NEMO_HD = 5120, 14336, 4096, 1024, 128
NEMO_KVH, NEMO_G = NEMO_KV // NEMO_HD, NEMO_Q // NEMO_KV


def _nemo_cat_shapes(m, d):
    from repro.core.hadamard import hadamard_factors
    ha, hb = hadamard_factors(d)
    return [((m, d), jnp.bfloat16), ((d // 32, 32, 32), jnp.float32),
            (ha.shape, jnp.float32), (hb.shape, jnp.float32),
            ((d,), jnp.float32)]


def _nemo_pool(n_ptab=320):
    return _pool(1 + 8 * n_ptab, NEMO_KVH, NEMO_HD)


@pytest.mark.parametrize("m", [8, 512], ids=["gemv", "matmul"])
@pytest.mark.parametrize("d,n", [(NEMO_D, NEMO_Q + 2 * NEMO_KV),
                                 (NEMO_D, 2 * NEMO_FF), (NEMO_Q, NEMO_D),
                                 (NEMO_FF, NEMO_D)],
                         ids=["wqkv", "wgu", "wo", "wd"])
def test_fused_cat_matmul_compiles_nemo_widths(one_chip, no_cache, m, d, n):
    """K up to 14336 and N up to 28672 through the serving dispatch: a
    512-row step asks for more than the default scoped VMEM, and the
    kernel sets its limit from the shapes."""
    from repro.kernels import ops

    def f(x, blocks, ha, hb, sign, qw, sw):
        return ops.fused_cat_matmul(x, blocks, ha, hb, sign, qw, sw,
                                    act_bits=8, packed=True,
                                    interpret=False)

    _compile(f, _nemo_cat_shapes(m, d) + [((d // 2, n), jnp.int8),
                                          ((1, n), jnp.float32)], one_chip)


def test_decode_qkv_prologue_compiles_nemo_widths(one_chip, no_cache):
    from repro.kernels.ops import decode_qkv_prologue
    n = NEMO_Q + 2 * NEMO_KV

    def f(x, blocks, ha, hb, sign, qw, sw, kp, ks, vp, vs, lyr, pid, rid,
          pos):
        return decode_qkv_prologue(
            x, blocks, ha, hb, sign, qw, sw, kp, ks, vp, vs, lyr, pid, rid,
            pos, n_q=NEMO_Q, head_dim=NEMO_HD, rope_theta=1e6,
            interpret=False)

    _compile(f, _nemo_cat_shapes(8, NEMO_D)
             + [((NEMO_D // 2, n), jnp.int8), ((1, n), jnp.float32)]
             + _nemo_pool() + [((8,), jnp.int32)] * 3, one_chip)


@pytest.mark.parametrize("kvh,g,hd,pool", [
    (NEMO_KVH, NEMO_G, NEMO_HD, "nemo"), (KVH, G, HD, "catlm")],
    ids=["nemo", "catlm"])
def test_paged_attention_ragged_compiles_cell_budget(one_chip, no_cache, kvh,
                                                     g, hd, pool):
    """Each of 8 work items a 512-query block over 320-page tables, as
    the cells' unified steps run it: 128-row query tiles against blocks
    of 8 pages fit the default scoped VMEM at both models' widths."""
    from repro.kernels.paged_attention import paged_attention_ragged
    nq, n_ptab = 512, 320
    pools = _pool(1 + 8 * n_ptab, kvh, hd)
    _compile(lambda q, *a: paged_attention_ragged(q, *a, interpret=False),
             [((8, nq, kvh, g, hd), jnp.bfloat16)] + pools
             + [((8, n_ptab), jnp.int32), ((8,), jnp.int32),
                ((8, nq), jnp.int32)], one_chip)


# ------------------------------------------------- whole step programs

# A pool page count that no other dimension of the step programs has, so
# that any shape holding it is a pool leaf or a slice of one.
GUARD_PAGES = 2579
STEP_WIDTHS = {
    # catlm_60m as served: d_model 512, 8/4 heads of 64, d_ff 1536
    "catlm": dict(),
    # Mistral-NeMo-12B's KV shape (8 KV heads of 128, groups of 4) on
    # narrow linears: the pool's layout follows from KVH, page and hd
    "nemo": dict(d_model=1024, n_heads=32, n_kv_heads=8, head_dim=128,
                 d_ff=1024, vocab=1024, n_layers=2),
}


def _serving_params(cfg):
    """The parameter tree ``ServeEngine`` serves on the chip for ``cfg``
    (zeros; nothing runs): every linear a packed CAT-W4A8 ``QLinear``
    (block CAT of 32, then Hadamard) as ``quantize_model`` makes it, fused
    by ``make_serving_params`` for the Pallas linears."""
    from repro.core import transforms as T
    from repro.core.hadamard import hadamard_factors
    from repro.core.qlinear import QLinear
    from repro.models import dense

    n = cfg.n_layers

    def linear(d_in, d_out):
        ha, hb = hadamard_factors(d_in)
        blocks = jnp.zeros((n, d_in // 32, 32, 32))
        t = T.Compose((T.BlockDiag(blocks, blocks),
                       T.Hadamard(jnp.zeros((n,) + ha.shape),
                                  jnp.zeros((n,) + hb.shape),
                                  jnp.ones((n, d_in)))))
        return QLinear(jnp.zeros((n, d_in // 2, d_out), jnp.int8),
                       jnp.ones((n, 1, d_out)), t, act_bits=8, w_bits=4,
                       d_in=d_in)

    d, f = cfg.d_model, cfg.d_ff
    layers = {"ln1": jnp.zeros((n, d)), "ln2": jnp.zeros((n, d)),
              "wq": linear(d, cfg.q_dim), "wk": linear(d, cfg.kv_dim),
              "wv": linear(d, cfg.kv_dim), "wo": linear(cfg.q_dim, d),
              "wg": linear(d, f), "wu": linear(d, f), "wd": linear(f, d)}
    params = {"embed": jnp.zeros((cfg.vocab, d)),
              "final_norm": jnp.zeros((d,)), "layers": layers}
    if not cfg.tie_embeddings:
        params["unembed"] = jnp.zeros((d, cfg.vocab))
    return dense.make_serving_params(cfg, params, keep_packed=True)


@pytest.fixture
def chip_routes(monkeypatch):
    """Trace the step programs as the chip runs them: the fused decode
    path, the Pallas linears and Mosaic kernels (not interpret mode)."""
    from repro.core import qlinear
    monkeypatch.setenv("REPRO_DECODE_FUSED", "1")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setattr(qlinear, "_use_fused_kernel", lambda: True)


def _pool_copies(text: str, n_pages: int) -> list:
    """The compiled program's ``copy`` ops whose result shape holds
    ``n_pages``: copies of a pool leaf or of a layer's slice of one."""
    dim = re.compile(rf"\[([0-9]+,)*{n_pages}(,[0-9]+)*\]")
    return [line.strip() for line in text.splitlines()
            if " copy(" in line and dim.search(line.split(" copy(")[0])]


@pytest.mark.parametrize("step", ["decode", "unified"])
@pytest.mark.parametrize("widths", sorted(STEP_WIDTHS))
def test_step_program_copies_no_pool(one_chip, no_cache, chip_routes,
                                     widths, step):
    """``_fused_decode_step`` (the pure-decode fast path: QKV prologue and
    paged attention) and ``_unified_step`` (XLA scatter of the step's K/V,
    then the ragged kernel) over an 8-slot, 320-page-table pool: the
    compiled programs hold the kernels and copy no pool leaf and no layer
    slice of one, in the layer loop or around it."""
    from repro.configs import get_config
    from repro.launch import executor
    from repro.models import build, dense

    cfg = get_config("catlm_60m").scaled(kv_quant_bits=8,
                                         **STEP_WIDTHS[widths])
    model = build(cfg)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(lambda: _serving_params(cfg)))
    cache = jax.tree.map(sds, jax.eval_shape(
        lambda: dense.init_paged_cache(cfg, GUARD_PAGES, PAGE)))
    slots, n_ptab, budget = 8, 320, 512

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if step == "decode":
        decode_fn = (lambda p, t, c: model.decode(p, t, c,
                                                  paged_kernel=True))
        lowered = executor._fused_decode_step.lower(
            decode_fn, params, cache, i32(slots, 1), i32(slots),
            i32(slots, n_ptab), i32(slots), i32(slots))
        kernels = ("decode_qkv_prologue", "paged_attention_decode")
    else:
        desc = {"qidx": i32(slots, budget), "qpos": i32(slots, budget),
                "lengths": i32(slots), "table": i32(slots, n_ptab),
                "inv_seq": i32(budget), "inv_qi": i32(budget)}
        lowered = executor._unified_step.lower(
            model.ragged_step, True, True, params, cache, i32(budget, 1),
            i32(budget), i32(budget, n_ptab), i32(slots), desc, i32(slots),
            i32(budget))
        kernels = ("paged_attention_ragged",)
    text = lowered.compile().as_text()
    for name in kernels:
        assert name in text, name
    assert _pool_copies(text, GUARD_PAGES) == []


def test_pool_copy_finder_finds_a_pool_copy(one_chip, no_cache):
    """The guard is not vacuous: a relayout of a pool leaf is found."""
    text = jax.jit(lambda a: jnp.swapaxes(a, 2, 3) + 1).lower(
        jax.ShapeDtypeStruct((2, GUARD_PAGES, 4, 16), jnp.float32,
                             sharding=one_chip)).compile().as_text()
    assert _pool_copies(text, GUARD_PAGES)
