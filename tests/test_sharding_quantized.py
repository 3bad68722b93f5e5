"""Quantization-aware sharding specs: ``cache_sharding`` must treat the
quantized KV cache pytree (int8 codes + per-token scales) congruently —
name-pinned head axis, not the old shape heuristic that misreads a scale
(or short-T cache) as an SSM state — and ``tp_param_specs`` must shard
packed int4 row weights in packed units, scales with their column
weights, and transforms never."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.qlinear import QLinear
from repro.distributed.compat import abstract_mesh
from repro.distributed.sharding import (cache_sharding, tp_cache_specs,
                                        tp_param_specs)


@pytest.fixture(scope="module")
def mesh():
    return abstract_mesh((2, 2), ("data", "model"))


@pytest.fixture(scope="module")
def tp4_mesh():
    return abstract_mesh((1, 4), ("data", "model"))


def _cache_shapes(cfg, batch, max_len):
    from repro.models import build
    return jax.eval_shape(lambda: build(cfg).init_cache(batch, max_len))


# ------------------------------------------------------------ cache forms

def test_cache_sharding_fp_form(tiny_cfg, mesh):
    cache = _cache_shapes(tiny_cfg, 4, 32)
    assert set(cache) == {"k", "v", "pos"}
    sh = cache_sharding(cache, mesh)
    assert sh["k"].spec == sh["v"].spec
    assert sh["pos"].spec == P()


def test_cache_sharding_quantized_form_congruent(tiny_cfg, mesh):
    """codes and per-token scales must land on identical specs — a
    mismatch would dequantize codes against the wrong scale rows."""
    cfg = tiny_cfg.scaled(kv_quant_bits=8)
    cache = _cache_shapes(cfg, 4, 32)
    assert set(cache) == {"k", "k_scale", "v", "v_scale", "pos"}
    sh = cache_sharding(cache, mesh)
    assert sh["k"].spec == sh["k_scale"].spec
    assert sh["v"].spec == sh["v_scale"].spec
    assert sh["pos"].spec == P()
    # tiny smoke has n_kv_heads=2, model=2: heads shard on the head axis
    assert sh["k"].spec[3] == "model"


def test_cache_sharding_short_t_not_misread_as_state(tiny_cfg, mesh):
    """Adversarial shape: max_len < n_kv_heads broke the old T>KV
    heuristic (scale leaves have hd=1, so T>hd always 'looked like' a
    cache while short-T codes looked like SSM state). Names pin it."""
    cfg = tiny_cfg.scaled(n_kv_heads=4, n_heads=4, kv_quant_bits=8)
    cache = _cache_shapes(cfg, 4, 2)   # T=2 < KV=4
    sh = cache_sharding(cache, mesh)
    assert sh["k"].spec == sh["k_scale"].spec
    assert sh["k"].spec[3] == "model"  # heads, NOT the T axis
    assert sh["k"].spec[2] is None


def test_tp_cache_specs_head_axis_and_pos(tiny_cfg, tp4_mesh):
    cfg = tiny_cfg.scaled(n_kv_heads=4, kv_quant_bits=8)
    cache = _cache_shapes(cfg, 4, 32)
    specs = tp_cache_specs(cache, tp4_mesh)
    for key in ("k", "v", "k_scale", "v_scale"):
        assert specs[key] == P(None, None, None, "model", None), key
    assert specs["pos"] == P()
    # non-divisible heads replicate (never split head_dim)
    specs2 = tp_cache_specs(_cache_shapes(tiny_cfg.scaled(kv_quant_bits=8),
                                          4, 32), tp4_mesh)
    assert specs2["k"] == P(None, None, None, None, None)


# ------------------------------------------------------------- tp params

def _qlinear(d_in, d_out, packed, layers=2):
    from repro.core.quantizers import pack_int4
    codes = jnp.zeros((layers, d_in, d_out), jnp.int8)
    qw = pack_int4(codes, axis=-2) if packed else codes
    t = {"s": jnp.ones((d_in,))}   # smoothquant-shaped transform leaf
    return QLinear(qw, jnp.ones((layers, 1, d_out)), t, act_bits=4,
                   w_bits=4 if packed else 8, d_in=d_in if packed else 0)


def test_tp_param_specs_packed_row_shards_packed_units(tp4_mesh):
    params = {"layers": {"wo": _qlinear(128, 64, packed=True)}}
    specs = tp_param_specs(params, tp4_mesh, row_mode="psum")
    wo = specs["layers"]["wo"]
    # packed axis (128/2=64 rows) splits 4-ways in packed units
    assert wo.qweight == P(None, "model", None)
    assert wo.scale == P(None, None, None)          # row scale replicates
    assert wo.transform["s"] == P()
    # gather mode replicates the row weight entirely
    specs_g = tp_param_specs(params, tp4_mesh, row_mode="gather")
    assert specs_g["layers"]["wo"].qweight == P(None, None, None)


def test_tp_param_specs_col_shards_scale_with_weight(tp4_mesh):
    params = {"layers": {"wu": _qlinear(128, 64, packed=True)}}
    specs = tp_param_specs(params, tp4_mesh)
    wu = specs["layers"]["wu"]
    assert wu.qweight == P(None, None, "model")
    assert wu.scale == P(None, None, "model")
    assert wu.transform["s"] == P()


def test_tp_param_specs_odd_packed_k_replicates(tp4_mesh):
    """65 packed rows don't split 4-ways -> whole-byte fallback."""
    params = {"layers": {"wo": _qlinear(130, 64, packed=True)}}
    specs = tp_param_specs(params, tp4_mesh, row_mode="psum")
    assert specs["layers"]["wo"].qweight == P(None, None, None)


def test_tp_param_specs_head_boundaries_group_rule(tp4_mesh, tiny_cfg):
    """With cfg given, the attention projections shard as a GROUP: tiny
    smoke has n_heads=4 (divides tp=4) but n_kv_heads=2 (doesn't), so
    wq must replicate along with wk/wv — a head-sharded wq next to
    replicated kv projections would scramble the GQA q->kv pairing."""
    params = {"layers": {"wq": jnp.zeros((2, 128, 128)),
                         "wk": jnp.zeros((2, 128, 64)),
                         "wu": jnp.zeros((2, 128, 256))}}
    specs = tp_param_specs(params, tp4_mesh, cfg=tiny_cfg)
    assert specs["layers"]["wq"] == P(None, None, None)
    assert specs["layers"]["wk"] == P(None, None, None)
    assert specs["layers"]["wu"] == P(None, None, "model")
    free = tp_param_specs(params, tp4_mesh)   # no cfg: dim rule only
    assert free["layers"]["wk"] == P(None, None, "model")


def test_tp_param_specs_unembed_replicates(tp4_mesh):
    """unembed (and embed) stay whole: the engine's shard_map out_specs
    declare logits replicated, so a vocab-sharded unembed would silently
    emit wrong tokens for untied configs."""
    params = {"embed": jnp.zeros((512, 128)),
              "unembed": jnp.zeros((2, 128, 512))}
    specs = tp_param_specs(params, tp4_mesh)
    assert specs["embed"] == P(None, None)
    assert specs["unembed"] == P(None, None, None)


def test_tp_param_specs_spec_tree_matches_param_tree(tiny_quantized,
                                                     tp4_mesh):
    """The spec tree must flatten exactly like the (quantized) params —
    shard_map in_specs and device_put both require it."""
    specs = tp_param_specs(tiny_quantized, tp4_mesh)
    ps = jax.tree_util.tree_structure(tiny_quantized)
    ss = jax.tree_util.tree_structure(
        jax.tree.map(lambda s: 0, specs, is_leaf=lambda x: isinstance(x, P)))
    assert ps == ss
