"""Logical sharding rules: param/batch/cache pytrees -> NamedSharding.

Mesh axes: ("pod", "data", "model") multi-pod or ("data", "model") single
pod. DP runs over pod×data (gradients all-reduce across both), TP/EP over
model, SP (long-context) shards the KV/sequence dim over data.

Rules are name-based over the stable param paths the model zoo emits; a
dim is sharded only when divisible by the mesh axis size (else replicated
— MQA KV heads, tiny routers, conv kernels etc. fall out naturally).

Quantized serving adds two wrinkles this module owns:

- int4-packed ``QLinear.qweight`` is packed two-nibbles-per-byte along K,
  so row-parallel (contracted-dim) sharding must split the *packed* axis
  in packed units — each shard then holds whole bytes and ``2·K_packed/tp``
  unpacked K rows. Column-parallel weights shard d_out, which packing
  never touches. Per-output-channel scales follow column-parallel weights
  and replicate for row-parallel ones; transform factors (small
  block/Hadamard matrices acting on the *full* input dim) always
  replicate.
- quantized KV caches are a (codes int8, per-token scale f32) pair per
  K/V; both must shard the head axis congruently or a decode step would
  dequantize codes against the wrong slice of scales.
- *paged* KV pools (``models.dense.init_paged_cache``) are (L, n_pages,
  page_size, KV·hd) codes, whole heads side by side on the lane axis 3,
  and (L, n_pages, KV, lanes) scales, heads on axis 2: both shard their
  heads on ``model`` — congruently, whole heads per shard — while the
  page axis NEVER shards (every device holds its head slice of every
  physical page; the host-side page table indexes pages globally) and
  the ``page_table`` leaf replicates like ``pos``.

``tp_param_specs``/``tp_cache_specs`` emit plain PartitionSpec trees for
``shard_map`` (the serve engine's tensor-parallel mode); the
NamedSharding builders below serve ``jit``/``device_put``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.qlinear import QLinear

# param-name -> which dim gets "model". Dims count from the END so the
# same rule covers stacked (L, ...) / per-expert (L, E, ...) variants.
_COL = {"wq", "wk", "wv", "wg", "wu", "we_g", "we_u", "ck",
        "in_x", "in_z", "in_b", "in_c", "unembed", "xq", "xk", "xv"}
_ROW = {"wo", "wd", "we_d", "cv", "out_proj", "xo"}
_REPL = {"router", "w_lora_a", "w_lora_b", "w0", "u", "mu_tmix", "mu_cmix",
         "conv_w", "a_log", "dt_bias", "d_skip", "in_dt", "enc_pos",
         "dec_pos"}


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _model_size(mesh) -> int:
    return mesh.shape.get("model", 1)


def _spec_for(name: str, leaf, mesh) -> P:
    ms = _model_size(mesh)
    ndim = getattr(leaf, "ndim", len(leaf.shape))
    shape = leaf.shape

    def ok(dim_from_end):
        return shape[ndim - dim_from_end] % ms == 0

    if name == "embed":
        return P("model", None) if shape[0] % ms == 0 else P(None, None)
    if name in _COL and ndim >= 2 and ok(1):
        return P(*([None] * (ndim - 1) + ["model"]))
    if name in _ROW and ndim >= 2 and ok(2):
        return P(*([None] * (ndim - 2) + ["model", None]))
    return P(*([None] * ndim))


# QLinear / transform pytree field names (paths look like layers/wq/qweight)
_QFIELDS = {"qweight", "scale", "blocks", "inv_blocks", "ha", "hb", "sign",
            "s", "t", "t_inv"}
_WEIGHT_NAMES = _COL | _ROW | _REPL | {"embed"}


def params_sharding(params, mesh):
    """NamedSharding tree matching `params` (works on ShapeDtypeStructs).
    Quantized leaves: qweight shards like the fp weight it replaced; the
    per-output-channel scale follows column-parallel weights; transform
    leaves (small blocks/Hadamard factors/signs) replicate."""

    def walk(path, leaf):
        keys = _path_keys(path)
        field = keys[-1] if keys and keys[-1] in _QFIELDS else None
        wname = next((k for k in reversed(keys) if k in _WEIGHT_NAMES), None)
        ms = _model_size(mesh)
        ndim = len(leaf.shape)
        if field in (None, "qweight"):
            spec = _spec_for(wname or (keys[-1] if keys else ""), leaf, mesh)
        elif field == "scale" and wname in _COL and ndim >= 1 \
                and leaf.shape[-1] % ms == 0:
            spec = P(*([None] * (ndim - 1) + ["model"]))
        else:
            spec = P(*([None] * ndim))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(
        walk, params,
        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))


def zero_opt_sharding(params_sh, opt_shapes, mesh, params_shapes=None):
    """ZeRO-1: m/v/master pick up an extra 'data' sharding on the first
    dim that is divisible and not already model-sharded; scalars stay
    replicated. params keep their own (model-only) sharding."""
    data = mesh.shape.get("data", 1)

    def widen(ps, leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        spec = list(ps.spec) + [None] * (nd - len(ps.spec))
        for dim in range(nd):
            if spec[dim] is None and leaf.shape[dim] % data == 0 \
                    and leaf.shape[dim] >= data:
                spec[dim] = "data"
                break
        return NamedSharding(mesh, P(*spec))

    out = {}
    for key in ("m", "v", "master"):
        if key in opt_shapes:
            out[key] = jax.tree.map(widen, params_sh, opt_shapes[key])
    out["step"] = NamedSharding(mesh, P())
    return out


def batch_sharding(batch, mesh, shard_seq: bool = False):
    """tokens/labels (B, S): batch over dp axes when divisible; optional SP
    shards S over 'data' (long-context, batch=1); replicate otherwise."""
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def spec(leaf):
        nd = len(leaf.shape)
        if dp and leaf.shape[0] % dp_size == 0:
            return NamedSharding(mesh, P(dp, *([None] * (nd - 1))))
        if shard_seq and nd >= 2 and leaf.shape[1] % mesh.shape.get(
                "data", 1) == 0 and leaf.shape[1] > 1:
            return NamedSharding(mesh, P(None, "data", *([None] * (nd - 2))))
        return NamedSharding(mesh, P(*([None] * nd)))

    return jax.tree.map(spec, batch,
                        is_leaf=lambda x: hasattr(x, "shape")
                        and not isinstance(x, dict))


# KV-cache leaf names: codes and their per-token scales must shard the
# head axis congruently (a decode step dequantizes codes against scales).
_KV_KEYS = {"k", "v"}
_KV_SCALE_KEYS = {"k_scale", "v_scale"}


def _head_axes(cache):
    """-> ``axis(key, shape)``: a KV cache leaf's (head axis, head
    count), or (None, None) for any other leaf.

    Contiguous caches (L, B, T, KV, hd|1) hold heads on axis 3. A paged
    pool (``models.dense.init_paged_cache``) holds them on the lane axis
    3 of its (L, n_pages, G, KV·hd) codes and on axis 2 of its (L,
    n_pages, KV, lanes) scales; the scales give the head count. An fp
    pool has no scales: its lanes stand in, and mesh serving admits only
    whole heads per shard (``launch.executor._validate_tp``)."""
    scale = cache.get("k_scale") if isinstance(cache, dict) else None
    paged_heads = (scale.shape[2] if scale is not None and scale.ndim == 4
                   else None)

    def axis(key, shape):
        nd = len(shape)
        if nd == 5 and key not in ("pos", "page_table"):
            return 3, shape[3]
        if nd == 4 and key in _KV_SCALE_KEYS:
            return 2, shape[2]
        if nd == 4 and key in _KV_KEYS:
            return 3, paged_heads or shape[3]
        return None, None

    return axis


def cache_sharding(cache, mesh, cfg=None, shard_seq: bool = False):
    """KV caches (L, B, T, KV, hd): batch on dp, heads on model when
    divisible; long-context (B not divisible) shards T on data instead.
    SSM states (L, B, H, dk, dv): heads on model.

    Quantized caches carry per-token scale leaves (L, B, T, KV, 1) next
    to the int8 codes; leaf *names* (k/v vs k_scale/v_scale) pin the head
    axis so scales shard exactly like their codes — the shape heuristic
    alone would misread a scale (or a short-T cache) as an SSM state.

    Paged caches (a ``page_table`` leaf next to the pools of
    ``models.dense.init_paged_cache``) shard heads on model only: the
    page axis stays whole on every device (page ids are global) and the
    table replicates."""
    dp = dp_axes(mesh)
    ms = _model_size(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    paged = isinstance(cache, dict) and "page_table" in cache

    head_axis = _head_axes(cache)

    def spec(path, leaf):
        shape = leaf.shape
        nd = len(shape)
        key = _last_key(path)
        if nd == 0 or key in ("pos", "page_table"):
            return NamedSharding(mesh, P(*([None] * nd)))
        ax, heads = head_axis(key, shape)
        if paged and ax is not None:
            sp = [None] * nd
            sp[ax] = "model" if heads % ms == 0 else None
            return NamedSharding(mesh, P(*sp))
        if nd == 5:  # (L, B, T, KV, hd) kv-cache or (L, B, H, dk, dv) state
            batch_ok = dp and shape[1] % dp_size == 0
            if key in _KV_KEYS or key in _KV_SCALE_KEYS:
                is_kv = True          # name-pinned: head axis is 3
            else:
                is_kv = shape[2] > shape[3]  # T dim much larger than heads
            head_ax = 3 if is_kv else 2
            heads = shape[head_ax]
            hspec = "model" if heads % ms == 0 else None
            if is_kv:
                t_ok = shape[2] % ms == 0 and shape[2] > 1
                # heads not TP-divisible (MQA/GQA-small): shard T on model
                tspec_m = "model" if (hspec is None and t_ok) else None
                if batch_ok:
                    return NamedSharding(mesh, P(None, dp, tspec_m, hspec,
                                                 None))
                t_data = "data" if shape[2] % mesh.shape.get("data", 1) == 0 \
                    else None
                return NamedSharding(mesh, P(None, None,
                                             t_data or tspec_m, hspec, None))
            if batch_ok:
                return NamedSharding(mesh, P(None, dp, hspec, None, None))
            return NamedSharding(mesh, P(None, None, hspec, None, None))
        if nd >= 2:
            batch_ax = 1 if nd >= 3 else 0
            if shape[batch_ax] % dp_size == 0:
                sp = [None] * nd
                sp[batch_ax] = dp
                return NamedSharding(mesh, P(*sp))
        return NamedSharding(mesh, P(*([None] * nd)))

    return jax.tree_util.tree_map_with_path(
        spec, cache,
        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))


def _path_keys(path) -> list:
    """String keys along a jax tree path (dict keys + dataclass fields)."""
    keys = []
    for entry in path:
        key = getattr(entry, "key", None)
        if key is None:
            key = getattr(entry, "name", None)
        if isinstance(key, str):
            keys.append(key)
    return keys


def _last_key(path) -> Optional[str]:
    keys = _path_keys(path)
    return keys[-1] if keys else None


# ------------------------------------------- shard_map TP PartitionSpecs

def tp_partition(name: Optional[str]) -> str:
    """Megatron role of a weight: 'col' (output dim sharded, no comm),
    'row' (contracted input dim sharded, psum), or 'replicated'."""
    if name in _COL:
        return "col"
    if name in _ROW:
        return "row"
    return "replicated"


def _tp_qlinear_specs(p: QLinear, part: str, tp: int, axis: str) -> QLinear:
    """PartitionSpec-valued QLinear mirroring ``p`` (meta fields kept, so
    the spec tree flattens identically). Row-parallel packed weights shard
    the packed axis — whole bytes per shard, K must split in packed units."""
    qnd = p.qweight.ndim
    qspec = P(*([None] * qnd))
    snd = p.scale.ndim
    sspec = P(*([None] * snd))
    if part == "col" and p.qweight.shape[-1] % tp == 0:
        qspec = P(*([None] * (qnd - 1) + [axis]))
        if p.scale.shape[-1] % tp == 0:
            sspec = P(*([None] * (snd - 1) + [axis]))
    elif part == "row" and qnd >= 2 and p.qweight.shape[-2] % tp == 0:
        qspec = P(*([None] * (qnd - 2) + [axis, None]))
    return dataclasses.replace(
        p, qweight=qspec, scale=sspec,
        transform=jax.tree.map(lambda _: P(), p.transform))


# Attention projections shard in units of whole heads: the reshape to
# (B, S, H, hd) and RoPE assume every device holds complete heads.
_ATTN_WEIGHTS = {"wq", "wk", "wv", "wo"}


def tp_param_specs(params, mesh, axis: str = "model", cfg=None,
                   row_mode: str = "gather"):
    """PartitionSpec tree for running the model forward under shard_map
    on a tensor-parallel mesh axis.

    Column weights (wq/wk/wv/wg/wu, ...) shard d_out (whole heads / FFN
    columns per device). Row weights (wo/wd, ...) follow ``row_mode``:

    - ``"gather"`` (default): replicate them; the forward all-gathers the
      sharded activation and contracts against the full weight. Column
      slices of a matmul are bitwise exact, so the whole forward — and
      every greedy token — is **bit-identical** to one device.
    - ``"psum"``: shard the contracted dim — in *packed units* for
      int4-packed QLinear — and psum partial outputs. True Megatron row
      parallelism (half the row-weight bytes per device), but partial-sum
      order makes it rtol-level, not bitwise, equal.

    Embedding, unembed, and norms replicate (residual stream and vocab
    dim stay whole). Falls back to replication wherever a dim does not
    divide; with ``cfg`` given, the attention projections (as a group —
    wq/wk/wv/wo shard together or not at all) additionally require BOTH
    head counts to divide, so no shard ever holds a partial head and the
    GQA q→kv pairing stays intact (MQA/GQA-small then replicates instead
    of splitting head_dim)."""
    assert row_mode in ("gather", "psum"), row_mode
    tp = mesh.shape[axis]
    # The attention projections shard as a GROUP: a head-sharded wq next
    # to replicated wk/wv would scramble the contiguous-block GQA pairing
    # inside chunked_attention, so if EITHER head count fails to divide,
    # all of wq/wk/wv/wo replicate together.
    attn_ok = cfg is None or (cfg.n_heads % tp == 0
                              and cfg.n_kv_heads % tp == 0)

    def walk(path, leaf):
        keys = _path_keys(path)
        wname = next((k for k in reversed(keys) if k in _WEIGHT_NAMES), None)
        part = tp_partition(wname)
        if (wname in ("embed", "unembed")          # logits stay whole
                or (wname in _ATTN_WEIGHTS and not attn_ok)
                or (part == "row" and row_mode == "gather")):
            part = "replicated"
        if isinstance(leaf, QLinear):
            return _tp_qlinear_specs(leaf, part, tp, axis)
        nd = len(leaf.shape)
        if part == "col" and nd >= 2 and leaf.shape[-1] % tp == 0:
            return P(*([None] * (nd - 1) + [axis]))
        if part == "row" and nd >= 2 and leaf.shape[-2] % tp == 0:
            return P(*([None] * (nd - 2) + [axis, None]))
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(
        walk, params,
        is_leaf=lambda x: isinstance(x, QLinear)
        or (hasattr(x, "shape") and not isinstance(x, dict)))


def tp_cache_specs(cache, mesh, axis: str = "model"):
    """PartitionSpec tree for a decode cache under shard_map: KV codes
    AND their per-token scales shard the head axis congruently when the
    head count divides; ``pos`` and anything non-divisible replicate.

    Axis 3 is the head axis of the contiguous layout (L, B, T, KV, hd);
    a paged pool holds whole heads on the lane axis 3 of its (L, n_pages,
    page_size, KV·hd) codes and on axis 2 of its (L, n_pages, KV, lanes)
    scales (``_head_axes``), so codes/scales shard congruently either
    way; the page axis stays whole and the ``page_table`` replicates
    alongside ``pos``."""
    tp = mesh.shape[axis]
    head_axis = _head_axes(cache)

    def walk(path, leaf):
        sp = [None] * len(leaf.shape)
        ax, heads = head_axis(_last_key(path), leaf.shape)
        if ax is not None and heads % tp == 0:
            sp[ax] = axis
        return P(*sp)

    return jax.tree_util.tree_map_with_path(
        walk, cache,
        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))


def ragged_desc_specs(desc) -> dict:
    """PartitionSpecs for the unified ragged step's host-built descriptor
    arrays (packed tokens / per-token positions / page-table rows / logit
    rows / kernel query blocks): everything **replicates** — descriptors
    are tiny int32 control data indexing the *global* page pool, exactly
    like ``pos``/``page_table`` in ``tp_cache_specs``; only the KV pools
    and params shard. Works on arrays or ShapeDtypeStructs."""
    return jax.tree.map(lambda a: P(*([None] * len(a.shape))), desc,
                        is_leaf=lambda x: hasattr(x, "shape")
                        and not isinstance(x, dict))


def named(spec_tree, mesh):
    """PartitionSpec tree -> NamedSharding tree (device_put / jit)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_sharding(params_sh, opt_state_shapes):
    """Adam m/v mirror the param shardings; scalars replicated."""
    def mirror(ps, leaf):
        if getattr(leaf, "ndim", 0) == 0:
            return NamedSharding(ps.mesh, P())
        return ps
    m = jax.tree.map(mirror, params_sh, opt_state_shapes["m"])
    v = jax.tree.map(mirror, params_sh, opt_state_shapes["v"])
    mesh = jax.tree.leaves(params_sh)[0].mesh
    return {"m": m, "v": v,
            "step": NamedSharding(mesh, P())}
