"""Dense decoder-only transformer family.

Covers: gemma2-2b (local/global alternation, softcaps, sandwich norms),
gemma3-12b (5:1 local:global, qk-norm), mistral-nemo-12b, granite-34b
(MQA), paligemma-3b backbone (prefix patch embeddings), catlm-60m, and the
MoE variants (granite-moe, moonshot) via repro.models.moe.

Layers are stacked on a leading axis and driven by lax.scan (small HLO,
fast multi-pod compiles). ``unroll=True`` runs a Python loop instead so
calibration taps can observe per-layer activations.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import qlinear
from repro.models import moe as moe_lib
from repro.models.layers import (chunked_attention, cache_update, glu_mlp,
                                 rms_norm, rope, softcap)


def _compute_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _init_linear(rng, d_in, d_out, dtype=jnp.float32):
    return (jax.random.normal(rng, (d_in, d_out), dtype=jnp.float32)
            / jnp.sqrt(d_in)).astype(dtype)


def is_global_flags(cfg) -> jnp.ndarray:
    """(L,) bool: which layers use global (full) attention."""
    if not cfg.window or cfg.local_ratio == 0:
        return jnp.ones((cfg.n_layers,), bool)
    idx = jnp.arange(cfg.n_layers)
    return (idx % (cfg.local_ratio + 1)) == cfg.local_ratio


def init(cfg, rng) -> dict:
    keys = iter(jax.random.split(rng, 64))
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    Hq, Hkv = cfg.q_dim, cfg.kv_dim

    def lin(d_in, d_out, extra=()):
        k = next(keys)
        ks = jax.random.split(k, L)
        return jnp.stack([_init_linear(ks[i], d_in, d_out) for i in range(L)]
                         ) if not extra else None

    # vectorized per-layer init (vmap over layer axis keeps it fast)
    def lins(d_in, d_out):
        k = jax.random.split(next(keys), L)
        return jax.vmap(lambda kk: _init_linear(kk, d_in, d_out))(k)

    layers = {
        "ln1": jnp.zeros((L, D)),
        "ln2": jnp.zeros((L, D)),
        "wq": lins(D, Hq),
        "wk": lins(D, Hkv),
        "wv": lins(D, Hkv),
        "wo": lins(Hq, D),
    }
    if cfg.post_norms:
        layers["ln1_post"] = jnp.zeros((L, D))
        layers["ln2_post"] = jnp.zeros((L, D))
    if cfg.qk_norm:
        layers["q_norm"] = jnp.zeros((L, cfg.head_dim))
        layers["k_norm"] = jnp.zeros((L, cfg.head_dim))
    if cfg.n_experts:
        layers.update(moe_lib.init_layers(cfg, next(keys)))
    else:
        if cfg.gated_mlp:
            layers["wg"] = lins(D, F)
        layers["wu"] = lins(D, F)
        layers["wd"] = lins(F, D)

    params = {
        "embed": jax.random.normal(next(keys), (cfg.vocab, D)) * 0.02,
        "final_norm": jnp.zeros((D,)),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["unembed"] = _init_linear(next(keys), D, cfg.vocab)
    return params


# ----------------------------------------------------------------- forward

def _fused_decode_operands(cfg, lp, cache, s, b, tp_axis, ragged_desc,
                           page_table, paged_kernel):
    """Two-launch decode gate: returns (wqkv QLinear, (blocks, ha, hb,
    sign)) when this layer's attention block can run the one-launch QKV
    prologue (``kernels/decode_layer.py``) + paged attention, else None.

    The prologue covers exactly the composed quantized decode shape:
    single-token rows (s == 1, B <= 8), quantized paged pools, serving
    params with a concatenated QKV QLinear whose transform the fused
    kernels can decompose, and none of the attention features the paged
    kernel already excludes (windows, softcap, qk-norm). Mixed-q_len
    (ragged) and tensor-parallel steps keep the current path. Routing is
    decided by ``ops.use_fused_decode()`` (backend/env), so off-TPU
    golden fixtures keep the composed path's exact numerics by default.
    """
    if not (paged_kernel and s == 1 and ragged_desc is None
            and tp_axis is None and page_table is not None
            and cache is not None and "k_scale" in cache
            and bool(cfg.kv_quant_bits) and b <= 8
            and not cfg.window and not cfg.attn_softcap
            and not cfg.qk_norm):
        return None
    p = lp.get("wqkv")
    if not isinstance(p, qlinear.QLinear) or not p.act_bits:
        return None
    from repro.kernels import ops
    if not ops.use_fused_decode():
        return None
    dec = ops.fused_transform_operands(p.transform)
    if dec is None:
        return None
    return p, dec


def _fused_decode_attn(cfg, fd, h, cache, layer, page_table, pos, b):
    """The two-launch decode attention block: ONE prologue launch (CAT ->
    quant -> W4A8 QKV GEMV -> RoPE -> int8 KV quant -> paged scatter into
    layer ``layer`` of the stacked pools) feeding ONE paged-attention
    launch over that layer. Returns (o (B, 1, Hq·hd), new_cache)."""
    from repro.kernels import ops
    from repro.models.layers import _paged_indices

    p, (blocks, ha, hb, sign) = fd
    cd = h.dtype
    page_size = cache["k"].shape[2]
    pos_vec = (pos if getattr(pos, "ndim", 0)
               else jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)))
    pids, rows = _paged_indices(page_table, pos_vec, b, 1, page_size)
    q, ck, cks, cv, cvs = ops.decode_qkv_prologue(
        h.reshape(b, -1), blocks, ha, hb, sign, p.qweight, p.scale,
        cache["k"], cache["k_scale"], cache["v"], cache["v_scale"], layer,
        pids, rows, pos_vec,
        n_q=cfg.q_dim, head_dim=cfg.head_dim,
        rope_theta=float(cfg.rope_theta), kv_bits=cfg.kv_quant_bits,
        act_bits=p.act_bits, packed=p.packed)
    new_cache = {"k": ck, "k_scale": cks, "v": cv, "v_scale": cvs}
    kvh = ck.shape[3] // cfg.head_dim
    g = cfg.q_dim // cfg.head_dim // kvh
    qk = q.astype(cd).reshape(b, kvh, g, cfg.head_dim)
    lengths = pos_vec + 1
    o = ops.paged_attention(qk, ck, cks, cv, cvs, layer, page_table,
                            lengths.astype(jnp.int32))
    return o.reshape(b, 1, -1).astype(cd), new_cache


def _layer_body(cfg, x, lp, cache, is_global, pos, positions,
                taps=None, layer_idx=None, tp_axis=None,
                tp_mode: str = "gather", tp_kernels=False,
                page_table=None, paged_kernel: bool = False,
                ragged_desc=None):
    """cache: the whole cache dict ({"k","v"[,"k_scale","v_scale"]}), every
    layer stacked on the leading axis, or None; the layer reads and
    writes its own layer, ``layer_idx`` (a traced int32 in the layer
    scan). Returns (x, new_cache, aux).

    With ``page_table`` (B, n_ptab) the cache leaves are *page pools*
    (``init_paged_cache``): k/v writes scatter to the physical rows the
    table maps [pos, pos+S) to in layer ``layer_idx``, and attention
    reads the gathered logical view — identical content and shape to the
    contiguous slot cache, so decoded tokens stay bitwise the same.
    ``paged_kernel`` additionally routes single-token (decode) attention
    on quantized pools through the Pallas paged-attention kernel
    (streams int8 pages, scales in VMEM — rtol-level, not bitwise).

    With ``tp_axis`` the body runs INSIDE shard_map on a tensor-parallel
    mesh axis: wq/wk/wv/wg/wu arrive column-sharded (whole local heads /
    FFN columns — head counts are derived from the projection shapes, not
    cfg) and the KV cache slices are head-sharded congruently. The
    row-position layers (wo/wd) follow ``tp_mode``: ``"gather"``
    all-gathers the head-/FFN-sharded activation and contracts against a
    replicated weight (bitwise-identical to single device — column slices
    of a matmul are exact); ``"psum"`` keeps the weight K-sharded and
    psums partial contractions via ``qlinear.dense_tp`` (rtol-level;
    ``tp_kernels=True`` additionally routes the local contraction through
    the packed W4A8 Pallas kernels)."""
    b, s, d = x.shape
    cd = x.dtype

    def row_dense(p, h):
        if tp_axis is None:
            return qlinear.dense(p, h)
        if tp_mode == "psum":
            return qlinear.dense_tp(p, h, tp_axis, use_kernel=tp_kernels)
        h = jax.lax.all_gather(h, tp_axis, axis=h.ndim - 1, tiled=True)
        return qlinear.dense(p, h)

    h = rms_norm(x, lp["ln1"])
    _tap(taps, layer_idx, "attn_in", h)
    fd = _fused_decode_operands(cfg, lp, cache, s, b, tp_axis,
                                ragged_desc, page_table, paged_kernel)
    if fd is not None:
        # two-launch decode: the QKV prologue kernel replaces the dense
        # projection + rope + KV-quant + scatter chain below. Numerics
        # follow the integer-accumulation route (``qlinear.dense_fused``
        # route 3 == the TPU kernel route), NOT the portable bf16
        # ``w_eff`` route the composed path takes off-TPU — the same
        # documented route-2/route-3 gap; gating defaults off outside
        # TPU (REPRO_DECODE_FUSED overrides) so stock CPU runs keep the
        # composed path bitwise.
        o, new_cache = _fused_decode_attn(cfg, fd, h, cache, layer_idx,
                                          page_table, pos, b)
    else:
        if "wqkv" in lp:
            # fused serving params (make_serving_params): one concatenated
            # QKV projection — one transform+quant+matmul chain instead of
            # three. Column slices of a matmul are exact, so splitting the
            # output reproduces the separate projections bitwise.
            qkv = qlinear.dense(lp["wqkv"], h)
            hq, hkv = cfg.q_dim, cfg.kv_dim
            q = qkv[..., :hq]
            k = qkv[..., hq:hq + hkv]
            v = qkv[..., hq + hkv:]
            q = q.reshape(b, s, -1, cfg.head_dim)
            k = k.reshape(b, s, -1, cfg.head_dim)
            v = v.reshape(b, s, -1, cfg.head_dim)
        else:
            q = qlinear.dense(lp["wq"], h).reshape(b, s, -1, cfg.head_dim)
            k = qlinear.dense(lp["wk"], h).reshape(b, s, -1, cfg.head_dim)
            v = qlinear.dense(lp["wv"], h).reshape(b, s, -1, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"])
            k = rms_norm(k, lp["k_norm"])
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

        window = None
        if cfg.window:
            window = jnp.where(is_global, jnp.int32(2**30),
                               jnp.int32(cfg.window))

        quant_cache = bool(cfg.kv_quant_bits) and cache is not None \
            and "k_scale" in cache
        if cfg.kv_quant_bits and not quant_cache:
            # no cache (training fwd): simulate KV quantization numerics
            from repro.core.quantizers import QuantSpec, fake_quant
            kv_spec = QuantSpec(bits=cfg.kv_quant_bits, symmetric=False,
                                per="token", dynamic=True)
            k = fake_quant(k, kv_spec)
            v = fake_quant(v, kv_spec)

        new_cache = None
        o = None
        if cache is not None and page_table is not None:
            from repro.models.layers import (gather_pages, gather_scales,
                                             paged_cache_update,
                                             paged_cache_update_quantized)
            if quant_cache:
                ck, cks, cv, cvs = paged_cache_update_quantized(
                    cache["k"], cache["k_scale"], cache["v"],
                    cache["v_scale"], k, v, page_table, pos, layer_idx,
                    cfg.kv_quant_bits)
                new_cache = {"k": ck, "k_scale": cks, "v": cv,
                             "v_scale": cvs}
                use_kernel = (paged_kernel and s == 1 and window is None
                              and not cfg.attn_softcap)
                if use_kernel and ragged_desc is not None:
                    # unified ragged step: regroup the flat packed rows
                    # into per-work-item query blocks so every sequence's
                    # pages stream ONCE for all its prefill-chunk +
                    # decode queries (one launch for the mixed batch)
                    from repro.kernels import ops
                    kvh = ck.shape[3] // cfg.head_dim
                    qf = q.reshape(b, kvh, q.shape[2] // kvh, cfg.head_dim)
                    qb = qf[ragged_desc["qidx"]]     # (R, Q, KVH, g, hd)
                    ob = ops.ragged_paged_attention(
                        qb, ck, cks, cv, cvs, layer_idx, ragged_desc["table"],
                        ragged_desc["lengths"].astype(jnp.int32),
                        ragged_desc["qpos"].astype(jnp.int32))
                    o = ob[ragged_desc["inv_seq"], ragged_desc["inv_qi"]]
                    o = o.reshape(b, 1, -1)
                elif use_kernel:
                    # decode fast path: stream int8 pages, dequant in
                    # VMEM (rtol-level vs the gathered view, not bitwise)
                    from repro.kernels import ops
                    kvh = ck.shape[3] // cfg.head_dim
                    qk = q.reshape(b, kvh, q.shape[2] // kvh, cfg.head_dim)
                    lengths = (pos if getattr(pos, "ndim", 0)
                               else jnp.broadcast_to(pos, (b,))) + 1
                    o = ops.paged_attention(qk, ck, cks, cv, cvs, layer_idx,
                                            page_table,
                                            lengths.astype(jnp.int32))
                    o = o.reshape(b, 1, -1)
                else:
                    at = (page_table, layer_idx)
                    page = ck.shape[2]
                    k_att = (gather_pages(ck, *at, cfg.head_dim),
                             gather_scales(cks, *at, page))
                    v_att = (gather_pages(cv, *at, cfg.head_dim),
                             gather_scales(cvs, *at, page))
            else:
                ck, cv = paged_cache_update(cache["k"], cache["v"], k, v,
                                            page_table, pos, layer_idx)
                new_cache = {"k": ck, "v": cv}
                at = (page_table, layer_idx, cfg.head_dim)
                k_att = gather_pages(ck, *at).astype(cd)
                v_att = gather_pages(cv, *at).astype(cd)
        elif cache is not None:
            # contiguous slot cache: update this layer's slice, put it back
            sl = {n: a[layer_idx] for n, a in cache.items()}
            if quant_cache:
                from repro.models.layers import cache_update_quantized
                ck, cks, cv, cvs = cache_update_quantized(
                    sl["k"], sl["k_scale"], sl["v"], sl["v_scale"], k, v,
                    pos, cfg.kv_quant_bits)
                sl = {"k": ck, "k_scale": cks, "v": cv, "v_scale": cvs}
                k_att, v_att = (ck, cks), (cv, cvs)
            else:
                ck, cv = cache_update(sl["k"], sl["v"], k, v, pos)
                sl = {"k": ck, "v": cv}
                k_att, v_att = ck.astype(cd), cv.astype(cd)
            new_cache = {n: cache[n].at[layer_idx].set(a)
                         for n, a in sl.items()}
        else:
            k_att, v_att = k, v

    if o is None:
        o = chunked_attention(q, k_att, v_att, q_positions=positions,
                              causal=True, window=window,
                              attn_softcap=cfg.attn_softcap)
        o = o.reshape(b, s, -1)
    _tap(taps, layer_idx, "o_in", o)
    attn_out = row_dense(lp["wo"], o)
    if cfg.post_norms:
        attn_out = rms_norm(attn_out, lp["ln1_post"])
    x = x + attn_out

    h2 = rms_norm(x, lp["ln2"])
    _tap(taps, layer_idx, "mlp_in", h2)
    if cfg.n_experts:
        mlp_out, aux = moe_lib.moe_mlp(cfg, lp, h2, taps=taps,
                                       layer_idx=layer_idx)
    else:
        from repro.models.layers import activation
        act = activation(cfg.act)
        if "wgu" in lp:
            # fused serving params: concatenated gate|up projection
            gu = qlinear.dense(lp["wgu"], h2)
            f = gu.shape[-1] // 2
            hmid = act(gu[..., :f]) * gu[..., f:]
        elif cfg.gated_mlp:
            hmid = act(qlinear.dense(lp["wg"], h2)) * qlinear.dense(lp["wu"], h2)
        else:
            hmid = act(qlinear.dense(lp["wu"], h2))
        _tap(taps, layer_idx, "down_in", hmid)
        mlp_out = row_dense(lp["wd"], hmid)
        aux = jnp.zeros((), jnp.float32)
    if cfg.post_norms:
        mlp_out = rms_norm(mlp_out, lp["ln2_post"])
    x = x + mlp_out
    if cfg.act_shard == "seq":
        from repro.distributed.act_sharding import constrain_seq
        x = constrain_seq(x)
    return x, new_cache, aux


def _tap(taps, layer_idx, name, x):
    if taps is not None and layer_idx is not None:
        taps.record(f"layers.{layer_idx}.{name}", x)


def forward(cfg, params, tokens, *, extra_embed=None, cache=None,
            taps=None, unroll: bool = False, tp_axis=None,
            tp_mode: str = "gather", tp_kernels: bool = False,
            paged_kernel: bool = False, ragged_desc=None):
    """-> (hidden (B, S, D), aux_loss, new_cache). ``tokens`` (B, S) int32;
    ``extra_embed`` (B, P, D) is prepended (vlm prefix); with ``cache`` the
    attention runs against the cache and writes k/v at cache['pos'].

    A cache carrying a ``page_table`` leaf is *paged*: its leaves are
    the page pools of ``init_paged_cache``, shared across slots, and
    the table ((B, n_ptab) int32) maps each row's logical positions to
    physical pages (see ``init_paged_cache`` / ``models.layers``).
    ``paged_kernel`` opts decode steps into the Pallas paged-attention
    kernel (quantized pools only; rtol-level numerics). A *ragged*
    (unified-step) batch — flat packed rows, per-token (B,) ``pos`` and
    (B, n_ptab) table rows, see ``ragged_step`` — may also pass
    ``ragged_desc`` (per-work-item query-block descriptors) so the
    kernel streams each sequence's pages once for all its queries.

    ``tp_axis`` names a mesh axis when the forward runs inside shard_map
    with params sharded per ``distributed.sharding.tp_param_specs`` (same
    ``tp_mode``); the embedding, residual stream, norms, and logits stay
    replicated, so the output is bitwise identical to the single-device
    forward in ``tp_mode="gather"`` and rtol-level in ``"psum"`` (see
    ``_layer_body``)."""
    if tp_axis is not None and cfg.n_experts:
        raise NotImplementedError("tensor-parallel forward covers the "
                                  "dense (non-MoE) family only")
    cd = _compute_dtype(cfg)
    x = params["embed"][tokens].astype(cd) * jnp.sqrt(float(cfg.d_model)
                                                      ).astype(cd)
    if extra_embed is not None:
        x = jnp.concatenate([extra_embed.astype(cd), x], axis=1)
    b, s, _ = x.shape
    pos = cache["pos"] if cache is not None else jnp.int32(0)
    steps = jnp.arange(s, dtype=jnp.int32)
    # pos is a scalar (static batching: whole batch at one offset) or a
    # (B,) vector of per-slot offsets (the serve engine's continuous
    # batching) — positions then (S,) or (B, S); rope/attention take both.
    positions = pos[:, None] + steps if getattr(pos, "ndim", 0) else pos + steps
    flags = is_global_flags(cfg)

    cache_layers = None
    page_table = None
    if cache is not None:
        page_table = cache.get("page_table")
        cache_layers = {k: v for k, v in cache.items()
                        if k not in ("pos", "page_table")}

    # The cache rides the layer loop whole, as one buffer per leaf: each
    # layer reads and writes its own layer of it in place (the paged
    # kernels take the stacked pools and the layer index), so no layer's
    # pool is sliced out, relaid or stacked back.
    body_kw = dict(tp_axis=tp_axis, tp_mode=tp_mode, tp_kernels=tp_kernels,
                   page_table=page_table, paged_kernel=paged_kernel,
                   ragged_desc=ragged_desc)
    aux = jnp.zeros((), jnp.float32)
    if unroll:
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, cache_layers, a = _layer_body(cfg, x, lp, cache_layers,
                                             flags[i], pos, positions,
                                             taps=taps, layer_idx=i,
                                             **body_kw)
            aux = aux + a
    else:
        def body(carry, xs):
            x, aux, cache_layers = carry
            lp, fl, i = xs
            x, cache_layers, a = _layer_body(cfg, x, lp, cache_layers, fl,
                                             pos, positions, layer_idx=i,
                                             **body_kw)
            return (x, aux + a, cache_layers), None

        if cfg.remat:
            body = jax.checkpoint(body)
        from repro.models.flags import scan as _scan
        xs = (params["layers"], flags,
              jnp.arange(cfg.n_layers, dtype=jnp.int32))
        (x, aux, cache_layers), _ = _scan(body, (x, aux, cache_layers), xs)
    new_cache = None
    if cache is not None:
        new_cache = dict(cache_layers, pos=pos + s)
        if page_table is not None:
            new_cache["page_table"] = page_table

    x = rms_norm(x, params["final_norm"])
    return x, aux, new_cache


def logits_fn(cfg, params, hidden):
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = hidden @ unembed.astype(hidden.dtype)
    return softcap(logits, cfg.logit_softcap)


def loss(cfg, params, batch, *, loss_chunk: int = 512):
    """Chunked CE over the sequence (never materializes (B, S, V) logits)."""
    from repro.models.losses import chunked_ce
    extra = batch.get("patch_embed") if cfg.n_patches else None
    hidden, aux, _ = forward(cfg, params, batch["tokens"], extra_embed=extra)
    if extra is not None:
        hidden = hidden[:, extra.shape[1]:]
    return chunked_ce(lambda h: logits_fn(cfg, params, h), hidden,
                      batch["labels"], aux, loss_chunk=loss_chunk)


# ----------------------------------------------------------------- serving

def make_serving_params(cfg, params, keep_packed=None) -> dict:
    """The fused-serving variant of a params pytree (single-device engine
    hot path; ``ServeEngine(fused=True)`` applies it at build time):

    * wq|wk|wv -> one ``wqkv`` and wg|wu -> one ``wgu`` column-concat
      (exact: the pipeline quantizes group members against ONE shared
      input transform, so the concat collapses three transform + quant +
      matmul chains — which XLA cannot CSE across distinct stacked
      params — into one; fp params concat too, so the comparison stays
      like-for-like).
    * every QLinear gains the precomputed ``colsum`` for the
      integer-accumulation epilogue (``qlinear.dense_fused``) and, off
      TPU, the dequantized compute-dtype weight ``w_eff`` so the per-step
      unpack + dequant chain moves to build time
      (see ``qlinear.make_serving``).

    Tensor-parallel serving keeps the original per-member params — the
    concatenated output dim would split unevenly across head shards.
    Decoded tokens are bitwise identical to the unfused params (golden
    fixtures run both)."""
    from repro.core.qlinear import QLinear, concat_out, make_serving

    cd = _compute_dtype(cfg)
    layers = dict(params["layers"])

    def try_concat(names, out_name):
        if not all(n in layers for n in names):
            return
        cat = concat_out([layers[n] for n in names], keep_packed, cd)
        if cat is None:
            return
        for n in names:
            del layers[n]
        layers[out_name] = cat

    try_concat(("wq", "wk", "wv"), "wqkv")
    if cfg.gated_mlp and not cfg.n_experts:
        try_concat(("wg", "wu"), "wgu")

    def prep(leaf):
        if isinstance(leaf, QLinear) and leaf.colsum is None:
            return make_serving(leaf, keep_packed, cd)
        return leaf

    layers = jax.tree.map(prep, layers,
                          is_leaf=lambda x: isinstance(x, QLinear))
    return dict(params, layers=layers)


# ------------------------------------------------------------------ caches

def init_cache(cfg, batch_size: int, max_len: int) -> dict:
    shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, cfg.head_dim)
    cd = _compute_dtype(cfg)
    if cfg.kv_quant_bits:
        sshape = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(sshape, jnp.float32),
                "pos": jnp.int32(0)}
    return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd),
            "pos": jnp.int32(0)}


def init_paged_cache(cfg, n_pages: int, page_size: int) -> dict:
    """Global paged KV pool, stored in the layout the Pallas kernels read
    (``kernels/paged_attention.py``): (L, n_pages, page_size, KV·hd)
    codes, a row's heads side by side on lanes, and when quantized (L,
    n_pages, KV, scale_lanes(page_size)) f32 per-(token, head) scales,
    row r of a page on lane r. No ``pos`` — page tables and per-slot
    lengths are the caller's (engine's) bookkeeping; page 0 is
    conventionally the never-owned null page (see
    ``repro.launch.paged.PagePool``)."""
    from repro.kernels.paged_attention import scale_lanes

    shape = (cfg.n_layers, n_pages, page_size, cfg.kv_dim)
    cd = _compute_dtype(cfg)
    if cfg.kv_quant_bits:
        sshape = (cfg.n_layers, n_pages, cfg.n_kv_heads,
                  scale_lanes(page_size))
        return {"k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd)}


def copy_paged_pages(cfg, cache, src, dst) -> dict:
    """Copy-on-write device copy for the serve stack: duplicate pool
    pages ``src`` into ``dst`` on every paged-cache leaf (codes AND
    scales — a cached quantized page is only bitwise-reusable with its
    per-token scales moved in lockstep). ``src``/``dst`` (C,) int32,
    padded with (0, 0) null-page self-copies (inert)."""
    from repro.models.layers import copy_pool_pages
    return {k: copy_pool_pages(v, src, dst) for k, v in cache.items()}


def prefill(cfg, params, tokens, cache, extra_embed=None, logits_at=None,
            **fwd_kw):
    """Prefill logits come from the last row by default; ``logits_at``
    (traced scalar) instead slices the row at that index — the hook that
    lets chunked/bucketed prefill pad tokens on the right and still read
    logits at the true last prompt token. A (R,) *vector* ``logits_at``
    gathers R rows instead (logits (B, R, V)) — the multi-row read
    speculative verification needs when checking k+1 positions of one
    forward at once (``launch.scheduler``/``ragged_step`` use the packed
    equivalent)."""
    hidden, _, cache = forward(cfg, params, tokens, extra_embed=extra_embed,
                               cache=cache, **fwd_kw)
    if logits_at is None:
        hidden = hidden[:, -1:]
    elif getattr(logits_at, "ndim", 0):
        hidden = jnp.take(hidden, logits_at, axis=1)
    else:
        hidden = jax.lax.dynamic_slice_in_dim(hidden, logits_at, 1, axis=1)
    return logits_fn(cfg, params, hidden), cache


def decode(cfg, params, token, cache, **fwd_kw):
    """token (B, 1) -> (logits (B, 1, V), cache)."""
    hidden, _, cache = forward(cfg, params, token, cache=cache, **fwd_kw)
    return logits_fn(cfg, params, hidden), cache


def ragged_step(cfg, params, tokens, cache, logit_rows, greedy=False,
                **fwd_kw):
    """Unified token-budget step: ONE forward over a flat ragged batch of
    mixed prefill-chunk and decode rows (``repro.launch.scheduler``).

    ``tokens`` (T, 1) packed rows — each row is one token of some
    sequence; ``cache`` holds the paged pools plus per-token ``pos``
    (T,) absolute positions and ``page_table`` (T, n_ptab) — every row
    carries its own slot's table row, so the existing paged scatter
    writes each token's k/v to its sequence's pages and the gathered
    logical view gives each query row exactly its own sequence's KV
    (padding rows ride the null table row -> inert writes, discarded
    reads). Intra-chunk causality needs no special casing: all packed
    rows write k/v before attention, and the causal ``q_pos >= kv_pos``
    test masks same-chunk future tokens — per-row numerics are bitwise
    identical to ``prefill``/``decode`` over a contiguous cache.

    ``logit_rows`` (R,) generalizes prefill's ``logits_at`` to the
    ragged batch: logits are computed only at those packed rows (the
    scheduler marks each decode row and each prompt-completing chunk's
    last row; padding entries are discarded by the caller) — the unembed
    cost scales with sequences, not packed tokens.

    ``greedy=True`` is device-resident sampling for the pipelined serve
    loop: instead of (R, 1, V) logits, return the greedy next token at
    each logit row as (R,) int32 — only R token ids ever cross D2H, and
    the argmax (lowest index on ties, matching ``np.argmax``) runs
    inside the same jitted program as the forward.
    -> (logits (R, 1, V), cache), or (tokens (R,), cache) when greedy."""
    hidden, _, cache = forward(cfg, params, tokens, cache=cache, **fwd_kw)
    sel = jnp.take(hidden[:, 0], logit_rows, axis=0)[:, None]
    logits = logits_fn(cfg, params, sel)
    if greedy:
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache
    return logits, cache
