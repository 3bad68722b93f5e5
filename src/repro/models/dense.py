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

def _fused_decode_operands(cfg, lp, cache_sl, s, b, tp_axis, ragged_desc,
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
            and cache_sl is not None and "k_scale" in cache_sl
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


def _fused_decode_attn(cfg, fd, h, cache_sl, page_table, pos, b):
    """The two-launch decode attention block: ONE prologue launch (CAT ->
    quant -> W4A8 QKV GEMV -> RoPE -> int8 KV quant -> paged scatter)
    feeding ONE paged-attention launch. Returns (o (B, 1, Hq·hd),
    new_cache_sl)."""
    from repro.kernels import ops
    from repro.models.layers import _paged_indices

    p, (blocks, ha, hb, sign) = fd
    cd = h.dtype
    page_size = cache_sl["k"].shape[1]
    pos_vec = (pos if getattr(pos, "ndim", 0)
               else jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)))
    pids, rows = _paged_indices(page_table, pos_vec, b, 1, page_size)
    q, ck, cks, cv, cvs = ops.decode_qkv_prologue(
        h.reshape(b, -1), blocks, ha, hb, sign, p.qweight, p.scale,
        cache_sl["k"], cache_sl["k_scale"], cache_sl["v"],
        cache_sl["v_scale"], pids, rows, pos_vec,
        n_q=cfg.q_dim, head_dim=cfg.head_dim,
        rope_theta=float(cfg.rope_theta), kv_bits=cfg.kv_quant_bits,
        act_bits=p.act_bits, packed=p.packed)
    new_cache_sl = {"k": ck, "k_scale": cks, "v": cv, "v_scale": cvs}
    kvh = ck.shape[2]
    g = cfg.q_dim // cfg.head_dim // kvh
    qk = q.astype(cd).reshape(b, kvh, g, cfg.head_dim)
    lengths = pos_vec + 1
    o = ops.paged_attention(qk, ck, cks, cv, cvs, page_table,
                            lengths.astype(jnp.int32))
    return o.reshape(b, 1, -1).astype(cd), new_cache_sl


def _layer_body(cfg, x, lp, cache_sl, is_global, pos, positions,
                taps=None, layer_idx=None, tp_axis=None,
                tp_mode: str = "gather", tp_kernels=False,
                page_table=None, paged_kernel: bool = False,
                ragged_desc=None):
    """cache_sl: per-layer cache slices dict ({"k","v"[,"k_scale","v_scale"]})
    or None. Returns (x, new_cache_sl, aux).

    With ``page_table`` (B, n_ptab) the cache slices are *page pools*
    ((n_pages, page_size, KV, hd) per layer, plus congruent per-token
    scale pools when quantized): k/v writes scatter to the physical rows
    the table maps [pos, pos+S) to, and attention reads the gathered
    logical view — identical content and shape to the contiguous slot
    cache, so decoded tokens stay bitwise the same. ``paged_kernel``
    additionally routes single-token (decode) attention on quantized
    pools through the Pallas paged-attention kernel (streams int8 pages,
    dequantizes in VMEM — rtol-level, not bitwise).

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
    fd = _fused_decode_operands(cfg, lp, cache_sl, s, b, tp_axis,
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
        o, new_cache_sl = _fused_decode_attn(cfg, fd, h, cache_sl,
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

        quant_cache = bool(cfg.kv_quant_bits) and cache_sl is not None \
            and "k_scale" in cache_sl
        if cfg.kv_quant_bits and not quant_cache:
            # no cache (training fwd): simulate KV quantization numerics
            from repro.core.quantizers import QuantSpec, fake_quant
            kv_spec = QuantSpec(bits=cfg.kv_quant_bits, symmetric=False,
                                per="token", dynamic=True)
            k = fake_quant(k, kv_spec)
            v = fake_quant(v, kv_spec)

        new_cache_sl = None
        o = None
        if cache_sl is not None and page_table is not None:
            from repro.models.layers import (gather_pages,
                                             paged_cache_update,
                                             paged_cache_update_quantized)
            if quant_cache:
                ck, cks, cv, cvs = paged_cache_update_quantized(
                    cache_sl["k"], cache_sl["k_scale"], cache_sl["v"],
                    cache_sl["v_scale"], k, v, page_table, pos,
                    cfg.kv_quant_bits)
                new_cache_sl = {"k": ck, "k_scale": cks, "v": cv,
                                "v_scale": cvs}
                use_kernel = (paged_kernel and s == 1 and window is None
                              and not cfg.attn_softcap)
                if use_kernel and ragged_desc is not None:
                    # unified ragged step: regroup the flat packed rows
                    # into per-work-item query blocks so every sequence's
                    # pages stream ONCE for all its prefill-chunk +
                    # decode queries (one launch for the mixed batch)
                    from repro.kernels import ops
                    kvh = ck.shape[2]
                    qf = q.reshape(b, kvh, q.shape[2] // kvh, cfg.head_dim)
                    qb = qf[ragged_desc["qidx"]]     # (R, Q, KVH, g, hd)
                    ob = ops.ragged_paged_attention(
                        qb, ck, cks, cv, cvs, ragged_desc["table"],
                        ragged_desc["lengths"].astype(jnp.int32),
                        ragged_desc["qpos"].astype(jnp.int32))
                    o = ob[ragged_desc["inv_seq"], ragged_desc["inv_qi"]]
                    o = o.reshape(b, 1, -1)
                elif use_kernel:
                    # decode fast path: stream int8 pages, dequant in
                    # VMEM (rtol-level vs the gathered view, not bitwise)
                    from repro.kernels import ops
                    kvh = ck.shape[2]
                    qk = q.reshape(b, kvh, q.shape[2] // kvh, cfg.head_dim)
                    lengths = (pos if getattr(pos, "ndim", 0)
                               else jnp.broadcast_to(pos, (b,))) + 1
                    o = ops.paged_attention(qk, ck, cks, cv, cvs,
                                            page_table,
                                            lengths.astype(jnp.int32))
                    o = o.reshape(b, 1, -1)
                else:
                    k_att = (gather_pages(ck, page_table),
                             gather_pages(cks, page_table))
                    v_att = (gather_pages(cv, page_table),
                             gather_pages(cvs, page_table))
            else:
                ck, cv = paged_cache_update(cache_sl["k"], cache_sl["v"],
                                            k, v, page_table, pos)
                new_cache_sl = {"k": ck, "v": cv}
                k_att = gather_pages(ck, page_table).astype(cd)
                v_att = gather_pages(cv, page_table).astype(cd)
        elif cache_sl is not None and quant_cache:
            from repro.models.layers import cache_update_quantized
            ck, cks, cv, cvs = cache_update_quantized(
                cache_sl["k"], cache_sl["k_scale"], cache_sl["v"],
                cache_sl["v_scale"], k, v, pos, cfg.kv_quant_bits)
            new_cache_sl = {"k": ck, "k_scale": cks, "v": cv,
                            "v_scale": cvs}
            k_att, v_att = (ck, cks), (cv, cvs)
        elif cache_sl is not None:
            ck, cv = cache_update(cache_sl["k"], cache_sl["v"], k, v, pos)
            new_cache_sl = {"k": ck, "v": cv}
            k_att, v_att = ck.astype(cd), cv.astype(cd)
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
    return x, new_cache_sl, aux


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

    A cache carrying a ``page_table`` leaf is *paged*: its k/v leaves are
    page pools (L, n_pages, page_size, KV, hd) shared across slots, and
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

    aux0 = jnp.zeros((), jnp.float32)
    if unroll:
        new_sl = []
        aux = aux0
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            csl = (jax.tree.map(lambda a: a[i], cache_layers)
                   if cache_layers is not None else None)
            x, csl, a = _layer_body(cfg, x, lp, csl, flags[i], pos,
                                    positions, taps=taps, layer_idx=i,
                                    tp_axis=tp_axis, tp_mode=tp_mode,
                                    tp_kernels=tp_kernels,
                                    page_table=page_table,
                                    paged_kernel=paged_kernel,
                                    ragged_desc=ragged_desc)
            aux = aux + a
            if csl is not None:
                new_sl.append(csl)
        new_cache = None
        if cache is not None:
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *new_sl)
            new_cache = dict(stacked, pos=pos + s)
    else:
        def body(carry, xs):
            x, aux = carry
            if cache_layers is not None:
                lp, csl, fl = xs
            else:
                (lp, fl), csl = xs, None
            x, csl, a = _layer_body(cfg, x, lp, csl, fl, pos, positions,
                                    tp_axis=tp_axis, tp_mode=tp_mode,
                                    tp_kernels=tp_kernels,
                                    page_table=page_table,
                                    paged_kernel=paged_kernel,
                                    ragged_desc=ragged_desc)
            return (x, aux + a), csl

        if cfg.remat:
            body = jax.checkpoint(body)
        if cache_layers is not None:
            xs = (params["layers"], cache_layers, flags)
        else:
            xs = (params["layers"], flags)
        from repro.models.flags import scan as _scan
        (x, aux), ys = _scan(body, (x, aux0), xs)
        new_cache = None
        if cache is not None:
            new_cache = dict(ys, pos=pos + s)
    if new_cache is not None and page_table is not None:
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
    """Global paged KV pool: (L, n_pages, page_size, KV, hd) codes (+
    congruent per-token scale pools when quantized). No ``pos`` — page
    tables and per-slot lengths are the caller's (engine's) bookkeeping;
    page 0 is conventionally the never-owned null page (see
    ``repro.launch.paged.PagePool``)."""
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    cd = _compute_dtype(cfg)
    if cfg.kv_quant_bits:
        sshape = shape[:-1] + (1,)
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
