"""Shared model layers: norms, RoPE, chunked (flash-style) attention, MLP.

Attention uses an online-softmax scan over KV chunks so the score matrix
is never materialized (O(S·chunk) working set instead of O(S²)) — required
for the 32k prefill cells to fit HBM, and the natural TPU formulation
(each chunk is an MXU matmul; the running max/sum rescale is VPU work).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import qlinear

_NEG_INF = -1e30


# ------------------------------------------------------------------- norms

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def group_rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    """Per-head RMS norm: x (..., H, hd), scale (H*hd,) reshaped."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    out = out * (1.0 + scale.astype(jnp.float32).reshape(x.shape[-2], x.shape[-1]))
    return out.astype(dt)


def softcap(x: jnp.ndarray, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


# -------------------------------------------------------------------- RoPE

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (B, S, H, hd), positions: (B, S) or (S,) absolute positions."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions.astype(jnp.float32)[:, :, None] * freq[None, None, :]
    cos = jnp.cos(ang)[:, :, None, :]  # (B, S, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------- chunked flash-style attention

def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      q_positions: jnp.ndarray,
                      causal: bool = True,
                      window: Optional[int] = None,
                      attn_softcap: float = 0.0,
                      kv_chunk: int = 1024) -> jnp.ndarray:
    """Online-softmax attention.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with KV | H (GQA) — or
    (codes int8, scale) tuples for int8 KV caches (dequantized per chunk
    inside the scan, so the bf16 cache is never materialized).
    q_positions: (B, Sq) absolute positions (decode passes the cache pos);
    KV positions are arange(Skv). Causal mask: q_pos >= kv_pos — this also
    masks unwritten cache slots (their positions exceed every query).
    """
    k_q = isinstance(k, tuple)
    v_q = isinstance(v, tuple)
    k_arr = k[0] if k_q else k
    v_arr = v[0] if v_q else v
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k_arr.shape
    g = h // kvh
    scale = hd ** -0.5
    # §Perf A3: keep q/k/v in compute dtype on the wire (SP/TP gathers at
    # bf16 bytes); score/PV einsums accumulate in f32 on the MXU via
    # preferred_element_type — flash-attention-standard numerics.
    cd = q.dtype
    qf = (q * jnp.asarray(scale, cd)).reshape(b, sq, kvh, g, hd)

    from repro.models.flags import exact_cost
    c = skv if exact_cost() else min(kv_chunk, skv)
    pad = (-skv) % c

    def prep(t):
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        nc = t.shape[1] // c
        return t.reshape(b, nc, c, t.shape[2], t.shape[3]
                         ).transpose(1, 0, 2, 3, 4)

    kc = jax.tree.map(prep, k)
    vc = jax.tree.map(prep, v)
    nc = (skv + pad) // c
    kv_pos = jnp.arange(nc * c, dtype=jnp.int32).reshape(nc, c)
    qp = q_positions if q_positions.ndim == 2 else q_positions[None, :]

    def _deq(t, quantized):
        if quantized:
            codes, sc = t
            return codes.astype(cd) * sc.astype(cd)
        return t.astype(cd)

    def step(carry, xs):
        m, l, acc = carry
        kj, vj, pj = xs
        kj = _deq(kj, k_q)
        vj = _deq(vj, v_q)
        s = jnp.einsum("bqkgd,bckd->bkgqc", qf, kj,
                       preferred_element_type=jnp.float32)
        s = softcap(s, attn_softcap)
        mask = jnp.ones((b, sq, c), dtype=bool)
        if causal:
            mask &= qp[:, :, None] >= pj[None, None, :]
        if window is not None:
            mask &= (qp[:, :, None] - pj[None, None, :]) < window
        if pad:
            mask &= (pj < skv)[None, None, :]
        s = jnp.where(mask[:, None, None, :, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(cd), vj,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((b, kvh, g, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, sq, hd), jnp.float32)
    from repro.models.flags import scan as _scan
    (m, l, acc), _ = _scan(step, (m0, l0, a0), (kc, vc, kv_pos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


# --------------------------------------------------------------------- MLP

def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def glu_mlp(p, x, act: str):
    """Gated MLP: down( act(gate(x)) * up(x) ). p: dict wg/wu/wd."""
    h = activation(act)(qlinear.dense(p["wg"], x)) * qlinear.dense(p["wu"], x)
    return qlinear.dense(p["wd"], h)


# ---------------------------------------------------------------- KV cache

def _write_kv(cache, x, pos):
    """Write x (B, S, ...) into cache (B, Smax, ...) at rows [pos, pos+S).

    ``pos`` is either a scalar (whole batch at the same offset — static
    batching) or an (B,) int32 vector of per-slot offsets (continuous
    batching: every slot sits at its own sequence position)."""
    x = x.astype(cache.dtype)
    if getattr(pos, "ndim", 0) == 0:
        start = (0, pos) + (0,) * (cache.ndim - 2)
        return jax.lax.dynamic_update_slice(cache, x, start)

    def one(c, u, p):
        return jax.lax.dynamic_update_slice(c, u, (p,) + (0,) * (c.ndim - 1))
    return jax.vmap(one)(cache, x, pos)


def cache_update(cache_k, cache_v, k, v, pos):
    """Write k, v (B, S, KV, hd) into caches at [pos, pos+S); ``pos``
    scalar or (B,) per-slot offsets (see ``_write_kv``)."""
    return _write_kv(cache_k, k, pos), _write_kv(cache_v, v, pos)


def quantize_kv(x: jnp.ndarray, bits: int):
    """Symmetric per-(token, head) int8-storage quantization of K/V.
    x (B, S, KV, hd) -> (codes int8, scale f32 (B, S, KV, 1))."""
    qmax = 2.0 ** (bits - 1) - 1
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    codes = jnp.clip(jnp.round(xf / scale), -qmax - 1, qmax).astype(jnp.int8)
    return codes, scale


def cache_update_quantized(ck, cks, cv, cvs, k, v, pos, bits: int):
    """int8 KV-cache write: codes + per-token scales at [pos, pos+S);
    ``pos`` scalar or (B,) per-slot offsets (see ``_write_kv``)."""
    kq, ks = quantize_kv(k, bits)
    vq, vs = quantize_kv(v, bits)
    return (_write_kv(ck, kq, pos), _write_kv(cks, ks, pos),
            _write_kv(cv, vq, pos), _write_kv(cvs, vs, pos))


# ----------------------------------------------------------- paged KV cache

# Sequence-axis granularity of the per-token KV quant scales. The serve
# CLI validates page_size % KV_QUANT_GROUP == 0 so a page never splits a
# scale group (today scales are per-token, so the group is 1; a grouped-
# scale quantizer must bump this in lockstep).
KV_QUANT_GROUP = 1


def _paged_indices(page_table, pos, b, s, page_size):
    """Physical (page, row) targets for writing (B, S) tokens starting at
    ``pos`` (scalar or (B,)) into a paged pool.

    Logical position p lives at row ``p % page_size`` of physical page
    ``page_table[b, p // page_size]``. Positions past the table (padded
    prefill chunks / bucket rows) and table entries that are 0 both land
    on the reserved null page 0 — never owned by a request, so the write
    is inert (and the garbage rows are causally masked on read anyway).
    Returns flat ((B*S,) page ids, (B*S,) rows)."""
    if getattr(pos, "ndim", 0) == 0:
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    logical = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # (B,S)
    n_ptab = page_table.shape[1]
    pidx = logical // page_size
    valid = pidx < n_ptab
    pids = jnp.take_along_axis(page_table, jnp.minimum(pidx, n_ptab - 1),
                               axis=1)
    pids = jnp.where(valid, pids, 0)
    rows = logical % page_size
    return pids.reshape(-1), rows.reshape(-1)


def _put_code_rows(pool, vals, layer, pids, rows):
    """Write (..., KV, d) rows, one per flat (N,) (page, row) target,
    into layer ``layer`` of a stacked code (or fp) pool leaf (L,
    n_pages, G, KV·d); targets past the pool drop."""
    vals = vals.reshape(pids.shape[0], -1).astype(pool.dtype)
    return pool.at[layer, pids, rows].set(vals, mode="drop")


def _put_scale_rows(pool, vals, layer, pids, rows):
    """Write (..., KV, 1) per-row scales, one per flat (N,) (page, row)
    target, into layer ``layer`` of a stacked scale leaf (L, n_pages, KV,
    lanes), row r of a page on lane r; targets past the pool drop."""
    vals = vals.reshape(pids.shape[0], -1).astype(pool.dtype)
    return pool.at[layer, pids, :, rows].set(vals, mode="drop")


def _page_targets(page_table, pos, x, pool):
    """Flat (page, row) targets of x's (B, S) tokens in a code leaf."""
    return _paged_indices(page_table, pos, x.shape[0], x.shape[1],
                          pool.shape[2])


def paged_cache_update(ck, cv, k, v, page_table, pos, layer):
    """fp paged write: k, v (B, S, KV, hd) into layer ``layer`` of the
    stacked (L, n_pages, G, KV·hd) pools at the rows the page table
    maps [pos, pos+S) to.

    Distinct slots own distinct pages, so real writes never collide; the
    only duplicate targets are inert null-page rows (see _paged_indices).
    """
    at = (layer, *_page_targets(page_table, pos, k, ck))
    return _put_code_rows(ck, k, *at), _put_code_rows(cv, v, *at)


def paged_cache_update_quantized(ck, cks, cv, cvs, k, v, page_table, pos,
                                 layer, bits: int):
    """int8 paged write: same quantizer as the contiguous cache
    (``quantize_kv``), codes + per-token scales scattered page-wise into
    layer ``layer`` of the stacked pools — the stored values are bitwise
    identical to the slot cache's."""
    kq, ks = quantize_kv(k, bits)
    vq, vs = quantize_kv(v, bits)
    at = (layer, *_page_targets(page_table, pos, k, ck))
    return (_put_code_rows(ck, kq, *at), _put_scale_rows(cks, ks, *at),
            _put_code_rows(cv, vq, *at), _put_scale_rows(cvs, vs, *at))


def copy_pool_pages(pool, src, dst):
    """Copy whole pages ``src`` -> ``dst`` along a pool leaf's page axis
    (axis 1 of every leaf: codes (L, n_pages, G, KV·hd) and scales (L,
    n_pages, KV, lanes) alike). The prefix cache's copy-on-write split:
    duplicate a shared page's rows into a private replacement before the
    new owner writes its divergent rows. ``src``/``dst`` are (C,) int32;
    padding pairs are (0, 0) — a null-page self-copy is a no-op write —
    so the copy keeps one compile shape."""
    return pool.at[:, dst].set(pool[:, src])


def gather_pages(pool, page_table, layer, head_dim: int):
    """Layer ``layer`` of a stacked code (or fp) pool leaf (L, n_pages,
    G, KV·hd) + (B, n_ptab) table -> the logical (B, n_ptab*G, KV, hd)
    view — identical (content and shape) to the contiguous slot cache
    over written rows, so downstream attention is bitwise the same;
    unwritten/null rows are finite garbage masked by the causal test."""
    view = pool[layer, page_table]
    return view.reshape(page_table.shape[0], -1, view.shape[-1] // head_dim,
                        head_dim)


def gather_scales(pool, page_table, layer, page_size: int):
    """Layer ``layer`` of a stacked scale leaf (L, n_pages, KV, lanes) +
    (B, n_ptab) table -> the logical (B, n_ptab*G, KV, 1) per-row scales,
    the contiguous slot cache's scale view (see ``gather_pages``)."""
    view = jnp.swapaxes(pool[layer, page_table][..., :page_size], 2, 3)
    return view.reshape(page_table.shape[0], -1, view.shape[-1], 1)
