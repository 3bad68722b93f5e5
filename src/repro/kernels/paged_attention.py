"""Pallas TPU kernel: paged decode attention over a quantized KV pool.

The serve engine's paged KV cache stores int8 codes + per-(token, head)
f32 scales in fixed-size *pages* of a global pool (``repro.launch.paged``);
a request's logical sequence is the concatenation of the pages its page
table names. Decode attention is then a gather problem: for slot ``b``,
stream pages ``page_table[b, i]`` from HBM, dequantize in VMEM, and fold
each page into an online-softmax accumulator — the bf16 logical cache is
never materialized and the int8 pages are the only HBM stream (half the
bytes of an fp16 cache per decoded token, the memory-bound regime where
KV quantization pays).

The page table and per-slot lengths ride in as **scalar-prefetch**
operands (``pltpu.PrefetchScalarGridSpec``): they are resident before the
kernel body runs, so the k/v BlockSpec index maps can address the
*physical* page ``pt[b, i]`` while the grid walks *logical* page slots
``(b, i)`` — the indirection is free, folded into the DMA descriptor.

Grid: ``(B, n_ptab)`` with the page axis innermost; VMEM scratch carries
the flash-attention running (m, l, acc) across a slot's pages (init at
``i == 0``, final ``acc / l`` write-out at the last page). Ragged last
pages and dummy table entries (null page 0) are handled by the
``kv_pos < length[b]`` mask — garbage rows get ``exp(-1e30 - m) == 0``
weight exactly.

``repro.kernels.ref.paged_attention_decode`` is the jnp oracle;
``paged_attention_fallback`` is a gather-based jnp path for fp pools and
backends without Pallas.

``paged_attention_ragged`` generalizes the q_len=1 decode kernel to a
*block of queries per sequence* with a per-(query, kv) causal mask — the
shape of a unified token-budget step, where one launch covers every
prefill chunk and decode token packed into the step and each sequence's
pages stream exactly once (see ``repro.launch.scheduler``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _paged_attn_kernel(len_ref, pt_ref, q_ref, k_ref, ks_ref, v_ref,
                       vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                       page_size: int):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (KVH, g, hd), pre-scaled
    k = k_ref[0].astype(jnp.float32) * ks_ref[0]  # (G, KVH, hd) dequant
    v = v_ref[0].astype(jnp.float32) * vs_ref[0]

    # scores for this page: (KVH, g, G)
    s = jnp.einsum("kgd,Gkd->kgG", q, k,
                   preferred_element_type=jnp.float32)
    kv_pos = i * page_size + jax.lax.iota(jnp.int32, page_size)
    mask = kv_pos < len_ref[b]
    s = jnp.where(mask[None, None, :], s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * corr[..., None]
                    + jnp.einsum("kgG,Gkd->kgd", p, v,
                                 preferred_element_type=jnp.float32))

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[...] = out.astype(o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                           k_scale: jnp.ndarray, v_pages: jnp.ndarray,
                           v_scale: jnp.ndarray, page_table: jnp.ndarray,
                           lengths: jnp.ndarray,
                           interpret: bool = True) -> jnp.ndarray:
    """Single-token paged decode attention from a quantized page pool.

    q           (B, KVH, g, hd)  query heads grouped GQA-style (g = H/KVH)
    k/v_pages   (n_pages, G, KVH, hd) int8 codes
    k/v_scale   (n_pages, G, KVH, 1) f32 per-(token, head) scales
    page_table  (B, n_ptab) int32 physical page ids (0 = null page for
                slots/entries beyond the sequence — masked by ``lengths``)
    lengths     (B,) int32 valid kv rows per slot (the decode token's row
                included: pass ``pos + 1``)
    -> (B, KVH, g, hd) in q's dtype.
    """
    b, kvh, g, hd = q.shape
    n_pages, page_size, kvh_p, _ = k_pages.shape
    n_ptab = page_table.shape[1]
    assert kvh_p == kvh, (q.shape, k_pages.shape)
    assert page_table.shape[0] == b and lengths.shape == (b,)

    qs = (q.astype(jnp.float32) * hd ** -0.5).astype(q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # lengths, page_table
        grid=(b, n_ptab),
        in_specs=[
            pl.BlockSpec((1, kvh, g, hd), lambda bb, i, ln, pt: (bb, 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, hd),
                         lambda bb, i, ln, pt: (pt[bb, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, 1),
                         lambda bb, i, ln, pt: (pt[bb, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, hd),
                         lambda bb, i, ln, pt: (pt[bb, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, 1),
                         lambda bb, i, ln, pt: (pt[bb, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, hd),
                               lambda bb, i, ln, pt: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, g), jnp.float32),       # running max
            pltpu.VMEM((kvh, g), jnp.float32),       # running denom
            pltpu.VMEM((kvh, g, hd), jnp.float32),   # running numerator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=page_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        name="paged_attention_decode",
        interpret=interpret,
    )(lengths, page_table, qs, k_pages, k_scale, v_pages, v_scale)


# ------------------------------------------------- ragged (mixed q_len)

def _ragged_attn_kernel(len_ref, pt_ref, q_ref, qpos_ref, k_ref, ks_ref,
                        v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                        page_size: int):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (KVH, Q·g, hd)
    qpos = qpos_ref[0]                            # (Q·g, 1) absolute positions
    # dequantize, then put the kv head first: (G, KVH, hd) -> (KVH, G, hd)
    k = jnp.swapaxes(k_ref[0].astype(jnp.float32) * ks_ref[0], 0, 1)
    v = jnp.swapaxes(v_ref[0].astype(jnp.float32) * vs_ref[0], 0, 1)

    # scores for this page, batched over kv heads: (KVH, Q·g, G)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    kv_pos = i * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    # per-(query, kv) causal mask inside the chunk: a prefill row at
    # position p sees exactly kv_pos <= p (its same-step chunk-mates
    # beyond p were already written but stay masked); padded query rows
    # (qpos < 0) mask everything and their garbage output is discarded
    mask = (kv_pos <= qpos) & (kv_pos < len_ref[b]) & (qpos >= 0)
    s = jnp.where(mask[None], s, _NEG_INF)

    m_prev = m_ref[...]                           # (KVH, Q·g, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * corr
                    + jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                                          preferred_element_type=jnp.float32))

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_ragged(q: jnp.ndarray, k_pages: jnp.ndarray,
                           k_scale: jnp.ndarray, v_pages: jnp.ndarray,
                           v_scale: jnp.ndarray, page_table: jnp.ndarray,
                           lengths: jnp.ndarray, q_pos: jnp.ndarray,
                           interpret: bool = True) -> jnp.ndarray:
    """Mixed-q_len paged attention: the q_len=1 decode kernel generalized
    to a *block of queries per sequence*, so one launch serves a unified
    token-budget step — each grid row is one work item (a prefill chunk
    OR a decode token) and its pages stream from HBM exactly once for
    all of its queries.

    q           (B, Q, KVH, g, hd)  per-item query blocks (right-padded)
    k/v_pages   (n_pages, G, KVH, hd) int8 codes
    k/v_scale   (n_pages, G, KVH, 1) f32 per-(token, head) scales
    page_table  (B, n_ptab) int32 physical page ids (0 = null page)
    lengths     (B,) int32 valid kv rows per item (last query's pos + 1)
    q_pos       (B, Q) int32 absolute position per query row; -1 marks
                padding rows (fully masked, output garbage — discard)
    -> (B, Q, KVH, g, hd) in q's dtype. ``q_len=1`` with
    ``q_pos = lengths - 1`` reproduces ``paged_attention_decode``.

    Inside the kernel each kv head's queries form one (Q·g, hd) matrix,
    so both contractions are matmuls batched over the kv head.
    """
    b, nq, kvh, g, hd = q.shape
    n_pages, page_size, kvh_p, _ = k_pages.shape
    n_ptab = page_table.shape[1]
    assert kvh_p == kvh, (q.shape, k_pages.shape)
    assert page_table.shape[0] == b and lengths.shape == (b,)
    assert q_pos.shape == (b, nq), (q_pos.shape, q.shape)

    qg = nq * g
    qs = (q.astype(jnp.float32) * hd ** -0.5).astype(q.dtype)
    qs = qs.transpose(0, 2, 1, 3, 4).reshape(b, kvh, qg, hd)
    # one position per (query, group) row, as a column
    qpos = jnp.repeat(q_pos, g, axis=1)[:, :, None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # lengths, page_table
        grid=(b, n_ptab),
        in_specs=[
            pl.BlockSpec((1, kvh, qg, hd),
                         lambda bb, i, ln, pt: (bb, 0, 0, 0)),
            pl.BlockSpec((1, qg, 1), lambda bb, i, ln, pt: (bb, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, hd),
                         lambda bb, i, ln, pt: (pt[bb, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, 1),
                         lambda bb, i, ln, pt: (pt[bb, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, hd),
                         lambda bb, i, ln, pt: (pt[bb, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, 1),
                         lambda bb, i, ln, pt: (pt[bb, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvh, qg, hd),
                               lambda bb, i, ln, pt: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, qg, 1), jnp.float32),       # running max
            pltpu.VMEM((kvh, qg, 1), jnp.float32),       # running denom
            pltpu.VMEM((kvh, qg, hd), jnp.float32),      # running numerator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_attn_kernel, page_size=page_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, qg, hd), q.dtype),
        name="paged_attention_ragged",
        interpret=interpret,
    )(lengths, page_table, qs, qpos, k_pages, k_scale, v_pages, v_scale)
    return out.reshape(b, kvh, nq, g, hd).transpose(0, 2, 1, 3, 4)


def paged_attention_ragged_fallback(q: jnp.ndarray, k_pages, k_scale,
                                    v_pages, v_scale,
                                    page_table: jnp.ndarray,
                                    lengths: jnp.ndarray,
                                    q_pos: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp ragged paged attention (same contract as the kernel).

    Gathers each item's logical view and runs a per-(query, kv) causally
    masked softmax in f32. Also serves fp pools: pass ``k_scale``/
    ``v_scale`` as ``None`` and fp ``*_pages``.
    """
    b, nq, kvh, g, hd = q.shape
    page_size = k_pages.shape[1]

    def logical(pages, scale):
        view = pages[page_table].reshape(b, -1, kvh, hd)  # (B, S, KVH, hd)
        if scale is None:
            return view.astype(jnp.float32)
        sc = scale[page_table].reshape(b, -1, kvh, 1)
        return view.astype(jnp.float32) * sc

    k = logical(k_pages, k_scale)
    v = logical(v_pages, v_scale)
    skv = page_table.shape[1] * page_size
    qf = q.astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bqkgd,bskd->bqkgs", qf, k)
    kv = jnp.arange(skv, dtype=jnp.int32)
    mask = ((kv[None, None, :] <= q_pos[:, :, None])
            & (kv[None, None, :] < lengths[:, None, None])
            & (q_pos[:, :, None] >= 0))
    s = jnp.where(mask[:, :, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgs,bskd->bqkgd", p, v)
    return out.astype(q.dtype)


def paged_attention_fallback(q: jnp.ndarray, k_pages, k_scale, v_pages,
                             v_scale, page_table: jnp.ndarray,
                             lengths: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp paged decode attention (same contract as the kernel).

    Gathers the logical view and runs a masked softmax in f32. Also serves
    fp pools: pass ``k_scale``/``v_scale`` as ``None`` and fp ``*_pages``.
    """
    b, kvh, g, hd = q.shape
    page_size = k_pages.shape[1]

    def logical(pages, scale):
        view = pages[page_table].reshape(b, -1, kvh, hd)  # (B, S, KVH, hd)
        if scale is None:
            return view.astype(jnp.float32)
        sc = scale[page_table].reshape(b, -1, kvh, 1)
        return view.astype(jnp.float32) * sc

    k = logical(k_pages, k_scale)
    v = logical(v_pages, v_scale)
    skv = page_table.shape[1] * page_size
    qf = q.astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k)
    mask = jnp.arange(skv, dtype=jnp.int32)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return out.astype(q.dtype)
