"""Pallas TPU kernel: paged decode attention over a quantized KV pool.

The serve engine's paged KV cache stores int8 codes + per-(token, head)
f32 scales in fixed-size *pages* of a global pool (``repro.launch.paged``);
a request's logical sequence is the concatenation of the pages its page
table names. The kernels take each pool leaf whole, every layer stacked
on its leading axis, as the model's layer loop carries it, and a layer
index that selects the layer in their index maps, so no layer's pool is
ever sliced out or relaid. The leaves are stored in the layout the
kernels read (``scale_lanes``): codes ``(L, n_pages, G, KVH·hd)``, a
page's rows with their heads side by side on lanes, and scales ``(L,
n_pages, KVH, lanes)``, a page's rows on the first ``G`` of 128 lanes.
With a minor dim a multiple of 128 the chip stores either as it is; a
minor ``hd`` or ``G`` under 128 would make it store the page axis minor
and relayout every call. A page's scales are one ``(KVH, lanes)`` block,
which the kernels multiply into the scores (K) and the softmax weights
(V) of the page's rows rather than into the codes. Decode attention is
then a gather problem: for slot ``b``, stream pages ``page_table[b, i]``
from HBM, scale in VMEM, and fold each page into an online-softmax
accumulator — the bf16 logical cache is never materialized and the int8
pages are the only HBM stream (half the bytes of an fp16 cache per
decoded token, the memory-bound regime where KV quantization pays).

The layer index, page table and per-slot lengths ride in as
**scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec``): they
are resident before the kernel body runs, so the k/v BlockSpec index
maps can address the *physical* page ``pt[b, t]`` of a *logical* table
entry ``t`` — the indirection is free, folded into the DMA descriptor.

Grid: one step per **live block** of ``ppb`` table pages, rows one after
another, so the grid's length is known only from the lengths (a dynamic
grid). ``ppb`` follows from the shapes alone (``_pages_per_block``: about
128 KV rows, double-buffered codes and scales within a fixed share of the
default scoped VMEM); each of a block's pages is its own operand (the
pool passed ``ppb`` times, one BlockSpec each), since a block's pages lie
anywhere in the pool. Entries past a row's context are never fetched:
their operands keep the block index they last held, so the pipeline
skips the copy, and a row of ``n`` live pages takes ``ceil(n / ppb)``
steps (at least one, which writes its output). VMEM scratch carries the
flash-attention running (m, l, acc) across a row's blocks (init at its
first block, final ``acc / l`` write-out at its last). The ragged last
page and a tail block's repeated pages are masked by ``kv_pos <
length[b]`` — garbage rows get ``exp(-1e30 - m) == 0`` weight exactly.
(The pool layout's 128-lane minor dims would also let a page be copied
by hand from a ``pl.ANY`` leaf: Mosaic compiles that DMA for the ``(G,
KVH·hd)`` code and ``(KVH, lanes)`` scale pages; it refuses a page whose
minor dim is not a multiple of 128.)

``repro.kernels.ref.paged_attention_decode`` is the jnp oracle;
``paged_attention_fallback`` is a gather-based jnp path for fp pools and
backends without Pallas.

``paged_attention_ragged`` generalizes the q_len=1 decode kernel to a
*block of queries per sequence* with a per-(query, kv) causal mask — the
shape of a unified token-budget step, where one launch covers every
prefill chunk and decode token packed into the step (see
``repro.launch.scheduler``). Its grid is the same kind of dynamic grid,
one step per live (item, query tile, KV block): an item's query rows are
cut into tiles of up to 128 rows, a tile without a live row takes no
step, and a tile's blocks stop at its causal horizon. ``ragged_work``
counts what it computes, on the host.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import DEFAULT_SCOPED_VMEM, tile_bytes, vmem_params

_NEG_INF = -1e30


# One grid step covers this many KV rows where the VMEM budget allows.
_BLOCK_ROWS = 128
# Budget of the double-buffered page blocks (codes and scales of K and V),
# padded to the chip's (8 x 32-bit sublane, 128-lane) tiles: a quarter of
# the 16 MiB default scoped VMEM, the rest left to the block's values.
_BLOCK_VMEM_BYTES = 4 << 20


def scale_lanes(page_size: int) -> int:
    """Lanes of a scale leaf's page row: ``page_size`` rounded up to a
    whole number of 128-lane vregs."""
    return -(-page_size // 128) * 128


def _page_vmem_bytes(page_size: int, kvh: int, hd: int, code_bytes: int,
                     scale_bytes: int) -> int:
    """VMEM of one page's code and scale blocks, in padded tiles."""
    return (tile_bytes(page_size, kvh * hd, code_bytes)
            + tile_bytes(kvh, scale_lanes(page_size), scale_bytes))


def _pages_per_block(page_size: int, kvh: int, hd: int, n_ptab: int,
                     code_bytes: int = 1, scale_bytes: int = 4) -> int:
    """Table pages one decode grid step fetches and computes: about
    ``_BLOCK_ROWS`` KV rows, as many as keep both buffers of K and V
    (codes + scales) within ``_BLOCK_VMEM_BYTES``, never more than the
    table holds."""
    per_page = 2 * 2 * _page_vmem_bytes(page_size, kvh, hd, code_bytes,
                                        scale_bytes)
    return max(1, min(_BLOCK_ROWS // page_size,
                      _BLOCK_VMEM_BYTES // per_page, n_ptab))


def live_pages(lengths, page_size: int, n_ptab: int) -> int:
    """Table pages that hold some of the live context, summed over rows:
    ``min(ceil(len / page_size), n_ptab)`` each — the pages
    ``paged_attention_decode`` fetches (a row shorter than one block also
    re-reads its last live page on its unused page operands). Host-side
    (numpy)."""
    need = -(-np.asarray(lengths, np.int64) // page_size)
    return int(np.minimum(need, n_ptab).sum())


def _live_blocks(lengths, page_size: int, n_ptab: int, ppb: int):
    """(live table pages, blocks) per row: ``ceil(len / page_size)``
    capped at the table width, and those pages in blocks of ``ppb`` —
    at least one block, so that every row's output is written."""
    pages = jnp.minimum((lengths + page_size - 1) // page_size, n_ptab)
    return pages, jnp.maximum((pages + ppb - 1) // ppb, 1)


def _page_specs(j: int, ppb: int, page_size: int, kvh: int, hd: int,
                lanes: int, n_ptab: int, where) -> tuple:
    """(codes, scales) BlockSpecs of the ``j``-th page operand: one page
    of the layer the first scalar-prefetch operand names, the physical
    page of table entry ``i * ppb + j`` of the step's table row while
    that entry is live; past the live context, the last live entry this
    operand held, so the pipeline sees an unchanged block and fetches
    nothing. An operand with no live entry in the row holds the row's
    last live page. ``where(s, *prefetch)`` gives step ``s``'s (page
    table, table row, live KV rows, block)."""
    def page(s, layer, *prefetch):
        pt, bb, length, i = where(s, *prefetch)
        last = _live_blocks(length, page_size, n_ptab, ppb)[0] - 1
        held = jnp.where(j <= last, j + (last - j) // ppb * ppb,
                         jnp.maximum(last, 0))
        return layer[0], pt[bb, jnp.minimum(i * ppb + j, held)]
    return (pl.BlockSpec((pl.squeezed, 1, page_size, kvh * hd),
                         lambda *a: (*page(*a), 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, kvh, lanes),
                         lambda *a: (*page(*a), 0, 0)))


def _check_pool(k_pages, k_scale, kvh: int, hd: int) -> None:
    """The pool leaves are in the layout ``scale_lanes`` describes."""
    lyr, n_pages, page_size, width = k_pages.shape
    assert width == kvh * hd, (k_pages.shape, kvh, hd)
    assert k_scale.shape == (lyr, n_pages, kvh, scale_lanes(page_size)), (
        k_scale.shape, k_pages.shape)


def _layer_operand(layer):
    """The layer index as the (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _decode_step(s, ln, pt, row, blk):
    """Page table, table row, KV length and block of decode step ``s``."""
    return pt, row[s], ln[row[s]], blk[s]


def _codes(refs, kvh: int):
    """A block's pages' codes as f32, kv head first: (KVH, rows, hd) from
    each head's lanes of the pages' (G, KVH·hd) rows."""
    rows = jnp.concatenate([c[0].astype(jnp.float32) for c in refs], axis=0)
    hd = rows.shape[1] // kvh
    return jnp.stack([rows[:, h * hd:(h + 1) * hd] for h in range(kvh)])


def _scales(refs, page_size: int):
    """A block's pages' scales, kv head first, rows on lanes: (KVH, 1,
    rows) — a row's K (V) scale multiplies its scores (weights)."""
    return jnp.swapaxes(jnp.concatenate(
        [sc[..., :page_size] for sc in refs], axis=-1), 0, 1)


def _paged_attn_kernel(layer_ref, len_ref, pt_ref, row_ref, blk_ref, q_ref,
                       *refs, page_size: int, ppb: int):
    k_refs, ks_refs, v_refs, vs_refs = (refs[n * ppb:(n + 1) * ppb]
                                        for n in range(4))
    o_ref, m_ref, l_ref, acc_ref = refs[4 * ppb:]
    s = pl.program_id(0)
    b, i = row_ref[s], blk_ref[s]
    rows = ppb * page_size
    length = len_ref[b]
    n_blocks = _live_blocks(length, page_size, pt_ref.shape[1], ppb)[1]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * rows < length)
    def _block():
        q = q_ref[0].astype(jnp.float32)              # (KVH, g, hd), pre-scaled
        kvh = q.shape[0]
        k, v = _codes(k_refs, kvh), _codes(v_refs, kvh)   # (KVH, rows, hd)

        # scores for this block, batched over kv heads: (KVH, g, rows);
        # entries past the live context hold a repeated live page and get
        # exactly zero weight
        sc = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        sc = sc * _scales(ks_refs, page_size)
        kv_pos = i * rows + jax.lax.iota(jnp.int32, rows)
        sc = jnp.where((kv_pos < length)[None, None, :], sc, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * corr[..., None]
                        + jax.lax.dot_general(
                            p * _scales(vs_refs, page_size), v,
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32))

    @pl.when(i == n_blocks - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[...] = out.astype(o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                           k_scale: jnp.ndarray, v_pages: jnp.ndarray,
                           v_scale: jnp.ndarray, layer,
                           page_table: jnp.ndarray, lengths: jnp.ndarray,
                           interpret: bool = True) -> jnp.ndarray:
    """Single-token paged decode attention from a quantized page pool.

    q           (B, KVH, g, hd)  query heads grouped GQA-style (g = H/KVH)
    k/v_pages   (L, n_pages, G, KVH·hd) int8 codes
    k/v_scale   (L, n_pages, KVH, scale_lanes(G)) f32 per-(token, head)
                scales, row r of a page on lane r
    layer       int32 scalar: the layer of the pools to attend over
    page_table  (B, n_ptab) int32 physical page ids (0 = null page for
                entries beyond the sequence — never fetched)
    lengths     (B,) int32 valid kv rows per slot (the decode token's row
                included: pass ``pos + 1``)
    -> (B, KVH, g, hd) in q's dtype.
    """
    b, kvh, g, hd = q.shape
    page_size, lanes = k_pages.shape[2], k_scale.shape[3]
    n_ptab = page_table.shape[1]
    _check_pool(k_pages, k_scale, kvh, hd)
    assert page_table.shape[0] == b and lengths.shape == (b,)
    ppb = _pages_per_block(page_size, kvh, hd, n_ptab,
                           k_pages.dtype.itemsize, k_scale.dtype.itemsize)

    # the grid walks the live blocks only: step s is block blk[s] of row
    # row[s]; the rows follow one another, each over its blocks in order
    n_blocks = _live_blocks(lengths, page_size, n_ptab, ppb)[1]
    ends = jnp.cumsum(n_blocks)
    steps = jnp.arange(b * pl.cdiv(n_ptab, ppb), dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(ends[None, :] <= steps[:, None], axis=1,
                              dtype=jnp.int32), b - 1)
    blk = steps - (ends - n_blocks)[row]

    qs = (q.astype(jnp.float32) * hd ** -0.5).astype(q.dtype)
    codes, scales = zip(*(_page_specs(j, ppb, page_size, kvh, hd, lanes,
                                      n_ptab, _decode_step)
                          for j in range(ppb)))
    per_row = pl.BlockSpec(
        (1, kvh, g, hd), lambda s, lyr, ln, pt, row, blk: (row[s], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,      # layer, lengths, page_table, row, blk
        grid=(ends[-1],),
        in_specs=[per_row, *codes, *scales, *codes, *scales],
        out_specs=per_row,
        scratch_shapes=[
            pltpu.VMEM((kvh, g), jnp.float32),       # running max
            pltpu.VMEM((kvh, g), jnp.float32),       # running denom
            pltpu.VMEM((kvh, g, hd), jnp.float32),   # running numerator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=page_size, ppb=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        name="paged_attention_decode",
        interpret=interpret,
    )(_layer_operand(layer), lengths, page_table, row, blk, qs,
      *[k_pages] * ppb, *[k_scale] * ppb, *[v_pages] * ppb,
      *[v_scale] * ppb)


# ------------------------------------------------- ragged (mixed q_len)

# A ragged grid step multiplies at most this many query rows: the MXU's
# height.
_TILE_ROWS = 128


def _ragged_vmem_bytes(kvh: int, tile: int, hd: int, page_size: int,
                       ppb: int, q_bytes: int, code_bytes: int,
                       scale_bytes: int) -> int:
    """VMEM one ragged grid step takes, in the chip's padded tiles: two
    buffers of the tile's (KVH, tile, hd) query and output blocks, of
    its positions and of ``ppb`` pages' codes and scales of K and V; the
    (m, l, acc) scratch; and the f32 values of a block (the query,
    dequantized K and V, scores, weights, the product into acc)."""
    rows = ppb * page_size
    per_head = (4 * tile_bytes(tile, hd, q_bytes)       # q, out buffers
                + 2 * tile_bytes(tile, 1, 4)             # m, l
                + 4 * tile_bytes(tile, hd, 4)            # q, acc, update
                + 2 * tile_bytes(tile, rows, 4)          # scores, weights
                + 2 * tile_bytes(rows, hd, 4))           # K, V
    pages = 2 * 2 * ppb * _page_vmem_bytes(page_size, kvh, hd, code_bytes,
                                           scale_bytes)
    return kvh * per_head + 2 * tile_bytes(tile, 1, 4) + pages


def _ragged_blocks(qg: int, g: int, kvh: int, hd: int, page_size: int,
                   n_ptab: int, code_bytes: int = 1,
                   scale_bytes: int = 4) -> tuple:
    """(query tile, pages per block) of a ragged call. The tile is the
    item's whole ``Q·g`` rows where they are at most ``_TILE_ROWS``,
    else whole queries in whole 16-row sublane tiles (even for f32 and
    bf16), up to ``_TILE_ROWS``, and fewer where a step's VMEM need,
    its query block counted as f32, would pass the default scoped
    limit. ``ppb`` is the decode kernel's."""
    ppb = _pages_per_block(page_size, kvh, hd, n_ptab, code_bytes,
                           scale_bytes)
    if qg <= _TILE_ROWS:
        return qg, ppb
    unit = math.lcm(g, 16)
    tile = max(unit, _TILE_ROWS // unit * unit)
    while tile > unit and _ragged_vmem_bytes(
            kvh, tile, hd, page_size, ppb, 4, code_bytes,
            scale_bytes) > DEFAULT_SCOPED_VMEM:
        tile -= unit
    return tile, ppb


def _row_positions(q_pos, g: int, tile: int, xp):
    """(B, n_tiles·tile) position of each query row: query-major, ``g``
    rows a query, padded with -1 to whole tiles. ``xp`` is ``numpy`` or
    ``jax.numpy``."""
    nq = q_pos.shape[1]
    pad = -(-nq * g // tile) * tile - nq * g
    return xp.pad(xp.repeat(q_pos, g, axis=1), ((0, 0), (0, pad)),
                  constant_values=-1)


def _tile_ends(rows, lengths, tile: int, xp):
    """(B, n_tiles) end of each query tile's KV range: ``min(max qpos in
    the tile + 1, length)``; -1 for a tile without a live row."""
    top = rows.reshape(rows.shape[0], -1, tile).max(axis=-1)
    return xp.where(top >= 0, xp.minimum(top + 1, lengths[:, None]), -1)


def _tile_blocks(ends, page_size: int, n_ptab: int, ppb: int, xp):
    """KV blocks a tile of KV range ``ends`` takes: its live pages in
    blocks of ``ppb``, at least one for a live tile (which writes its
    output), none for a tile without a live row."""
    pages = xp.minimum(-(-xp.maximum(ends, 0) // page_size), n_ptab)
    return xp.where(ends >= 0, xp.maximum(-(-pages // ppb), 1), 0)


def ragged_work(lengths, q_pos, g: int, kvh: int, hd: int, page_size: int,
                n_ptab: int) -> tuple:
    """(query rows x KV pages ``paged_attention_ragged`` computes, the
    same of a walk of every row over the whole table: ``B x Q·g x
    n_ptab``) for an int8 pool. Each visited (item, tile, block) step
    counts its tile's rows times its ``ppb`` pages; a call with no live
    row still takes one step. Host-side (numpy), by the kernel's own
    rule."""
    q_pos = np.asarray(q_pos, np.int64)
    b, nq = q_pos.shape
    tile, ppb = _ragged_blocks(nq * g, g, kvh, hd, page_size, n_ptab)
    ends = _tile_ends(_row_positions(q_pos, g, tile, np),
                      np.asarray(lengths, np.int64), tile, np)
    steps = max(int(_tile_blocks(ends, page_size, n_ptab, ppb, np).sum()),
                1)
    return steps * tile * ppb, b * nq * g * n_ptab


def _ragged_grid(rows, lengths, tile: int, page_size: int, n_ptab: int,
                 ppb: int):
    """The ragged kernel's dynamic grid over row positions ``rows``
    (``_row_positions``): -> (ends, work, blk, steps). ``ends``
    (B·n_tiles,) is each work unit's KV range (``_tile_ends``, flattened
    item-major); step ``s < steps`` computes block ``blk[s]`` of work
    unit ``work[s]`` = item x n_tiles + tile. Live units follow one
    another, each over its blocks in order; units without a live row
    take no step (one step in all where none is live)."""
    ends = _tile_ends(rows, lengths, tile, jnp).reshape(-1)
    n_blocks = _tile_blocks(ends, page_size, n_ptab, ppb, jnp)
    stops = jnp.cumsum(n_blocks)
    bound = ends.shape[0] * pl.cdiv(n_ptab, ppb)
    steps = jnp.arange(bound, dtype=jnp.int32)
    work = jnp.minimum(jnp.sum(stops[None, :] <= steps[:, None], axis=1,
                               dtype=jnp.int32), ends.shape[0] - 1)
    blk = steps - (stops - n_blocks)[work]
    return ends, work, blk, jnp.maximum(stops[-1], 1)


def _ragged_attn_kernel(layer_ref, pt_ref, end_ref, work_ref, blk_ref, q_ref,
                        qpos_ref, *refs, page_size: int, ppb: int):
    k_refs, ks_refs, v_refs, vs_refs = (refs[n * ppb:(n + 1) * ppb]
                                        for n in range(4))
    o_ref, m_ref, l_ref, acc_ref = refs[4 * ppb:]
    s = pl.program_id(0)
    i, end = blk_ref[s], end_ref[work_ref[s]]
    rows = ppb * page_size
    n_blocks = _live_blocks(end, page_size, pt_ref.shape[1], ppb)[1]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * rows < end)
    def _block():
        q = q_ref[0].astype(jnp.float32)          # (KVH, tile, hd)
        qpos = qpos_ref[0]                        # (tile, 1) positions
        kvh = q.shape[0]
        k, v = _codes(k_refs, kvh), _codes(v_refs, kvh)   # (KVH, rows, hd)

        # scores for this block, batched over kv heads: (KVH, tile, rows)
        sc = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        sc = sc * _scales(ks_refs, page_size)
        kv_pos = i * rows + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1)
        # per-(query, kv) causal mask: a prefill row at position p sees
        # exactly kv_pos <= p (its same-step chunk-mates beyond p were
        # already written but stay masked); the tile's range end also
        # masks a tail block's repeated pages; padded query rows (qpos
        # < 0) mask everything and their garbage output is discarded
        mask = (kv_pos <= qpos) & (kv_pos < end) & (qpos >= 0)
        sc = jnp.where(mask[None], sc, _NEG_INF)

        m_prev = m_ref[...]                       # (KVH, tile, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p * _scales(vs_refs, page_size), v,
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32))

    @pl.when(i == n_blocks - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)[None]


def _ragged_step(s, pt, ends, work, blk, *, n_tiles: int):
    """Page table, table row, KV range and block of ragged step ``s``."""
    return pt, work[s] // n_tiles, ends[work[s]], blk[s]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_ragged(q: jnp.ndarray, k_pages: jnp.ndarray,
                           k_scale: jnp.ndarray, v_pages: jnp.ndarray,
                           v_scale: jnp.ndarray, layer,
                           page_table: jnp.ndarray, lengths: jnp.ndarray,
                           q_pos: jnp.ndarray,
                           interpret: bool = True) -> jnp.ndarray:
    """Mixed-q_len paged attention: the q_len=1 decode kernel generalized
    to a *block of queries per sequence*, so one launch serves a unified
    token-budget step — each work item is one prefill chunk OR one
    decode token (or a speculative verify block).

    q           (B, Q, KVH, g, hd)  per-item query blocks (right-padded)
    k/v_pages   (L, n_pages, G, KVH·hd) int8 codes
    k/v_scale   (L, n_pages, KVH, scale_lanes(G)) f32 per-(token, head)
                scales, row r of a page on lane r
    layer       int32 scalar: the layer of the pools to attend over
    page_table  (B, n_ptab) int32 physical page ids (0 = null page)
    lengths     (B,) int32 valid kv rows per item (last query's pos + 1)
    q_pos       (B, Q) int32 absolute position per query row; -1 marks
                padding rows (fully masked, output garbage — discard)
    -> (B, Q, KVH, g, hd) in q's dtype. ``q_len=1`` with
    ``q_pos = lengths - 1`` reproduces ``paged_attention_decode``.

    Each kv head's queries form one (Q·g, hd) matrix, query-major, cut
    into tiles of ``tile`` rows (``_ragged_blocks``). The grid walks only
    live (item, tile, KV block) triples, built from ``lengths`` and
    ``q_pos`` (``_ragged_grid``): a tile without a live row takes no
    step, and a tile's blocks stop at its causal horizon, ``min(max qpos
    in the tile + 1, length)``. A block is ``ppb`` pages, each its own
    operand, so one step's contractions are (tile, hd) x (hd, ppb·G)
    matmuls batched over the kv head. Rows of tiles never visited are
    garbage, like padded rows.
    """
    b, nq, kvh, g, hd = q.shape
    page_size, lanes = k_pages.shape[2], k_scale.shape[3]
    n_ptab = page_table.shape[1]
    _check_pool(k_pages, k_scale, kvh, hd)
    assert page_table.shape[0] == b and lengths.shape == (b,)
    assert q_pos.shape == (b, nq), (q_pos.shape, q.shape)

    qg = nq * g
    tile, ppb = _ragged_blocks(qg, g, kvh, hd, page_size, n_ptab,
                               k_pages.dtype.itemsize,
                               k_scale.dtype.itemsize)
    n_tiles = pl.cdiv(qg, tile)
    qs = (q.astype(jnp.float32) * hd ** -0.5).astype(q.dtype)
    qs = qs.transpose(0, 2, 1, 3, 4).reshape(b, kvh, qg, hd)
    qs = jnp.pad(qs, ((0, 0), (0, 0), (0, n_tiles * tile - qg), (0, 0)))
    rows = _row_positions(q_pos, g, tile, jnp)
    ends, work, blk, steps = _ragged_grid(rows, lengths, tile, page_size,
                                          n_ptab, ppb)

    where = functools.partial(_ragged_step, n_tiles=n_tiles)
    codes, scales = zip(*(_page_specs(j, ppb, page_size, kvh, hd, lanes,
                                      n_ptab, where) for j in range(ppb)))
    per_tile = pl.BlockSpec(
        (1, kvh, tile, hd),
        lambda s, lyr, pt, e, w, bk: (w[s] // n_tiles, 0, w[s] % n_tiles, 0))
    pos_tile = pl.BlockSpec(
        (1, tile, 1),
        lambda s, lyr, pt, e, w, bk: (w[s] // n_tiles, w[s] % n_tiles, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,      # layer, page_table, ends, work, blk
        grid=(steps,),
        in_specs=[per_tile, pos_tile, *codes, *scales, *codes, *scales],
        out_specs=per_tile,
        scratch_shapes=[
            pltpu.VMEM((kvh, tile, 1), jnp.float32),     # running max
            pltpu.VMEM((kvh, tile, 1), jnp.float32),     # running denom
            pltpu.VMEM((kvh, tile, hd), jnp.float32),    # running numerator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_attn_kernel, page_size=page_size,
                          ppb=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, n_tiles * tile, hd),
                                       q.dtype),
        name="paged_attention_ragged",
        compiler_params=vmem_params(_ragged_vmem_bytes(
            kvh, tile, hd, page_size, ppb, q.dtype.itemsize,
            k_pages.dtype.itemsize, k_scale.dtype.itemsize)),
        interpret=interpret,
    )(_layer_operand(layer), page_table, ends, work, blk, qs,
      rows[:, :, None], *[k_pages] * ppb, *[k_scale] * ppb,
      *[v_pages] * ppb, *[v_scale] * ppb)
    out = out[:, :, :qg].reshape(b, kvh, nq, g, hd)
    return out.transpose(0, 2, 1, 3, 4)


def _logical(pages, scale, layer, page_table, hd: int):
    """One layer's logical (B, n_ptab·G, KVH, hd) f32 view of the pool
    leaves through ``page_table`` (``models.layers.gather_pages``): codes
    times their per-row scales, or an fp pool as it is where ``scale``
    is None."""
    from repro.models.layers import gather_pages, gather_scales
    view = gather_pages(pages, page_table, layer, hd).astype(jnp.float32)
    if scale is None:
        return view
    return view * gather_scales(scale, page_table, layer, pages.shape[2])


def paged_attention_ragged_fallback(q: jnp.ndarray, k_pages, k_scale,
                                    v_pages, v_scale, layer,
                                    page_table: jnp.ndarray,
                                    lengths: jnp.ndarray,
                                    q_pos: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp ragged paged attention (same contract as the kernel).

    Gathers each item's logical view and runs a per-(query, kv) causally
    masked softmax in f32. Also serves fp pools: pass ``k_scale``/
    ``v_scale`` as ``None`` and fp ``*_pages``.
    """
    hd = q.shape[-1]
    k = _logical(k_pages, k_scale, layer, page_table, hd)
    v = _logical(v_pages, v_scale, layer, page_table, hd)
    skv = k.shape[1]
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
                             v_scale, layer, page_table: jnp.ndarray,
                             lengths: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp paged decode attention (same contract as the kernel).

    Gathers the logical view and runs a masked softmax in f32. Also serves
    fp pools: pass ``k_scale``/``v_scale`` as ``None`` and fp ``*_pages``.
    """
    hd = q.shape[-1]
    k = _logical(k_pages, k_scale, layer, page_table, hd)
    v = _logical(v_pages, v_scale, layer, page_table, hd)
    skv = k.shape[1]
    qf = q.astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k)
    mask = jnp.arange(skv, dtype=jnp.int32)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return out.astype(q.dtype)
