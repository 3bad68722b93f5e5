"""Ragged (mixed q_len) paged-attention kernel vs its jnp oracle.

``kernels.paged_attention.paged_attention_ragged`` generalizes the
q_len=1 decode kernel to per-sequence query *blocks* with a
per-(query, kv) causal mask — the attention shape of a unified
token-budget step. Pins, at rtol 1e-5 against ``kernels.ref``:

- mixed q_len batches (decode singletons next to multi-token chunks)
- ragged last pages (lengths not multiples of page_size)
- padded query rows (qpos = -1) never contaminating real rows
- exact masking: poisoning rows beyond each sequence's causal horizon
  with huge codes cannot move the output
- the q_len=1 degenerate case equals the decode kernel bitwise-ish
  (same math, same rtol band vs the oracle)
- ops dispatch: fp pools (no scales) route to the jnp fallback
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pools import stacked
from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.kernels.paged_attention import (paged_attention_decode,
                                           paged_attention_ragged,
                                           paged_attention_ragged_fallback)

RTOL = 1e-5


def _pool(key, n_pages, page_size, kvh, hd):
    kk, ks, kv, kvs = jax.random.split(key, 4)
    k_pages = jax.random.randint(kk, (n_pages, page_size, kvh, hd),
                                 -127, 128, jnp.int8)
    v_pages = jax.random.randint(kv, (n_pages, page_size, kvh, hd),
                                 -127, 128, jnp.int8)
    k_scale = jax.random.uniform(ks, (n_pages, page_size, kvh, 1),
                                 jnp.float32, 0.01, 0.1)
    v_scale = jax.random.uniform(kvs, (n_pages, page_size, kvh, 1),
                                 jnp.float32, 0.01, 0.1)
    return stacked(k_pages, k_scale, v_pages, v_scale)


def _case(seed, b, nq, kvh, g, hd, page_size, n_ptab, q_lens, lengths):
    """Build a ragged batch: row i holds q_lens[i] real query rows ending
    at position lengths[i]-1, with distinct pages per row, over a
    one-layer stacked pool (layer 0)."""
    key = jax.random.PRNGKey(seed)
    n_pages = 1 + b * n_ptab
    kq, kp = jax.random.split(key)
    pools = _pool(kp, n_pages, page_size, kvh, hd)
    q = jax.random.normal(kq, (b, nq, kvh, g, hd), jnp.float32)
    table = np.zeros((b, n_ptab), np.int32)
    nxt = 1
    for i in range(b):
        used = -(-int(lengths[i]) // page_size)
        for j in range(used):
            table[i, j] = nxt
            nxt += 1
    qpos = np.full((b, nq), -1, np.int32)
    for i, (ql, ln) in enumerate(zip(q_lens, lengths)):
        qpos[i, :ql] = ln - ql + np.arange(ql)
    return (q, *pools, 0, jnp.asarray(table),
            jnp.asarray(np.asarray(lengths, np.int32)),
            jnp.asarray(qpos))


# (q_lens, lengths, page_size, n_ptab) of a ragged batch (``_case``)
CASES = [
    # mixed: a decode singleton, a mid-size chunk, a full-block chunk
    ((1, 3, 6), (9, 7, 6), 4, 3),
    # ragged last pages: lengths far from page multiples
    ((2, 5, 1), (11, 5, 13), 4, 4),
    # single page, GQA-free edge
    ((1, 2, 2), (1, 2, 8), 8, 1),
    # an empty item (length 0, every qpos -1) between live ones
    ((3, 0, 1), (9, 0, 5), 4, 3),
    # decode items only: one live query tile each
    ((1, 1, 1, 1), (5, 16, 1, 33), 8, 5),
    # a 200-query chunk from position 0 is four 128-row tiles; the first
    # two stop after one 128-row KV block of the three a decode item
    # beside it takes
    ((200, 1), (200, 300), 16, 20),
    # lengths exactly at page and block ends (8 pages of 16 a block)
    ((3, 1, 2), (128, 16, 256), 16, 16),
    # 21 table entries in blocks of 16 pages: a 5-page tail block
    ((2, 1, 4), (168, 100, 9), 8, 21),
    # 140 live rows: a full tile and one of 12 live rows
    ((70, 5), (150, 90), 16, 10),
]
CASE_IDS = ["mixed", "ragged-pages", "one-page", "empty-item",
            "decode-only", "causal-tiles", "boundaries", "tail-block",
            "partial-tile"]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("q_lens,lengths,page_size,n_ptab", CASES,
                         ids=CASE_IDS)
def test_ragged_kernel_matches_oracle(seed, q_lens, lengths, page_size,
                                      n_ptab):
    args = _case(seed, len(q_lens), max(q_lens), kvh=2, g=2, hd=8,
                 page_size=page_size, n_ptab=n_ptab, q_lens=q_lens,
                 lengths=lengths)
    got = paged_attention_ragged(*args, interpret=True)
    want = ref.paged_attention_ragged(*args)
    qpos = np.asarray(args[-1])
    valid = qpos >= 0
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(want)[valid],
                               rtol=RTOL, atol=1e-5)


def _live_steps(q_pos, lengths, g, tile, ppb, page_size, n_ptab):
    """Brute force: every (item, query tile, KV block) with a live query
    row in the tile whose block starts before the tile's causal horizon
    (its last live position + 1) and the item's length; a live tile
    whose horizon is 0 still takes its first block."""
    out = []
    for j, (row, length) in enumerate(zip(q_pos, lengths)):
        rows = [int(p) for p in row for _ in range(g)]
        for t in range(-(-len(rows) // tile)):
            live = [p for p in rows[t * tile:(t + 1) * tile] if p >= 0]
            if not live:
                continue
            horizon = min(max(live) + 1, int(length))
            for i in range(-(-n_ptab // ppb)):
                if i == 0 or i * ppb * page_size < horizon:
                    out.append((j, t, i))
    return out


@pytest.mark.parametrize("q_lens,lengths,page_size,n_ptab", CASES,
                         ids=CASE_IDS)
def test_ragged_grid_visits_only_live_work(q_lens, lengths, page_size,
                                           n_ptab):
    """The wrapper's dynamic grid is exactly the live (item, tile, block)
    triples, in order, and ``ragged_work`` counts its steps' rows x
    pages against the whole walk."""
    g, kvh, hd = 2, 2, 8
    *_, ln, qpos = _case(0, len(q_lens), max(q_lens), kvh, g, hd,
                         page_size, n_ptab, q_lens, lengths)
    nq = qpos.shape[1]
    tile, ppb = pa._ragged_blocks(nq * g, g, kvh, hd, page_size, n_ptab)
    n_tiles = -(-nq * g // tile)
    _, work, blk, steps = pa._ragged_grid(
        pa._row_positions(qpos, g, tile, jnp), ln, tile, page_size, n_ptab,
        ppb)
    steps = int(steps)
    got = [(int(w) // n_tiles, int(w) % n_tiles, int(i))
           for w, i in zip(work[:steps], blk[:steps])]
    want = _live_steps(np.asarray(qpos), lengths, g, tile, ppb, page_size,
                       n_ptab)
    assert got == want
    assert work.shape == (len(q_lens) * n_tiles * -(-n_ptab // ppb),)
    read, table = pa.ragged_work(lengths, qpos, g, kvh, hd, page_size,
                                 n_ptab)
    assert read == len(want) * tile * ppb
    assert table == len(q_lens) * nq * g * n_ptab


def test_ragged_work_of_a_cell_chunk_step():
    """At ``nemo8.azconv``'s widths (8 KV heads of 128, groups of 4,
    256-query items, 308-page tables of 16) a step of one 256-token chunk
    ending at 1,206 and seven decode items at 1,200 computes 5.8% of the
    whole walk: the chunk's eight 32-query tiles end at 982, 1,014, ...,
    1,206 and take 8, 8, 9, 9, 9, 9, 10, 10 blocks of 8 pages, each
    decode item 10; every step computes 128 rows."""
    q_pos = np.full((8, 256), -1)
    q_pos[0] = 950 + np.arange(256)
    q_pos[1:, 0] = 1199
    lengths = np.r_[1206, np.full(7, 1200)]
    read, table = pa.ragged_work(lengths, q_pos, 4, 8, 128, 16, 308)
    assert (read, table) == ((72 + 7 * 10) * 128 * 8, 8 * 1024 * 308)
    assert read / table < 0.1


def test_ragged_work_with_no_live_row_is_one_step():
    read, table = pa.ragged_work([0, 0], np.full((2, 4), -1), 2, 2, 8, 4, 3)
    assert (read, table) == (8 * 3, 2 * 8 * 3)


def test_padded_query_rows_do_not_contaminate():
    """Adding padded (qpos=-1) rows must not change the real rows."""
    q_lens, lengths = (2, 1), (6, 3)
    a_small = _case(3, 2, 2, 2, 2, 8, 4, 2, q_lens, lengths)
    out_small = paged_attention_ragged(*a_small, interpret=True)
    # same case embedded in a wider query block
    q, kp, ks, vp, vs, layer, table, ln, qpos = a_small
    pad = 3
    q_wide = jnp.concatenate(
        [q, jax.random.normal(jax.random.PRNGKey(9), (2, pad, 2, 2, 8))],
        axis=1)
    qpos_wide = jnp.concatenate(
        [qpos, jnp.full((2, pad), -1, jnp.int32)], axis=1)
    out_wide = paged_attention_ragged(q_wide, kp, ks, vp, vs, layer, table,
                                      ln, qpos_wide, interpret=True)
    valid = np.asarray(qpos) >= 0
    np.testing.assert_array_equal(np.asarray(out_small)[valid],
                                  np.asarray(out_wide)[:, :2][valid])


def test_causal_horizon_masking_is_exact():
    """Poisoning every kv row past each query's causal horizon (same-
    chunk future tokens, ragged page tails, the null page) with extreme
    codes/scales cannot move the output — masked rows get exactly zero
    weight."""
    q_lens, lengths = (3, 1), (5, 9)
    args = _case(5, 2, 3, 2, 2, 8, 4, 3, q_lens, lengths)
    q, kp, ks, vp, vs, layer, table, ln, qpos = args
    out = paged_attention_ragged(*args, interpret=True)
    # poison: every (page, row) whose logical position exceeds the MAX
    # qpos of its sequence, plus the whole null page (row r of a page:
    # codes [0, page, r], scales [0, page, :, r])
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    ks2, vs2 = np.asarray(ks).copy(), np.asarray(vs).copy()
    page_size = kp2.shape[2]
    tbl = np.asarray(table)
    for i in range(2):
        horizon = int(np.max(np.asarray(qpos)[i]))
        for j in range(tbl.shape[1]):
            page = tbl[i, j]
            for r in range(page_size):
                if page == 0 or j * page_size + r > horizon:
                    if page:
                        kp2[0, page, r] = 127
                        vp2[0, page, r] = -127
                        ks2[0, page, :, r] = 1e8
                        vs2[0, page, :, r] = 1e8
    kp2[0, 0], vp2[0, 0], ks2[0, 0], vs2[0, 0] = 127, -127, 1e8, 1e8
    out2 = paged_attention_ragged(q, jnp.asarray(kp2), jnp.asarray(ks2),
                                  jnp.asarray(vp2), jnp.asarray(vs2),
                                  layer, table, ln, qpos, interpret=True)
    valid = np.asarray(qpos) >= 0
    np.testing.assert_array_equal(np.asarray(out)[valid],
                                  np.asarray(out2)[valid])


def test_qlen1_reduces_to_decode_kernel():
    """A batch of q_len=1 rows with qpos = lengths-1 is exactly the
    decode kernel's contract; both must sit in the same rtol band vs
    the shared oracle semantics."""
    b, kvh, g, hd, page_size, n_ptab = 3, 2, 2, 8, 4, 3
    args = _case(7, b, 1, kvh, g, hd, page_size, n_ptab,
                 q_lens=(1, 1, 1), lengths=(5, 12, 1))
    q, kp, ks, vp, vs, layer, table, ln, qpos = args
    ragged = paged_attention_ragged(*args, interpret=True)
    decode = paged_attention_decode(q[:, 0], kp, ks, vp, vs, layer, table,
                                    ln, interpret=True)
    np.testing.assert_allclose(np.asarray(ragged[:, 0]),
                               np.asarray(decode), rtol=RTOL, atol=1e-6)


def _dequantized(codes, scales):
    """A stacked int8 pool leaf times its scales: the fp pool of the same
    values, in the same (1, n_pages, G, KVH·hd) layout."""
    page = codes.shape[2]
    sc = jnp.swapaxes(scales[..., :page], 2, 3)[..., None]  # (1, n, G, KVH, 1)
    rows = codes.reshape(sc.shape[:4] + (-1,)).astype(jnp.float32) * sc
    return rows.reshape(codes.shape)


def test_ops_dispatch_fp_pool_falls_back():
    """fp pools (scales None) must route to the jnp fallback and agree
    with a quantized pool dequantized up front."""
    args = _case(11, 2, 3, 2, 2, 8, 4, 2, (3, 2), (7, 4))
    q, kp, ks, vp, vs, layer, table, ln, qpos = args
    k_fp, v_fp = _dequantized(kp, ks), _dequantized(vp, vs)
    via_ops = ops.ragged_paged_attention(q, k_fp, None, v_fp, None, layer,
                                         table, ln, qpos)
    direct = paged_attention_ragged_fallback(q, k_fp, None, v_fp, None,
                                             layer, table, ln, qpos)
    np.testing.assert_array_equal(np.asarray(via_ops), np.asarray(direct))
    quant = ops.ragged_paged_attention(q, kp, ks, vp, vs, layer, table, ln,
                                       qpos)
    valid = np.asarray(qpos) >= 0
    np.testing.assert_allclose(np.asarray(via_ops)[valid],
                               np.asarray(quant)[valid],
                               rtol=1e-4, atol=1e-5)
