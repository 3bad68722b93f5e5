"""Pallas TPU kernel: the whole decode-attention QKV prologue in ONE launch.

``decode_qkv_prologue`` extends ``fused_cat_gemv_w4``'s scratch dataflow
through everything that sits between the hidden state and the paged
attention kernel on the decode path: block-CAT/Hadamard transform ->
dynamic act quant -> packed W4A8 QKV GEMV -> RoPE(q, k) -> symmetric
int8 KV quantization -> scatter of the new K/V rows into the paged pool
via the scalar-prefetched page table. With it, a transformer layer's
decode attention block is exactly **two** Pallas launches: this prologue
and the existing online-softmax paged attention — the composed path's
XLA glue (rope, quantize, 4 scatter dispatches) disappears into the
prologue's epilogue.

Dataflow (grid (gn, gk, M) with the row axis r innermost so weight
blocks are DMA'd once per (j, kk) — Pallas skips re-fetch while the
block index is unchanged):

    (j, kk, r) == (0, 0, 0):                   # once per launch
        x (8, D) --HBM--> VMEM -> CAT -> sign ⊙ -> Hadamard
        -> per-token asym quant -> qx/sx/zx scratch
    every (j, kk) at r == 0:                   # the contraction
        qw block (TK/2, TN) --HBM--> VMEM -> unpack
        acc[:, j·TN:..] (+)= sx·sw·(qx @ qw − zx·colsum)
    last (j, kk) at r == 0:                    # the epilogue
        acc -> split q|k|v columns -> RoPE(q, k) with per-row positions
        -> q out; quantize_kv(k), quantize_kv(v) -> code/scale scratch
    last (j, kk), every r:                     # the paged scatter
        row r's (KVH·hd) codes -> sublane row_ids[r] of page
        page_ids[r]'s (G, KVH·hd) code block; its (KVH,) scales -> lane
        row_ids[r] of the page's (KVH, lanes) scale block

The four pool leaves come whole, every layer stacked, in the layout
``kernels/paged_attention.py`` reads (codes ``(L, n_pages, G, KVH·hd)``,
scales ``(L, n_pages, KVH, lanes)``), with the layer index as a
scalar-prefetch operand, and ride through ``input_output_aliases``, so
no layer is sliced out. A row is no block of its own in either leaf, so
each leaf is read a page at a time at the index it is written and only
the row's sublane (codes) or lane (scales) replaced: every row the grid
does not target keeps its prior content. Before the final (j, kk) sweep
the pool index maps park on the reserved null page (0, 0) — inert by
the pool contract, exactly like the composed path's padded
``paged_cache_update_quantized`` rows. Padded batch rows (M < 8) pass
``page_ids == row_ids == 0`` and land there too. Real rows target pages
of distinct slots, so no two of them read or write one page.

Numerics: the RoPE and KV-quant stages mirror ``models.layers.rope`` /
``quantize_kv`` op for op in f32 and the contraction is exact int32, but
XLA contracts the kernel's fused f32 chains (``x1·cos − x2·sin`` becomes
mul+FMA inside the jitted launch) so agreement with the eager
``ref.decode_qkv_prologue`` oracle is rtol ~1e-6, same caveat as
``fused_cat_matmul_w4``; the int8 KV codes — the values paged attention
actually reads — round identically and are pinned bitwise by the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_cat_matmul import _prep_operands, _transform_quant
from .paged_attention import _check_pool, _layer_operand
from .quant_matmul_w4 import _GEMV_M, _unpack_block, int8_contract


def _rope_rows(y, pos_f32, head_dim: int, theta: float):
    """RoPE over flat (M, H*hd) rows with per-row f32 positions (M, 1) —
    mirrors ``models.layers.rope`` op for op (all f32). Heads are static
    lane slices of the flat row: Mosaic cannot split the lane axis into
    (H, hd) for hd < 128."""
    m, hn = y.shape
    half = head_dim // 2
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1)
    freq = theta ** (-idx.astype(jnp.float32) / half)
    ang = pos_f32 * freq                        # (M, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    parts = []
    for h in range(0, hn, head_dim):
        x1, x2 = y[:, h:h + half], y[:, h + half:h + head_dim]
        parts += [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    return jnp.concatenate(parts, axis=-1)


def _quantize_kv_rows(t, bits: int):
    """``models.layers.quantize_kv`` op for op: symmetric codes + f32
    scales over the last axis of one head's (M, hd) rows. The codes stay
    f32 (integral values) until the scatter casts them to the pool's
    int8: Mosaic stores a head's rows into its lanes of the (M, KVH·hd)
    scratch only for 32-bit values."""
    qmax = 2.0 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    return jnp.clip(jnp.round(t / scale), -qmax - 1, qmax), scale


def _make_prologue_kernel(*, act_bits: int, packed: bool, has_blocks: bool,
                          tk: int, tn: int, k_pad: int, gn: int, gk: int,
                          n_q: int, n_kv: int, head_dim: int,
                          rope_theta: float, kv_bits: int):
    kvh = n_kv // head_dim

    def kernel(*refs):
        (_layer_ref, _pid_ref, rid_ref), refs = refs[:3], refs[3:]
        if has_blocks:
            (x_ref, sign_ref, ha_ref, hb_ref, blocks_ref, w_ref, sw_ref,
             pos_ref), refs = refs[:8], refs[8:]
        else:
            (x_ref, sign_ref, ha_ref, hb_ref, w_ref, sw_ref,
             pos_ref), refs = refs[:7], refs[7:]
            blocks_ref = None
        (ki_ref, ksi_ref, vi_ref, vsi_ref,            # aliased pool pages
         qo_ref, ko_ref, kso_ref, vo_ref, vso_ref,
         qx_ref, sx_ref, zx_ref, acc_ref,
         kq_ref, ks_ref, vq_ref, vs_ref) = refs
        j = pl.program_id(0)
        kk = pl.program_id(1)
        r = pl.program_id(2)
        last_jk = (j == gn - 1) & (kk == gk - 1)

        @pl.when((j == 0) & (kk == 0) & (r == 0))
        def _prep():
            _transform_quant(x_ref, sign_ref, ha_ref, hb_ref, blocks_ref,
                             qx_ref, sx_ref, zx_ref, act_bits=act_bits,
                             k_pad=k_pad)

        @pl.when(r == 0)
        def _contract():
            qw = _unpack_block(w_ref[...]) if packed else w_ref[...]
            acc, colsum = int8_contract(qx_ref[:, pl.ds(kk * tk, tk)], qw)
            part = sx_ref[...] * sw_ref[...] * (acc - zx_ref[...] * colsum)

            @pl.when(kk == 0)
            def _set():
                acc_ref[:, pl.ds(j * tn, tn)] = part

            @pl.when(kk != 0)
            def _add():
                acc_ref[:, pl.ds(j * tn, tn)] += part

        @pl.when(last_jk & (r == 0))
        def _epilogue():
            y = acc_ref[...]                        # (8, N_pad) f32
            posf = pos_ref[...].astype(jnp.float32)  # (8, 1)
            qo_ref[...] = _rope_rows(y[:, :n_q], posf, head_dim, rope_theta)
            k = _rope_rows(y[:, n_q:n_q + n_kv], posf, head_dim, rope_theta)
            v = y[:, n_q + n_kv:n_q + 2 * n_kv]
            for h in range(kvh):
                cols = slice(h * head_dim, (h + 1) * head_dim)
                kq_ref[:, cols], ks_ref[:, h, :] = _quantize_kv_rows(
                    k[:, cols], kv_bits)
                vq_ref[:, cols], vs_ref[:, h, :] = _quantize_kv_rows(
                    v[:, cols], kv_bits)

        @pl.when(last_jk)
        def _scatter():
            rid = rid_ref[r]
            # the row's codes into its sublane of the page's code block
            # (selected in f32: the int8 values round-trip exactly)
            sub = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape, 1)
            for row_ref, page_ref, out_ref in ((kq_ref, ki_ref, ko_ref),
                                               (vq_ref, vi_ref, vo_ref)):
                new = row_ref[pl.ds(r, 1), :][None]        # (1, 1, KVH·hd)
                old = page_ref[...].astype(jnp.float32)
                out_ref[...] = jnp.where(sub == rid, new, old).astype(
                    out_ref.dtype)
            # the row's (KVH, 1) scales into its lane of the scale block
            lane = jax.lax.broadcasted_iota(jnp.int32, kso_ref.shape, 2)
            kso_ref[...] = jnp.where(lane == rid, ks_ref[r][None],
                                     ksi_ref[...])
            vso_ref[...] = jnp.where(lane == rid, vs_ref[r][None],
                                     vsi_ref[...])

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "n_q", "head_dim", "rope_theta", "kv_bits", "act_bits", "packed",
    "block_n", "block_k", "interpret"))
def decode_qkv_prologue(x, blocks, ha, hb, sign, qw, sw,
                        k_pool, k_scale, v_pool, v_scale, layer,
                        page_ids, row_ids, positions, *,
                        n_q: int, head_dim: int, rope_theta: float,
                        kv_bits: int = 8, act_bits: int = 8,
                        packed: bool = True, block_n: int = 256,
                        block_k: int = 512, interpret: bool = True):
    """Fused decode QKV prologue: one launch from hidden rows to rope'd
    q plus the paged pool with the step's K/V rows scattered in.

    x           (B, D) fp normed hidden rows, B <= 8 (decode batch)
    blocks/ha/hb/sign  CAT transform operands (``fused_transform_operands``)
    qw          (ceil(D/2), N) packed int4 — or (D, N) int8 — QKV weight,
                N = n_q + 2·n_kv columns laid out [q | k | v]
    sw          (1, N) f32 weight scales
    k/v_pool    (L, n_pages, page_size, KVH·hd) int8 pool leaves
    k/v_scale   (L, n_pages, KVH, scale_lanes(page_size)) f32 pool
                leaves (``paged_attention.scale_lanes``)
    layer       int32 scalar: the layer whose pools take the rows
    page_ids    (B,) int32 physical page per row (0 = null page for
                padded/invalid rows — the write is inert)
    row_ids     (B,) int32 row within the page
    positions   (B,) int32 absolute position per row (RoPE angle)
    -> (q (B, n_q) f32 rope'd, k_pool', k_scale', v_pool', v_scale')

    The pool operands are aliased into the outputs (donated); rows not
    targeted by ``page_ids``/``row_ids`` keep their prior content.
    """
    m, d = x.shape
    assert m <= _GEMV_M, f"decode prologue is for B<=8 rows, got B={m}"
    n = qw.shape[1]
    n_kv = (n - n_q) // 2
    assert n_q + 2 * n_kv == n, (n_q, n)
    assert n_q % head_dim == 0 and n_kv % head_dim == 0, (n_q, n_kv,
                                                          head_dim)
    kvh = n_kv // head_dim
    _check_pool(k_pool, k_scale, kvh, head_dim)
    page_size, lanes = k_pool.shape[2], k_scale.shape[3]
    tk = min(block_k, d + d % 2)
    tk += tk % 2
    tn = min(block_n, n)
    x, sign, qw, sw, dims = _prep_operands(x, blocks, ha, hb, sign, qw, sw,
                                           packed, _GEMV_M, tn, tk)
    k_pad, n_pad = dims["k_pad"], qw.shape[1]
    gn = n_pad // tn
    gk = k_pad // tk

    def _pad8(v):
        v = jnp.asarray(v, jnp.int32)
        return jnp.pad(v, (0, _GEMV_M - v.shape[0])) if v.shape[0] < _GEMV_M \
            else v

    page_ids = _pad8(page_ids)
    row_ids = _pad8(row_ids)
    pos8 = _pad8(positions)[:, None]

    has_blocks = blocks is not None
    kern = _make_prologue_kernel(
        act_bits=act_bits, packed=packed, has_blocks=has_blocks, tk=tk,
        tn=tn, k_pad=k_pad, gn=gn, gk=gk, n_q=n_q, n_kv=n_kv,
        head_dim=head_dim, rope_theta=rope_theta, kv_bits=kv_bits)

    def _page_idx(j, kk, r, lyr, pid, rid):
        # park on the inert null page until the final (j, kk) sweep — the
        # only flushes that reach real rows carry the finished epilogue
        last = (j == gn - 1) & (kk == gk - 1)
        return lyr[0], jnp.where(last, pid[r], 0), 0, 0

    def const(*idx):
        return lambda j, kk, r, lyr, pid, rid: idx

    code_block = (pl.squeezed, 1, page_size, n_kv)
    scale_block = (pl.squeezed, 1, kvh, lanes)
    in_specs = [
        pl.BlockSpec((_GEMV_M, d), const(0, 0)),
        pl.BlockSpec((1, d), const(0, 0)),
        pl.BlockSpec(ha.shape, const(0, 0)),
        pl.BlockSpec(hb.shape, const(0, 0)),
    ]
    operands = [x, sign, ha, hb]
    if has_blocks:
        in_specs.append(pl.BlockSpec(blocks.shape, const(0, 0, 0)))
        operands.append(blocks)
    in_specs += [
        pl.BlockSpec((tk // 2 if packed else tk, tn),
                     lambda j, kk, r, lyr, pid, rid: (kk, j)),
        pl.BlockSpec((1, tn), lambda j, kk, r, lyr, pid, rid: (0, j)),
        pl.BlockSpec((_GEMV_M, 1), const(0, 0)),
        # aliased pool leaves, read a page at a time where written
        pl.BlockSpec(code_block, _page_idx),
        pl.BlockSpec(scale_block, _page_idx),
        pl.BlockSpec(code_block, _page_idx),
        pl.BlockSpec(scale_block, _page_idx),
    ]
    operands += [qw, sw, pos8, k_pool, k_scale, v_pool, v_scale]
    # alias indices count ALL pallas_call operands, scalar prefetch first:
    # layer=0, pid=1, rid=2, then `operands` — pools are the last four
    pool0 = 3 + len(operands) - 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # layer, page_ids, row_ids
        grid=(gn, gk, _GEMV_M),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((_GEMV_M, n_q), const(0, 0)),
            pl.BlockSpec(code_block, _page_idx),
            pl.BlockSpec(scale_block, _page_idx),
            pl.BlockSpec(code_block, _page_idx),
            pl.BlockSpec(scale_block, _page_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((_GEMV_M, k_pad), jnp.int8),      # act codes
            pltpu.VMEM((_GEMV_M, 1), jnp.float32),       # act scale
            pltpu.VMEM((_GEMV_M, 1), jnp.float32),       # act zero point
            pltpu.VMEM((_GEMV_M, n_pad), jnp.float32),   # qkv accumulator
            pltpu.VMEM((_GEMV_M, n_kv), jnp.float32),    # k codes
            pltpu.VMEM((_GEMV_M, kvh, 1), jnp.float32),  # k scales
            pltpu.VMEM((_GEMV_M, n_kv), jnp.float32),    # v codes
            pltpu.VMEM((_GEMV_M, kvh, 1), jnp.float32),  # v scales
        ],
    )
    q8, kp, ksc, vp, vsc = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((_GEMV_M, n_q), jnp.float32),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ],
        input_output_aliases={pool0: 1, pool0 + 1: 2, pool0 + 2: 3,
                              pool0 + 3: 4},
        name="decode_qkv_prologue",
        interpret=interpret,
    )(_layer_operand(layer), page_ids, row_ids, *operands)
    return q8[:m], kp, ksc, vp, vsc
