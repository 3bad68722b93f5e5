"""Pallas TPU kernel: int4-packed-weight quantized matmul (W4A8).

Weights arrive packed two nibbles per int8 byte along K (see
``repro.core.quantizers.pack_int4``: even K index -> low nibble, odd ->
high). The kernel unpacks in VMEM right before the contraction, so HBM->
VMEM weight traffic is halved vs the int8 kernel while the MXU still sees
an int8 contraction:

    y[m,n] = sx[m]·sw[n]·( Σ_k qx[m,k]·qw[k,n] − zpx[m]·Σ_k qw[k,n] )

Output accumulation across the K grid dimension reuses the revisited-output
pattern from ``quant_matmul.py`` (out block index ignores k; init at k=0);
the zero-point correction likewise uses the per-tile column sum of the
*unpacked* qw, which is linear in k.

Grid: (M/TM, N/TN, K/TK). Per step the packed weight block is (TK//2, TN)
int8 — half the bytes of the int8 kernel's (TK, TN). Nibble sign-extension
uses ((v & 0xF) ^ 8) - 8, which is portable across interpret and Mosaic.

``quant_gemv_w4`` is the decode-shaped sibling (M ∈ [1, 8] single-token
rows): no M grid — the activation sliver stays VMEM-resident across an
(N, K) grid and the packed weight is the only HBM stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _unpack_block(pw: jnp.ndarray) -> jnp.ndarray:
    """(TK//2, TN) packed int8 -> (TK, TN) int8 codes in [-8, 7]."""
    p = pw.astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8
    hi = (((p >> 4) & 0xF) ^ 8) - 8
    tk2, tn = p.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * tk2, tn).astype(jnp.int8)


def int8_contract(qx: jnp.ndarray, qw: jnp.ndarray):
    """int8 codes (TM, TK) @ (TK, TN) -> (f32 acc, f32 (1, TN) colsum).

    Both operands stay int8 into the dot: Mosaic lowers only int8 × int8
    -> int32 to the MXU and refuses a dot on int32 vectors."""
    acc = jnp.dot(qx, qw, preferred_element_type=jnp.int32)
    colsum = jnp.sum(qw.astype(jnp.int32), axis=0, keepdims=True)
    return acc.astype(jnp.float32), colsum.astype(jnp.float32)


def _w4_accumulate(x_ref, sx_ref, zx_ref, w_ref, sw_ref, o_ref, k):
    """Shared K-step body: unpack the packed weight block in VMEM, int8
    MXU contraction, dequant + zero-point epilogue into the revisited
    output block. ``k`` is this grid's K program id."""
    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc, colsum = int8_contract(x_ref[...], _unpack_block(w_ref[...]))
    sx = sx_ref[...]
    zx = zx_ref[...]
    sw = sw_ref[...]
    o_ref[...] += (sx * sw * (acc - zx * colsum)).astype(o_ref.dtype)


def _qmm_w4_kernel(x_ref, sx_ref, zx_ref, w_ref, sw_ref, o_ref):
    _w4_accumulate(x_ref, sx_ref, zx_ref, w_ref, sw_ref, o_ref,
                   pl.program_id(2))


def _gemv_w4_kernel(x_ref, sx_ref, zx_ref, w_ref, sw_ref, o_ref):
    _w4_accumulate(x_ref, sx_ref, zx_ref, w_ref, sw_ref, o_ref,
                   pl.program_id(1))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def quant_matmul_w4(qx: jnp.ndarray, sx: jnp.ndarray, zpx: jnp.ndarray,
                    qw_packed: jnp.ndarray, sw: jnp.ndarray,
                    block_m: int = 256, block_n: int = 256,
                    block_k: int = 512,
                    out_dtype=jnp.float32, interpret: bool = True
                    ) -> jnp.ndarray:
    """qx (M,K) int8 activation codes, sx/zpx (M,1) f32, qw_packed
    (ceil(K/2), N) int8 nibble-packed weight codes, sw (1,N) f32 -> (M,N).

    Odd K is allowed: the packed weight's final byte carries a zero high
    nibble and qx's K axis is zero-padded to match — both inert.
    """
    m, k = qx.shape
    k2, n = qw_packed.shape
    assert k2 == (k + 1) // 2, (qx.shape, qw_packed.shape)
    if k % 2:  # align qx's K with the padded nibble
        qx = jnp.pad(qx, ((0, 0), (0, 1)))
        k += 1
    # block_k counts UNPACKED K rows and must stay even so each packed
    # byte lands wholly inside one grid step.
    tm, tn = min(block_m, m), min(block_n, n)
    tk = min(block_k, k)
    tk += tk % 2
    pm, pn, pk = (-m) % tm, (-n) % tn, (-k) % tk
    if pm or pk:
        qx = jnp.pad(qx, ((0, pm), (0, pk)))
        sx = jnp.pad(sx, ((0, pm), (0, 0)), constant_values=1.0)
        zpx = jnp.pad(zpx, ((0, pm), (0, 0)))
    if pk or pn:
        qw_packed = jnp.pad(qw_packed, ((0, pk // 2), (0, pn)))
        sw = jnp.pad(sw, ((0, 0), (0, pn)), constant_values=1.0)
    gm, gn, gk = qx.shape[0] // tm, qw_packed.shape[1] // tn, qx.shape[1] // tk
    out = pl.pallas_call(
        _qmm_w4_kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((tm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((tk // 2, tn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, tn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qx.shape[0], qw_packed.shape[1]),
                                       out_dtype),
        interpret=interpret,
    )(qx, sx, zpx, qw_packed, sw)
    return out[:m, :n]


_GEMV_M = 8  # decode micro-batch rows kept VMEM-resident (f32 sublane tile)


@functools.partial(jax.jit, static_argnames=("block_n", "block_k",
                                             "out_dtype", "interpret"))
def quant_gemv_w4(qx: jnp.ndarray, sx: jnp.ndarray, zpx: jnp.ndarray,
                  qw_packed: jnp.ndarray, sw: jnp.ndarray,
                  block_n: int = 256, block_k: int = 512,
                  out_dtype=jnp.float32, interpret: bool = True
                  ) -> jnp.ndarray:
    """Decode-shaped W4A8 GEMV: same contraction as ``quant_matmul_w4``
    but for M ∈ [1, 8] rows (single-token decode over a few slots).

    The M axis is padded to 8 and kept whole — one VMEM-resident activation
    sliver revisited across the whole (N, K) grid, so the packed weight is
    the only HBM stream (the memory-bound regime where int4 packing pays:
    half the bytes of the int8 kernel per decoded token). Odd K follows
    the matmul kernel's contract (inert zero high nibble + zero-padded qx).
    """
    m, k = qx.shape
    k2, n = qw_packed.shape
    assert m <= _GEMV_M, f"GEMV path is for M<=8 decode shapes, got M={m}"
    assert k2 == (k + 1) // 2, (qx.shape, qw_packed.shape)
    if k % 2:
        qx = jnp.pad(qx, ((0, 0), (0, 1)))
        k += 1
    tn = min(block_n, n)
    tk = min(block_k, k)
    tk += tk % 2  # whole packed bytes per grid step
    pm, pn, pk = _GEMV_M - m, (-n) % tn, (-k) % tk
    if pm or pk:
        qx = jnp.pad(qx, ((0, pm), (0, pk)))
        sx = jnp.pad(sx, ((0, pm), (0, 0)), constant_values=1.0)
        zpx = jnp.pad(zpx, ((0, pm), (0, 0)))
    if pk or pn:
        qw_packed = jnp.pad(qw_packed, ((0, pk // 2), (0, pn)))
        sw = jnp.pad(sw, ((0, 0), (0, pn)), constant_values=1.0)
    gn, gk = qw_packed.shape[1] // tn, qx.shape[1] // tk
    out = pl.pallas_call(
        _gemv_w4_kernel,
        grid=(gn, gk),
        in_specs=[
            pl.BlockSpec((_GEMV_M, tk), lambda j, kk: (0, kk)),
            pl.BlockSpec((_GEMV_M, 1), lambda j, kk: (0, 0)),
            pl.BlockSpec((_GEMV_M, 1), lambda j, kk: (0, 0)),
            pl.BlockSpec((tk // 2, tn), lambda j, kk: (kk, j)),
            pl.BlockSpec((1, tn), lambda j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((_GEMV_M, tn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((_GEMV_M, qw_packed.shape[1]),
                                       out_dtype),
        interpret=interpret,
    )(qx, sx, zpx, qw_packed, sw)
    return out[:m, :n]
