"""Pallas TPU kernel: the whole CAT serving hot path in ONE launch.

``fused_cat_matmul_w4`` runs block-CAT -> (sign ⊙) Hadamard -> dynamic
per-token asymmetric quantization -> W4A8 (or W8A8) matmul with the
dequant + zero-point epilogue as a single kernel. The unfused composition
(``ops.cat_transform_matmul``) round-trips three fp intermediates through
HBM per linear — transformed activations twice (block-CAT out, Hadamard
out) plus the int8 codes; here the activation tile is read from HBM
once, transformed and quantized in VMEM scratch, and the packed weight
is the only other HBM stream.

Dataflow per M-tile (grid (gm, gn, gk); K fastest, TPU iteration order):

    (j == 0 and kk == 0):                      # once per M-tile
        x (TM, D) --HBM--> VMEM
        block-CAT (static per-block dots) -> ⊙ combined-sign
        -> Hadamard (``hadamard.kron_rows``: two batched factor dots)
        -> per-token min/max -> scale/zp -> int8 codes
        -> qx scratch (TM, K_pad) int8, sx/zx scratch (TM, 1) f32
    every (j, kk):                             # the contraction
        qw block (TK/2, TN) packed --HBM--> VMEM -> unpack
        o[i,j] += sx·sw·(qx[:, kk·TK:..] @ qw − zx·colsum(qw))

The transform spans the FULL feature dim (CAT blocks / Hadamard factors
mix all of D), so the x block is always (TM, D) and the quantized codes
live in a (TM, K_pad) VMEM scratch revisited across the (N, K) grid —
Pallas only re-fetches x when the M index changes, so activations cross
HBM once per tile. Scratch columns past D are zeroed; the matching
padded weight rows are zero too, so the padding is doubly inert. Padded
M rows quantize an all-zero row to codes == zp and the epilogue cancels
them to exactly 0.

``fused_cat_gemv_w4`` is the decode-shaped sibling (M <= 8 rows kept
whole and VMEM-resident across an (N, K) grid), mirroring
``quant_matmul_w4.quant_gemv_w4``.

Numerics match composing the stand-alone kernels (all-f32 transform,
``ref.dynamic_quant`` signed-shifted codes, int32 accumulation) — the
oracle is ``ref.fused_cat_matmul_w4``; agreement is rtol-level (~1e-6)
because the in-kernel dots may associate differently from the composed
kernels' dots.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import tile_bytes, vmem_params
from .hadamard import kron_rows
from .quant_matmul_w4 import _GEMV_M, _unpack_block, int8_contract


def _transform_quant(x_ref, sign_ref, ha_ref, hb_ref, blocks_ref,
                     qx_ref, sx_ref, zx_ref, *, act_bits: int, k_pad: int):
    """Shared once-per-M-tile body: CAT transform + dynamic quant into the
    VMEM scratch refs. All-f32; codes are signed-shifted exactly like
    ``ref.dynamic_quant`` so the contraction epilogue matches the
    stand-alone W4A8 kernels."""
    x = x_ref[...].astype(jnp.float32)
    tm, d = x.shape
    if blocks_ref is not None:
        # block-diag CAT: y[:, i·k:(i+1)·k] = x_i @ B_iᵀ, statically
        # unrolled per block (blocks stay VMEM-resident across the grid)
        nblk, bk, _ = blocks_ref.shape
        parts = []
        for bi in range(nblk):
            xi = x[:, bi * bk:(bi + 1) * bk]
            parts.append(jnp.dot(xi, blocks_ref[bi].T,
                                 preferred_element_type=jnp.float32))
        x = jnp.concatenate(parts, axis=1)
    # combined elementwise vector: Hadamard randomization sign, with any
    # diagonal (Scale) CAT factor folded in by the dispatcher
    x = x * sign_ref[...].astype(jnp.float32)
    y = kron_rows(x, ha_ref[...].astype(jnp.float32),
                  hb_ref[...].astype(jnp.float32))
    # dynamic per-token asymmetric quant (ref.dynamic_quant semantics)
    levels = 2.0 ** act_bits - 1
    ymin = jnp.min(y, axis=-1, keepdims=True)
    ymax = jnp.max(y, axis=-1, keepdims=True)
    scale = jnp.maximum(ymax - ymin, 1e-12) / levels
    zp = jnp.round(-ymin / scale)
    q = jnp.clip(jnp.round(y / scale + zp), 0, levels) - 2.0 ** (act_bits - 1)
    zp = zp - 2.0 ** (act_bits - 1)
    if k_pad > d:   # zero the scratch tail (padded qw rows are zero too)
        q = jnp.concatenate(
            [q, jnp.zeros((tm, k_pad - d), jnp.float32)], axis=1)
    qx_ref[...] = q.astype(jnp.int8)
    sx_ref[...] = scale
    zx_ref[...] = zp


def _contract(qx_ref, sx_ref, zx_ref, w_ref, sw_ref, o_ref, *, kk, tk,
              packed: bool):
    """Per-(j, kk) contraction step against the quantized scratch codes
    (the ``quant_matmul_w4`` K-step body, reading qx from scratch)."""
    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    qx = qx_ref[:, pl.ds(kk * tk, tk)]
    qw = _unpack_block(w_ref[...]) if packed else w_ref[...]
    acc, colsum = int8_contract(qx, qw)
    o_ref[...] += (sx_ref[...] * sw_ref[...]
                   * (acc - zx_ref[...] * colsum)).astype(o_ref.dtype)


def _make_kernel(act_bits: int, packed: bool, has_blocks: bool, tk: int,
                 k_pad: int, gemv: bool):
    def kernel(*refs):
        if has_blocks:
            (x_ref, sign_ref, ha_ref, hb_ref, blocks_ref, w_ref, sw_ref,
             o_ref, qx_ref, sx_ref, zx_ref) = refs
        else:
            (x_ref, sign_ref, ha_ref, hb_ref, w_ref, sw_ref,
             o_ref, qx_ref, sx_ref, zx_ref) = refs
            blocks_ref = None
        j = pl.program_id(0) if gemv else pl.program_id(1)
        kk = pl.program_id(1) if gemv else pl.program_id(2)

        # transform + quantize ONCE per M-tile: the scratch persists
        # across the (N, K) sweep (grid iterates K fastest, then N, so
        # (j, kk) == (0, 0) is the first visit of each M-tile)
        @pl.when((j == 0) & (kk == 0))
        def _prep():
            _transform_quant(x_ref, sign_ref, ha_ref, hb_ref, blocks_ref,
                             qx_ref, sx_ref, zx_ref, act_bits=act_bits,
                             k_pad=k_pad)

        _contract(qx_ref, sx_ref, zx_ref, w_ref, sw_ref, o_ref, kk=kk,
                  tk=tk, packed=packed)

    return kernel


def _prep_operands(x, blocks, ha, hb, sign, qw, sw, packed, tm, tn, tk):
    """Shared padding/validation -> (padded operands, dims dict); the
    sign vector comes back as a (1, D) row."""
    m, d = x.shape
    if packed:
        k2, n = qw.shape
        assert k2 == (d + 1) // 2, (x.shape, qw.shape)
        k0 = 2 * k2
    else:
        k0, n = qw.shape
        assert k0 == d, (x.shape, qw.shape)
    assert ha.shape[0] * hb.shape[0] == d, (ha.shape, hb.shape, d)
    if blocks is not None:
        nblk, bk, _ = blocks.shape
        assert nblk * bk == d, (blocks.shape, d)
    pk = (-k0) % tk
    pn = (-n) % tn
    pm = (-m) % tm
    if pm:
        x = jnp.pad(x, ((0, pm), (0, 0)))
    if pk or pn:
        pk_rows = pk // 2 if packed else pk
        qw = jnp.pad(qw, ((0, pk_rows), (0, pn)))
        sw = jnp.pad(sw, ((0, 0), (0, pn)), constant_values=1.0)
    return x, sign.reshape(1, d), qw, sw, dict(m=m, d=d, n=n, k_pad=k0 + pk)


def _vmem_bytes(operands, tm: int, tn: int, tk: int, k_pad: int,
                packed: bool) -> int:
    """VMEM one grid step takes, in the chip's padded tiles: two buffers
    of each input block (the (tm, D) activation tile, the transform
    factors, the weight and scale blocks) and of the (tm, tn) f32 output
    block, the code scratch, and four f32 copies of the activation tile
    for the in-kernel transform. The (k, k) CAT blocks pad to 128 lanes:
    at k = 32 they take four times their size."""
    x, *factors = operands[:-2]
    d = x.shape[1]
    mats = sum(tile_bytes(*f.shape[-2:], 4) * math.prod(f.shape[:-2])
               for f in factors)
    ins = (tile_bytes(tm, d, x.dtype.itemsize) + mats
           + tile_bytes(tk // 2 if packed else tk, tn, 1)
           + tile_bytes(1, tn, 4))
    scratch = tile_bytes(tm, k_pad, 1) + 2 * tile_bytes(tm, 1, 4)
    return (2 * (ins + tile_bytes(tm, tn, 4)) + scratch
            + 4 * tile_bytes(tm, d, 4))


@functools.partial(jax.jit, static_argnames=("act_bits", "packed",
                                             "block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def fused_cat_matmul_w4(x, blocks, ha, hb, sign, qw, sw, *,
                        act_bits: int = 8, packed: bool = True,
                        block_m: int = 128, block_n: int = 256,
                        block_k: int = 512, out_dtype=jnp.float32,
                        interpret: bool = True) -> jnp.ndarray:
    """x (M, D) fp activations; blocks (n, k, k) CAT block factors (None
    for a diagonal/absent CAT stage — fold a ``Scale`` into ``sign``);
    ha/hb Kronecker Hadamard factors; sign (D,) elementwise pre-Hadamard
    vector; qw (ceil(D/2), N) nibble-packed int4 codes — or, with
    ``packed=False``, (D, N) int8 codes; sw (1, N) f32 -> (M, N).

    One pallas_call for the full transform->quant->matmul chain; see the
    module docstring for the dataflow. Odd D follows the packed-weight
    contract (inert zero high nibble; the scratch's matching column is
    explicitly zeroed)."""
    m, d = x.shape
    tm = min(block_m, max(8, m))
    tk = min(block_k, d + d % 2)
    tk += tk % 2
    tn = min(block_n, qw.shape[1])
    x, sign, qw, sw, dims = _prep_operands(x, blocks, ha, hb, sign, qw, sw,
                                           packed, tm, tn, tk)
    k_pad, n = dims["k_pad"], dims["n"]
    gm = x.shape[0] // tm
    gn = qw.shape[1] // tn
    gk = k_pad // tk
    has_blocks = blocks is not None
    kern = _make_kernel(act_bits, packed, has_blocks, tk, k_pad, gemv=False)
    in_specs = [
        pl.BlockSpec((tm, d), lambda i, j, kk: (i, 0)),
        pl.BlockSpec((1, d), lambda i, j, kk: (0, 0)),
        pl.BlockSpec(ha.shape, lambda i, j, kk: (0, 0)),
        pl.BlockSpec(hb.shape, lambda i, j, kk: (0, 0)),
    ]
    operands = [x, sign, ha, hb]
    if has_blocks:
        in_specs.append(pl.BlockSpec(blocks.shape, lambda i, j, kk: (0, 0, 0)))
        operands.append(blocks)
    in_specs += [
        pl.BlockSpec((tk // 2 if packed else tk, tn),
                     lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, tn), lambda i, j, kk: (0, j)),
    ]
    operands += [qw, sw]
    out = pl.pallas_call(
        kern,
        grid=(gm, gn, gk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], qw.shape[1]), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, k_pad), jnp.int8),
                        pltpu.VMEM((tm, 1), jnp.float32),
                        pltpu.VMEM((tm, 1), jnp.float32)],
        name="fused_cat_matmul_w4",
        compiler_params=vmem_params(_vmem_bytes(operands, tm, tn, tk, k_pad,
                                                packed)),
        interpret=interpret,
    )(*operands)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("act_bits", "packed",
                                             "block_n", "block_k",
                                             "out_dtype", "interpret"))
def fused_cat_gemv_w4(x, blocks, ha, hb, sign, qw, sw, *,
                      act_bits: int = 8, packed: bool = True,
                      block_n: int = 256, block_k: int = 512,
                      out_dtype=jnp.float32,
                      interpret: bool = True) -> jnp.ndarray:
    """Decode-shaped fused chain for M <= 8 single-token rows: the
    activation sliver (padded to 8 rows) is transformed + quantized into
    VMEM once and revisited across the whole (N, K) grid — the packed
    weight is the only HBM stream, as in ``quant_gemv_w4``."""
    m, d = x.shape
    assert m <= _GEMV_M, f"GEMV path is for M<=8 decode shapes, got M={m}"
    tk = min(block_k, d + d % 2)
    tk += tk % 2
    tn = min(block_n, qw.shape[1])
    x, sign, qw, sw, dims = _prep_operands(x, blocks, ha, hb, sign, qw, sw,
                                           packed, _GEMV_M, tn, tk)
    k_pad, n = dims["k_pad"], dims["n"]
    gn = qw.shape[1] // tn
    gk = k_pad // tk
    has_blocks = blocks is not None
    kern = _make_kernel(act_bits, packed, has_blocks, tk, k_pad, gemv=True)
    in_specs = [
        pl.BlockSpec((_GEMV_M, d), lambda j, kk: (0, 0)),
        pl.BlockSpec((1, d), lambda j, kk: (0, 0)),
        pl.BlockSpec(ha.shape, lambda j, kk: (0, 0)),
        pl.BlockSpec(hb.shape, lambda j, kk: (0, 0)),
    ]
    operands = [x, sign, ha, hb]
    if has_blocks:
        in_specs.append(pl.BlockSpec(blocks.shape, lambda j, kk: (0, 0, 0)))
        operands.append(blocks)
    in_specs += [
        pl.BlockSpec((tk // 2 if packed else tk, tn), lambda j, kk: (kk, j)),
        pl.BlockSpec((1, tn), lambda j, kk: (0, j)),
    ]
    operands += [qw, sw]
    out = pl.pallas_call(
        kern,
        grid=(gn, gk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((_GEMV_M, tn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((_GEMV_M, qw.shape[1]), out_dtype),
        scratch_shapes=[pltpu.VMEM((_GEMV_M, k_pad), jnp.int8),
                        pltpu.VMEM((_GEMV_M, 1), jnp.float32),
                        pltpu.VMEM((_GEMV_M, 1), jnp.float32)],
        name="fused_cat_gemv_w4",
        compiler_params=vmem_params(_vmem_bytes(operands, _GEMV_M, tn, tk,
                                                k_pad, packed)),
        interpret=interpret,
    )(*operands)
    return out[:m, :n]
