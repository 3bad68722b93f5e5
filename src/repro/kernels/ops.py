"""jit'd public wrappers for the Pallas kernels.

`interpret` defaults to True off-TPU (the kernels execute their bodies in
Python on CPU for validation); on a real TPU backend it flips to False and
the same BlockSpecs drive Mosaic codegen.

`cat_transform_matmul` composes the full paper serving hot path:
   block-CAT -> Hadamard -> dynamic per-token quant -> int8 matmul.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .block_matmul import block_diag_matmul
from .decode_layer import decode_qkv_prologue as _decode_qkv_prologue
from .dynamic_quant import dynamic_quant
from .fused_cat_matmul import fused_cat_gemv_w4, fused_cat_matmul_w4
from .hadamard import hadamard_transform
from .paged_attention import (paged_attention_decode,
                              paged_attention_fallback,
                              paged_attention_ragged,
                              paged_attention_ragged_fallback)
from .quant_matmul import quant_matmul
from .quant_matmul_w4 import _GEMV_M, quant_gemv_w4, quant_matmul_w4

_FALSY = ("", "0", "false", "no", "off")


def default_interpret() -> bool:
    """Whether pallas_call should run in interpret mode.

    ``REPRO_PALLAS_INTERPRET`` overrides in BOTH directions (``1`` forces
    interpret even on TPU — useful for oracle-exact debugging; ``0``
    forces Mosaic codegen); unset, interpret follows the backend so CPU
    CI executes every kernel body in Python instead of silently skipping
    kernel-vs-oracle coverage.
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env.strip().lower() not in _FALSY
    return jax.default_backend() != "tpu"


def use_fused_decode() -> bool:
    """Whether decode layers route through the two-launch fused path
    (``decode_qkv_prologue`` + paged attention).

    ``REPRO_DECODE_FUSED`` overrides in both directions (``1`` enables it
    off-TPU — interpret mode, used by the parity tests; ``0`` pins the
    composed path); unset, it follows the backend like the other fused
    kernels, so off-TPU golden fixtures keep the composed path's exact
    numerics.
    """
    env = os.environ.get("REPRO_DECODE_FUSED")
    if env is not None:
        return env.strip().lower() not in _FALSY
    return jax.default_backend() == "tpu"


def hadamard(x, ha, hb, sign=None, **kw):
    kw.setdefault("interpret", default_interpret())
    return hadamard_transform(x, ha, hb, sign, **kw)


def dyn_quant(x, bits: int = 8, symmetric: bool = False, **kw):
    kw.setdefault("interpret", default_interpret())
    return dynamic_quant(x, bits=bits, symmetric=symmetric, **kw)


def qmatmul(qx, sx, zpx, qw, sw, **kw):
    kw.setdefault("interpret", default_interpret())
    return quant_matmul(qx, sx, zpx, qw, sw, **kw)


def qmatmul_w4(qx, sx, zpx, qw_packed, sw, **kw):
    kw.setdefault("interpret", default_interpret())
    return quant_matmul_w4(qx, sx, zpx, qw_packed, sw, **kw)


def qgemv_w4(qx, sx, zpx, qw_packed, sw, **kw):
    kw.setdefault("interpret", default_interpret())
    return quant_gemv_w4(qx, sx, zpx, qw_packed, sw, **kw)


def block_matmul(x, blocks, **kw):
    kw.setdefault("interpret", default_interpret())
    return block_diag_matmul(x, blocks, **kw)


def cat_transform_matmul(x, blocks, ha, hb, sign, qw, sw,
                         act_bits: int = 4, packed_int4: bool = False,
                         axis_name=None, **kw):
    """The paper's deployed quantized linear layer, end to end:
    y ≈ W·T⁻¹·Q(T x) with T = H·M̂_block, weights pre-fused & pre-quantized.

    x (..., d) fp; blocks (n,k,k); qw (d, d_out) int8 — or, with
    ``packed_int4``, (ceil(d/2), d_out) nibble-packed int4 codes;
    sw (1, d_out) f32.

    ``axis_name`` marks a call from INSIDE shard_map on a tensor-parallel
    mesh axis: ``x`` is replicated (the CAT/Hadamard transform and the
    per-token act-quant scales span the full d, so they run globally) and
    ``qw`` is this device's K shard — whole packed bytes per shard. The
    matching slice of the quantized activation contracts locally (decode
    shapes still route to the GEMV kernel; M is unchanged by K sharding)
    and partial outputs psum over ``axis_name`` — the zero-point
    correction is linear in K, so per-shard ``sx·sw·(acc − zp·colsum)``
    terms sum exactly."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    xf = block_matmul(xf, blocks, **kw)
    xf = hadamard(xf, ha, hb, sign, **kw)
    qx, sx, zpx = dyn_quant(xf, bits=act_bits, symmetric=False, **kw)
    if axis_name is not None:
        k_local = qw.shape[0] * 2 if packed_int4 else qw.shape[0]
        if packed_int4:
            assert d % 2 == 0, "sharded packed serving needs even K"
        idx = jax.lax.axis_index(axis_name)
        qx = jax.lax.dynamic_slice_in_dim(qx, idx * k_local, k_local, axis=1)
    if packed_int4:
        # decode shapes (few single-token rows) serve straight from the
        # packed buffer via the GEMV kernel instead of the tiled matmul
        if qx.shape[0] <= _GEMV_M:
            y = qgemv_w4(qx, sx, zpx, qw, sw, **kw)
        else:
            y = qmatmul_w4(qx, sx, zpx, qw, sw, **kw)
    else:
        y = qmatmul(qx, sx, zpx, qw, sw, **kw)
    if axis_name is not None:
        y = jax.lax.psum(y, axis_name)
    return y.reshape(*lead, qw.shape[1]).astype(x.dtype)


def fused_transform_operands(t):
    """Decompose a CAT transform pytree into the fused kernel's
    ``(blocks, ha, hb, sign)`` operands, or None when it doesn't fit.

    Supported shapes (exactly what ``transforms.make_cat_block`` /
    ``make_hadamard`` build): a bare ``Hadamard``, or a ``Compose`` of
    (``Scale`` | ``BlockDiag``, ``Hadamard``). A diagonal ``Scale``
    factor folds into the pre-Hadamard sign vector (both are elementwise,
    so they commute). Anything else — ``Dense``, bare block transforms
    without a Hadamard stage, nested composes — returns None and the
    caller uses the composed per-kernel path.
    """
    from repro.core import transforms as T

    if isinstance(t, T.Hadamard):
        return None, t.ha, t.hb, t.sign
    if not isinstance(t, T.Compose) or len(t.parts) != 2:
        return None
    first, had = t.parts
    if not isinstance(had, T.Hadamard):
        return None
    if isinstance(first, T.Scale):
        return None, had.ha, had.hb, had.sign * first.s
    if isinstance(first, T.BlockDiag):
        return first.blocks, had.ha, had.hb, had.sign
    if isinstance(first, T.Identity):
        return None, had.ha, had.hb, had.sign
    return None


def fused_cat_matmul(x, blocks, ha, hb, sign, qw, sw, act_bits: int = 8,
                     packed: bool = True, axis_name=None, **kw):
    """Single-launch serving linear: y ≈ W·T⁻¹·Q(T x) with the whole
    transform -> quant -> W4A8 chain fused into one Pallas kernel
    (``kernels/fused_cat_matmul.py``): the activation tile crosses HBM
    once and the (packed) weight is the only other stream.

    Operands as in ``fused_cat_matmul_w4`` (get them from a transform
    pytree via ``fused_transform_operands``); ``packed=False`` contracts
    (D, N) int8 weight codes instead of nibble-packed int4. Block sizes
    come from the per-shape autotune cache (``kernels/autotune.py``)
    unless passed explicitly.

    ``axis_name`` marks a call from inside shard_map on a K-sharded mesh
    axis. The transform and per-token quant scales span the full feature
    dim, so they cannot tile with a K shard — the tp path composes the
    stand-alone kernels (global transform + quant, local K-slice
    contraction, exact psum) just like ``cat_transform_matmul``.
    """
    from . import autotune

    kw.setdefault("interpret", default_interpret())
    lead = x.shape[:-1]
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    if axis_name is not None:
        if blocks is not None:
            xf = block_matmul(xf, blocks, **kw)
            xf = hadamard(xf, ha, hb, sign, **kw)
        else:
            xf = hadamard(xf * sign.astype(xf.dtype), ha, hb, None, **kw)
        qx, sx, zpx = dyn_quant(xf, bits=act_bits, symmetric=False, **kw)
        k_local = qw.shape[0] * 2 if packed else qw.shape[0]
        if packed:
            assert d % 2 == 0, "sharded packed serving needs even K"
        idx = jax.lax.axis_index(axis_name)
        qx = jax.lax.dynamic_slice_in_dim(qx, idx * k_local, k_local, axis=1)
        if not packed:
            y = qmatmul(qx, sx, zpx, qw, sw, **kw)
        elif qx.shape[0] <= _GEMV_M:
            y = qgemv_w4(qx, sx, zpx, qw, sw, **kw)
        else:
            y = qmatmul_w4(qx, sx, zpx, qw, sw, **kw)
        y = jax.lax.psum(y, axis_name)
        return y.reshape(*lead, qw.shape[1]).astype(x.dtype)
    m, n = xf.shape[0], qw.shape[1]
    if xf.shape[0] <= _GEMV_M:
        if "block_n" not in kw or "block_k" not in kw:
            tn, tk = autotune.gemv_blocks(d, n, packed)
            kw.setdefault("block_n", tn)
            kw.setdefault("block_k", tk)
        y = fused_cat_gemv_w4(xf, blocks, ha, hb, sign, qw, sw,
                              act_bits=act_bits, packed=packed, **kw)
    else:
        if not {"block_m", "block_n", "block_k"} <= kw.keys():
            m_bucket = 1 << max(3, (m - 1).bit_length())
            key = ("fused", m_bucket, d, n, packed, kw["interpret"])

            def run(cand):
                tm, tn, tk = cand
                fused_cat_matmul_w4(
                    xf, blocks, ha, hb, sign, qw, sw, act_bits=act_bits,
                    packed=packed, block_m=tm, block_n=tn, block_k=tk,
                    interpret=kw["interpret"]).block_until_ready()

            tm, tn, tk = autotune.pick(key, m, d, n, packed, run=run)
            kw.setdefault("block_m", tm)
            kw.setdefault("block_n", tn)
            kw.setdefault("block_k", tk)
        y = fused_cat_matmul_w4(xf, blocks, ha, hb, sign, qw, sw,
                                act_bits=act_bits, packed=packed, **kw)
    return y.reshape(*lead, n).astype(x.dtype)


def decode_qkv_prologue(x, blocks, ha, hb, sign, qw, sw,
                        k_pool, k_scale, v_pool, v_scale, layer,
                        page_ids, row_ids, positions, *,
                        n_q: int, head_dim: int, rope_theta: float,
                        kv_bits: int = 8, act_bits: int = 8,
                        packed: bool = True, **kw):
    """One-launch decode QKV prologue (``kernels/decode_layer.py``):
    CAT -> dynamic quant -> W4A8 QKV GEMV -> RoPE -> int8 KV quant ->
    paged-pool scatter. Together with the paged-attention kernel this
    makes a decode layer's attention block exactly two launches.

    Takes the stacked pool leaves and the layer that takes the rows;
    returns (q (B, n_q) f32 rope'd, k_pool', k_scale', v_pool',
    v_scale') with the pool leaves donated through
    ``input_output_aliases``. Block sizes come from
    ``autotune.prologue_blocks`` unless passed explicitly.
    """
    from . import autotune

    kw.setdefault("interpret", default_interpret())
    if "block_n" not in kw or "block_k" not in kw:
        n_kv = (qw.shape[1] - n_q) // 2
        tn, tk = autotune.prologue_blocks(x.shape[-1], qw.shape[1], n_kv,
                                          packed)
        kw.setdefault("block_n", tn)
        kw.setdefault("block_k", tk)
    return _decode_qkv_prologue(
        x, blocks, ha, hb, sign, qw, sw, k_pool, k_scale, v_pool, v_scale,
        layer, page_ids, row_ids, positions, n_q=n_q, head_dim=head_dim,
        rope_theta=rope_theta, kv_bits=kv_bits, act_bits=act_bits,
        packed=packed, **kw)


def paged_attention(q, k_pages, k_scale, v_pages, v_scale, layer,
                    page_table, lengths, **kw):
    """Paged decode attention from layer ``layer`` of the stacked
    quantized KV page pool.

    Routes int8 pools to the Pallas kernel (page table + lengths ride as
    scalar-prefetch operands driving the per-page DMA; dequant + online
    softmax in VMEM) and fp pools — which carry no scales to stream — to
    the jnp gather fallback. See ``kernels/paged_attention.py``.
    """
    if k_scale is None or v_scale is None:
        return paged_attention_fallback(q, k_pages, k_scale, v_pages,
                                        v_scale, layer, page_table, lengths)
    kw.setdefault("interpret", default_interpret())
    return paged_attention_decode(q, k_pages, k_scale, v_pages, v_scale,
                                  layer, page_table, lengths, **kw)


def ragged_paged_attention(q, k_pages, k_scale, v_pages, v_scale, layer,
                           page_table, lengths, q_pos, **kw):
    """Mixed-q_len paged attention for the unified token-budget step:
    per-work-item query blocks against the page pool, with the
    per-(query, kv) causal mask applied inside the launch — prefill
    chunks and decode tokens share one kernel call. int8 pools go to the
    Pallas kernel, fp pools (no scales to stream) to the jnp fallback.
    """
    if k_scale is None or v_scale is None:
        return paged_attention_ragged_fallback(q, k_pages, k_scale,
                                               v_pages, v_scale, layer,
                                               page_table, lengths, q_pos)
    kw.setdefault("interpret", default_interpret())
    return paged_attention_ragged(q, k_pages, k_scale, v_pages, v_scale,
                                  layer, page_table, lengths, q_pos, **kw)


# ------------------------------------------------- tensor-parallel wrappers

def _w4_tp(kernel, qx, sx, zpx, qw_packed, sw, mesh, axis, kw):
    """Run a W4A8 kernel with the contraction sharded over ``axis``: qx
    splits on K, qw_packed on packed-K (whole bytes per shard), and the
    per-device partial — dequant and zero-point correction are both
    linear in K — psums to the exact full contraction."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.compat import shard_map

    kw.setdefault("interpret", default_interpret())
    tp = mesh.shape[axis]
    k = qx.shape[1]
    assert k % (2 * tp) == 0, (
        f"K={k} must split into whole packed bytes across {axis}={tp}")
    assert qw_packed.shape[0] * 2 == k, (qx.shape, qw_packed.shape)

    def body(qxl, sxl, zxl, qwl, swl):
        return jax.lax.psum(kernel(qxl, sxl, zxl, qwl, swl, **kw), axis)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, None), P(None, None),
                  P(axis, None), P(None, None)),
        out_specs=P(None, None), check_vma=False,
    )(qx, sx, zpx, qw_packed, sw)


def qmatmul_w4_tp(qx, sx, zpx, qw_packed, sw, *, mesh, axis: str = "model",
                  **kw):
    """K-sharded ``qmatmul_w4`` under shard_map with a psum over ``axis``."""
    return _w4_tp(quant_matmul_w4, qx, sx, zpx, qw_packed, sw, mesh, axis, kw)


def qgemv_w4_tp(qx, sx, zpx, qw_packed, sw, *, mesh, axis: str = "model",
                **kw):
    """K-sharded decode GEMV under shard_map with a psum over ``axis``."""
    return _w4_tp(quant_gemv_w4, qx, sx, zpx, qw_packed, sw, mesh, axis, kw)
