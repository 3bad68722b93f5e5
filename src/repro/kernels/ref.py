"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors the semantics of the corresponding kernel in this
package exactly, including quantization rounding and accumulation dtypes.
"""
from __future__ import annotations

import jax.numpy as jnp

from .hadamard import kron_rows


def hadamard_transform(x: jnp.ndarray, ha: jnp.ndarray, hb: jnp.ndarray,
                       sign: jnp.ndarray | None = None) -> jnp.ndarray:
    """y = (x ⊙ sign) @ Hᵀ with H = ha ⊗ hb (orthonormal factors)."""
    a, b = ha.shape[0], hb.shape[0]
    if sign is not None:
        x = x * sign.astype(x.dtype)
    shape = x.shape
    xr = x.astype(jnp.float32).reshape(*shape[:-1], a, b)
    y = jnp.einsum("ij,...jk,lk->...il", ha.astype(jnp.float32), xr,
                   hb.astype(jnp.float32))
    return y.reshape(shape).astype(x.dtype)


def dynamic_quant(x: jnp.ndarray, bits: int = 8, symmetric: bool = False):
    """Per-token (last-axis) dynamic quantization.

    Returns (q int8, scale f32 (..., 1), zp f32 (..., 1)).
    Asymmetric: q in [0, 2^b - 1] stored offset-by-qmax... (int8-safe via
    shifting to signed range: q_signed = q - 2^(b-1)).
    """
    xf = x.astype(jnp.float32)
    if symmetric:
        qmax = 2.0 ** (bits - 1) - 1
        amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-12) / qmax
        zp = jnp.zeros_like(scale)
        q = jnp.clip(jnp.round(xf / scale), -qmax - 1, qmax)
    else:
        levels = 2.0**bits - 1
        xmin = jnp.min(xf, axis=-1, keepdims=True)
        xmax = jnp.max(xf, axis=-1, keepdims=True)
        scale = jnp.maximum(xmax - xmin, 1e-12) / levels
        zp = jnp.round(-xmin / scale)
        q = jnp.clip(jnp.round(xf / scale + zp), 0, levels)
        q = q - 2.0 ** (bits - 1)  # shift to signed storage
        zp = zp - 2.0 ** (bits - 1)
    return q.astype(jnp.int8), scale, zp


def quant_matmul(qx: jnp.ndarray, sx: jnp.ndarray, zpx: jnp.ndarray,
                 qw: jnp.ndarray, sw: jnp.ndarray,
                 out_dtype=jnp.float32) -> jnp.ndarray:
    """y[m,n] = sx[m]·sw[n]·( Σ_k qx[m,k]·qw[k,n] − zpx[m]·Σ_k qw[k,n] ).

    qx: (M, K) int8 (signed-shifted codes), sx/zpx: (M, 1) f32,
    qw: (K, N) int8, sw: (1, N) f32.
    """
    acc = jnp.dot(qx.astype(jnp.int32), qw.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    colsum = jnp.sum(qw.astype(jnp.int32), axis=0, keepdims=True)
    y = sx * sw * (acc.astype(jnp.float32) - zpx * colsum.astype(jnp.float32))
    return y.astype(out_dtype)


def unpack_int4(packed: jnp.ndarray, k: int | None = None) -> jnp.ndarray:
    """(K//2, N) nibble-packed int8 -> (K, N) int8 codes in [-8, 7].

    Delegates to the canonical layout in repro.core.quantizers so the
    storage contract lives in exactly one place (the kernel's in-VMEM
    _unpack_block is validated against this oracle by the tests).
    """
    from repro.core.quantizers import unpack_int4 as _unpack
    return _unpack(packed, k, axis=0)


def quant_matmul_w4(qx: jnp.ndarray, sx: jnp.ndarray, zpx: jnp.ndarray,
                    qw_packed: jnp.ndarray, sw: jnp.ndarray,
                    out_dtype=jnp.float32) -> jnp.ndarray:
    """W4A8 oracle: unpack the int4 weight codes, then int8 quant_matmul.

    qx: (M, K) int8, sx/zpx: (M, 1) f32, qw_packed: (ceil(K/2), N) int8,
    sw: (1, N) f32.
    """
    qw = unpack_int4(qw_packed, qx.shape[1])
    return quant_matmul(qx, sx, zpx, qw, sw, out_dtype=out_dtype)


def quant_gemv_w4(qx: jnp.ndarray, sx: jnp.ndarray, zpx: jnp.ndarray,
                  qw_packed: jnp.ndarray, sw: jnp.ndarray,
                  out_dtype=jnp.float32) -> jnp.ndarray:
    """Decode-shaped W4A8 GEMV oracle (M ∈ [1, 8] rows).

    The math is exactly ``quant_matmul_w4`` — the kernel differs only in
    blocking (M resident in VMEM, no M grid) — so the oracle delegates;
    a separate name keeps the kernel↔oracle pairing one-to-one."""
    from repro.kernels.quant_matmul_w4 import _GEMV_M
    assert qx.shape[0] <= _GEMV_M, qx.shape
    return quant_matmul_w4(qx, sx, zpx, qw_packed, sw, out_dtype=out_dtype)


def paged_attention_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                           k_scale, v_pages: jnp.ndarray, v_scale, layer,
                           page_table: jnp.ndarray,
                           lengths: jnp.ndarray) -> jnp.ndarray:
    """Paged decode-attention oracle (mirrors kernels.paged_attention).

    q (B, KVH, g, hd); k/v_pages (L, n_pages, G, KVH·hd) int8 codes (or
    fp when the matching scale is None); k/v_scale (L, n_pages, KVH,
    lanes) f32 (``kernels.paged_attention.scale_lanes``); layer the
    int32 layer index; page_table (B, n_ptab) int32;
    lengths (B,) valid kv rows per slot.
    Gathers each slot's logical sequence, dequantizes, and runs a masked
    f32 softmax — positions >= lengths[b] (ragged last pages, null-page
    entries) get exactly zero weight.

    Delegates to the canonical jnp gather path so the semantics live in
    exactly one place (same pattern as ``unpack_int4`` above); the Pallas
    kernel's online-softmax reformulation is what gets validated against
    this."""
    from repro.kernels.paged_attention import paged_attention_fallback
    return paged_attention_fallback(q, k_pages, k_scale, v_pages, v_scale,
                                    layer, page_table, lengths)


def paged_attention_ragged(q: jnp.ndarray, k_pages, k_scale,
                           v_pages: jnp.ndarray, v_scale, layer,
                           page_table: jnp.ndarray, lengths: jnp.ndarray,
                           q_pos: jnp.ndarray) -> jnp.ndarray:
    """Ragged (mixed q_len) paged-attention oracle.

    q (B, Q, KVH, g, hd) per-work-item query blocks; q_pos (B, Q)
    absolute positions (-1 = padding row, fully masked); other operands
    as in ``paged_attention_decode``. Each query row attends exactly the
    kv rows at positions <= its own — the per-(query, kv) causal test
    that makes prefill chunks and decode tokens composable in one batch.

    Delegates to the canonical jnp gather path (same pattern as
    ``paged_attention_decode``); the Pallas kernel's per-page
    online-softmax reformulation is what gets validated against this."""
    from repro.kernels.paged_attention import paged_attention_ragged_fallback
    return paged_attention_ragged_fallback(q, k_pages, k_scale, v_pages,
                                           v_scale, layer, page_table,
                                           lengths, q_pos)


def block_diag_matmul(x: jnp.ndarray, blocks: jnp.ndarray) -> jnp.ndarray:
    """y = x @ Tᵀ for block-diagonal T = Diag(B_1..B_n); blocks (n, k, k).
    y[..., i, a] = Σ_b blocks[i, a, b] · x[..., i, b]."""
    n, k, _ = blocks.shape
    shape = x.shape
    xb = x.astype(jnp.float32).reshape(*shape[:-1], n, k)
    yb = jnp.einsum("...nk,nak->...na", xb, blocks.astype(jnp.float32))
    return yb.reshape(shape).astype(x.dtype)


def fused_hadamard_quant(x, ha, hb, sign, bits: int = 8):
    """Online-transform hot path: Hadamard then per-token dynamic quant."""
    y = hadamard_transform(x, ha, hb, sign)
    return dynamic_quant(y, bits=bits, symmetric=False)


def kernel_transform_quant(x, blocks, ha, hb, sign, *, act_bits: int = 8):
    """CAT transform + dynamic quant in the KERNEL's exact op order.

    Mirrors ``fused_cat_matmul._transform_quant`` operation for operation
    (per-block dots, the kernels' own ``hadamard.kron_rows`` walk, then
    ``dynamic_quant`` rounding) instead of
    ``hadamard_transform``'s single einsum — f32 dot association is the
    only difference, and matching it makes oracles built on this helper
    **bitwise** against the fused kernels rather than rtol-close.
    Returns (q int8 (M, D), scale f32 (M, 1), zp f32 (M, 1)).
    """
    xf = x.astype(jnp.float32)
    if blocks is not None:
        nblk, bk, _ = blocks.shape
        parts = [jnp.dot(xf[:, bi * bk:(bi + 1) * bk],
                         blocks[bi].astype(jnp.float32).T,
                         preferred_element_type=jnp.float32)
                 for bi in range(nblk)]
        xf = jnp.concatenate(parts, axis=1)
    xf = xf * sign.astype(jnp.float32)
    y = kron_rows(xf, ha.astype(jnp.float32), hb.astype(jnp.float32))
    return dynamic_quant(y, bits=act_bits, symmetric=False)


def decode_qkv_prologue(x, blocks, ha, hb, sign, qw, sw,
                        k_pool, k_scale, v_pool, v_scale, layer,
                        page_ids, row_ids, positions, *,
                        n_q: int, head_dim: int, rope_theta: float,
                        kv_bits: int = 8, act_bits: int = 8,
                        packed: bool = True):
    """Oracle for ``kernels.decode_layer.decode_qkv_prologue`` — the
    one-launch decode QKV prologue (CAT -> quant -> W4A8 QKV GEMV ->
    RoPE -> KV int8 quant -> paged scatter).

    Composes ``kernel_transform_quant`` (kernel op order) + the exact
    int32 ``quant_matmul`` + ``models.layers.rope`` + ``quantize_kv`` +
    the paged scatter (``_put_code_rows``, ``_put_scale_rows``) into
    layer ``layer`` of the stacked pool leaves. Agreement with the
    kernel is rtol ~1e-6 on the f32 outputs (XLA FMA-contracts the
    fused mul/sub chains inside the jitted launch; this eager
    composition keeps them separate) while the scattered int8 KV codes
    round identically and match bitwise. The kernel additionally parks
    padded batch rows and intermediate flushes on the null page — page 0
    is outside the contract and excluded from comparison.
    """
    from repro.models.layers import (_put_code_rows, _put_scale_rows,
                                     quantize_kv, rope)

    m, _ = x.shape
    n = qw.shape[1]
    n_kv = (n - n_q) // 2
    kvh = n_kv // head_dim
    q8, sx, zx = kernel_transform_quant(x, blocks, ha, hb, sign,
                                        act_bits=act_bits)
    w = unpack_int4(qw, x.shape[1]) if packed else qw
    y = quant_matmul(q8, sx, zx, w, sw)
    pos = positions.astype(jnp.int32)[:, None]                  # (M, 1)
    q = rope(y[:, :n_q].reshape(m, 1, n_q // head_dim, head_dim),
             pos, theta=rope_theta).reshape(m, n_q)
    k = rope(y[:, n_q:n_q + n_kv].reshape(m, 1, kvh, head_dim),
             pos, theta=rope_theta).reshape(m, kvh, head_dim)
    v = y[:, n_q + n_kv:].reshape(m, kvh, head_dim)
    kq, ks = quantize_kv(k, bits=kv_bits)
    vq, vs = quantize_kv(v, bits=kv_bits)
    at = (layer, page_ids.astype(jnp.int32), row_ids.astype(jnp.int32))
    return (q, _put_code_rows(k_pool, kq, *at),
            _put_scale_rows(k_scale, ks, *at),
            _put_code_rows(v_pool, vq, *at),
            _put_scale_rows(v_scale, vs, *at))


def fused_cat_matmul_w4(x, blocks, ha, hb, sign, qw, sw, *,
                        act_bits: int = 8, packed: bool = True,
                        out_dtype=jnp.float32) -> jnp.ndarray:
    """Oracle for the single-launch fused serving chain
    (``kernels.fused_cat_matmul``): block-CAT -> (sign ⊙) Hadamard ->
    dynamic per-token asymmetric quant -> W4A8 (or W8A8 with
    ``packed=False``) matmul with the zero-point epilogue.

    x (M, D) fp; blocks (n, k, k) or None; sign (D,) combined elementwise
    vector (Hadamard sign with any Scale CAT factor folded in); qw
    (ceil(D/2), N) packed int4 codes or (D, N) int8 codes; sw (1, N) f32.
    Composes the stand-alone oracles above, so agreement with the fused
    kernel is rtol-level (dot association differs), not bitwise.
    """
    xf = x.astype(jnp.float32)
    if blocks is not None:
        xf = block_diag_matmul(xf, blocks)
    q, s, zp = fused_hadamard_quant(xf, ha, hb, sign, bits=act_bits)
    if packed:
        return quant_matmul_w4(q, s, zp, qw, sw, out_dtype=out_dtype)
    return quant_matmul(q, s, zp, qw, sw, out_dtype=out_dtype)
