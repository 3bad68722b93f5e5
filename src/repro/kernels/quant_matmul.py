"""Pallas TPU kernel: int8×int8 quantized matmul with fused dequant epilogue.

y[m,n] = sx[m]·sw[n]·( Σ_k qx[m,k]·qw[k,n] − zpx[m]·Σ_k qw[k,n] )

The int8 contraction hits the MXU natively on v5e; the asymmetric
zero-point correction uses the per-k-tile column sum of qw (linear in k,
so each grid step adds its exact share — no cross-step scratch needed).
Output accumulation across the K grid dimension uses the standard
revisited-output pattern (out block index ignores k; initialized at k=0).

Grid: (M/TM, N/TN, K/TK). VMEM per step ≈ TM·TK + TK·TN int8 + TM·TN f32.
Defaults (256, 256, 512) ⇒ ~0.5 MB, leaving headroom for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .quant_matmul_w4 import int8_contract


def _qmm_kernel(x_ref, sx_ref, zx_ref, w_ref, sw_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc, colsum = int8_contract(x_ref[...], w_ref[...])
    sx = sx_ref[...]
    zx = zx_ref[...]
    sw = sw_ref[...]
    o_ref[...] += (sx * sw * (acc - zx * colsum)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def quant_matmul(qx: jnp.ndarray, sx: jnp.ndarray, zpx: jnp.ndarray,
                 qw: jnp.ndarray, sw: jnp.ndarray,
                 block_m: int = 256, block_n: int = 256, block_k: int = 512,
                 out_dtype=jnp.float32, interpret: bool = True) -> jnp.ndarray:
    """qx (M,K) int8, sx/zpx (M,1) f32, qw (K,N) int8, sw (1,N) f32 -> (M,N)."""
    m, k = qx.shape
    k2, n = qw.shape
    assert k == k2, (qx.shape, qw.shape)
    tm, tn, tk = min(block_m, m), min(block_n, n), min(block_k, k)
    pm, pn, pk = (-m) % tm, (-n) % tn, (-k) % tk
    if pm or pk:
        qx = jnp.pad(qx, ((0, pm), (0, pk)))
        sx = jnp.pad(sx, ((0, pm), (0, 0)), constant_values=1.0)
        zpx = jnp.pad(zpx, ((0, pm), (0, 0)))
    if pk or pn:
        qw = jnp.pad(qw, ((0, pk), (0, pn)))
        sw = jnp.pad(sw, ((0, 0), (0, pn)), constant_values=1.0)
    gm, gn, gk = qx.shape[0] // tm, qw.shape[1] // tn, qx.shape[1] // tk
    out = pl.pallas_call(
        _qmm_kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((tm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((tk, tn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, tn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qx.shape[0], qw.shape[1]), out_dtype),
        interpret=interpret,
    )(qx, sx, zpx, qw, sw)
    return out[:m, :n]
