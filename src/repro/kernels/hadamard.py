"""Pallas TPU kernel: blocked Walsh–Hadamard transform.

TPU-native formulation (DESIGN.md §3): H_d = H_a ⊗ H_b, so the transform
of a token X_t viewed as an (a, b) matrix is H_a · X_t · H_bᵀ — two small
dense matmuls. The factor matrices (≤ 192×192 for every assigned dim)
stay resident in VMEM across the grid.

Mosaic refuses to split the lane axis of a (TM, d) tile into (a, b)
with b < 128, so ``kron_rows`` walks the factors on the TRANSPOSED tile
instead: d moves to the sublane axis, where splitting it into (a, b) is
a tile-aligned reshape (a, b multiples of 8), and each factor becomes a
batched matmul over the other factor's index.

Grid: one program per TM-token tile. VMEM per step ≈ TM·d·4 B ·2 (in+out)
+ a² + b² floats; TM=256, d=4096 ⇒ ~8.4 MB < 16 MB v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _batched(h, x):
    """h (n, n) applied to every x[i] (n, t): (B, n, t) -> (B, n, t)."""
    hh = jnp.broadcast_to(h, (x.shape[0],) + h.shape)
    return jax.lax.dot_general(hh, x, (((2,), (1,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def kron_rows(x, ha, hb):
    """y = x @ (ha ⊗ hb)ᵀ for f32 rows x (TM, a·b), all f32.

    Shared by every kernel that applies the Hadamard stage and by the
    op-order oracle ``ref.kernel_transform_quant``, so the two associate
    their f32 dots identically."""
    tm, d = x.shape
    a, b = ha.shape[0], hb.shape[0]
    xt = x.T.reshape(a, b, tm)                     # xt[i, j] = X_t[i, j]
    z = _batched(hb, xt)                           # X_t · hbᵀ, per i
    y = _batched(ha, jnp.swapaxes(z, 0, 1))        # ha · (...), per j
    return jnp.swapaxes(y, 0, 1).reshape(d, tm).T


def _hadamard_kernel(x_ref, sign_ref, ha_ref, hb_ref, o_ref):
    x = x_ref[...].astype(jnp.float32) * sign_ref[...].astype(jnp.float32)
    y = kron_rows(x, ha_ref[...].astype(jnp.float32),
                  hb_ref[...].astype(jnp.float32))
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_tokens", "interpret"))
def hadamard_transform(x: jnp.ndarray, ha: jnp.ndarray, hb: jnp.ndarray,
                       sign: jnp.ndarray | None = None,
                       block_tokens: int = 256,
                       interpret: bool = True) -> jnp.ndarray:
    """y = (x ⊙ sign) @ (ha ⊗ hb)ᵀ for x of shape (..., d). Tokens are
    padded up to a multiple of block_tokens (cheap; removed after)."""
    a, b = ha.shape[0], hb.shape[0]
    d = a * b
    assert x.shape[-1] == d, (x.shape, a, b)
    if sign is None:
        sign = jnp.ones((d,), jnp.float32)
    lead = x.shape[:-1]
    xf = x.reshape(-1, d)
    m = xf.shape[0]
    tm = min(block_tokens, max(m, 1))
    tm += (-tm) % 8
    pad = (-m) % tm
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    grid = (xf.shape[0] // tm,)
    out = pl.pallas_call(
        _hadamard_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((a, a), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, sign.reshape(1, d), ha, hb)
    if pad:
        out = out[:m]
    return out.reshape(*lead, d)
