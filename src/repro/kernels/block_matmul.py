"""Pallas TPU kernel: block-diagonal matmul — the online CAT transform.

y[..., i·k:(i+1)·k] = x[..., i·k:(i+1)·k] @ B_iᵀ  for blocks (n, k, k).

The grid walks (token-tile × lane group). A lane group holds g whole
blocks spanning a multiple of 128 lanes (Mosaic's lane tile), so CAT
blocks narrower than 128 (k=32 → g=4) still tile; with the paper's k=128
each group is exactly one block and one MXU tile. The working set stays
tiny (TM·g·k in + g·k² weights + TM·g·k out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bdm_kernel(x_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)           # (TM, g·k)
    g, k, _ = b_ref.shape
    parts = [jnp.dot(x[:, i * k:(i + 1) * k], b_ref[i].astype(jnp.float32).T,
                     preferred_element_type=jnp.float32) for i in range(g)]
    o_ref[...] = jnp.concatenate(parts, axis=1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_tokens", "interpret"))
def block_diag_matmul(x: jnp.ndarray, blocks: jnp.ndarray,
                      block_tokens: int = 512, interpret: bool = True):
    """x (..., n·k), blocks (n, k, k) -> y = x @ blockdiag(B)ᵀ."""
    n, k, _ = blocks.shape
    d = n * k
    assert x.shape[-1] == d, (x.shape, blocks.shape)
    lead = x.shape[:-1]
    xf = x.reshape(-1, d)
    m = xf.shape[0]
    tm = min(block_tokens, max(m, 1))
    pad = (-m) % tm
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    # fewest whole blocks per lane group that fill 128-lane tiles; the
    # whole row when none divides n
    g = next((g for g in range(1, n + 1) if n % g == 0 and g * k % 128 == 0),
             n)
    grid = (xf.shape[0] // tm, n // g)
    out = pl.pallas_call(
        _bdm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, g * k), lambda i, j: (i, j)),
            pl.BlockSpec((g, k, k), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, g * k), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, blocks)
    if pad:
        out = out[:m]
    return out.reshape(*lead, d)
