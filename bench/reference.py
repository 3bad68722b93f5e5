"""Plain float32 reference of a CAT-W4A8 llama-family decoder.

Independent of the program: it imports nothing from ``src/`` and reads the
parameter tree only by its stated storage format, the one a checkpoint of
such a model has:

- every linear is ``qweight`` (ceil(d_in/2), d_out) int8, two int4 codes
  per byte along d_in (even row in the low nibble, odd row in the high
  one), ``scale`` (1, d_out) per output channel, and an input transform
  ``transform.parts = (BlockDiag(blocks (n, k, k)), Hadamard(ha, hb,
  sign))``: x -> block-diagonal map, times ``sign``, then the Kronecker
  Hadamard ``ha ⊗ hb``;
- activations entering a linear are quantized per token, asymmetric, to
  ``act_bits`` after the transform; keys and values are stored
  symmetric per (token, head) at ``kv_bits``;
- RMS norm with a (1 + w) gain and eps 1e-6, rotary embedding on the two
  halves of each head, grouped-query causal attention, SiLU-gated MLP,
  embedding scaled by sqrt(d_model), untied or tied output head.

Everything runs in float32 at highest matmul precision, one layer at a
time over one sequence at a time, so that only one layer's dequantized
weights are on the device at once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
# sequences are padded to a multiple of this many tokens, so that a run
# compiles a handful of shapes; padding rows come after every real row
# and the causal mask keeps them out of the real rows' results
BUCKET = 1024
# query rows per attention block: bounds the (heads, rows, keys) scores
Q_BLOCK = 512


def unpack_int4(packed: jnp.ndarray, d_in: int) -> jnp.ndarray:
    p = packed.astype(jnp.int32)
    lo = ((p & 15) ^ 8) - 8
    hi = (((p >> 4) & 15) ^ 8) - 8
    q = jnp.stack([lo, hi], axis=1).reshape(2 * p.shape[0], p.shape[1])
    return q[:d_in].astype(jnp.float32)


def dequant_linear(p) -> dict:
    """One linear's float32 operands: the dequantized weight (d_in, d_out)
    and the transform factors."""
    blockdiag, had = p.transform.parts
    d_in = blockdiag.blocks.shape[0] * blockdiag.blocks.shape[1]
    w = unpack_int4(p.qweight, d_in) * p.scale.astype(jnp.float32)
    return {"w": w, "blocks": blockdiag.blocks.astype(jnp.float32),
            "ha": had.ha.astype(jnp.float32),
            "hb": had.hb.astype(jnp.float32),
            "sign": had.sign.astype(jnp.float32)}


def act_quant(y: jnp.ndarray, bits: int) -> jnp.ndarray:
    levels = 2.0 ** bits - 1
    lo = jnp.min(y, axis=-1, keepdims=True)
    hi = jnp.max(y, axis=-1, keepdims=True)
    s = jnp.maximum(hi - lo, 1e-12) / levels
    zp = jnp.round(-lo / s)
    q = jnp.clip(jnp.round(y / s + zp), 0, levels)
    return (q - zp) * s


def kv_quant(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                    1e-12) / qmax
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax) * s


def linear(x: jnp.ndarray, lp: dict, act_bits: int) -> jnp.ndarray:
    m, d = x.shape
    n, k, _ = lp["blocks"].shape
    y = jnp.einsum("mnk,nak->mna", x.reshape(m, n, k), lp["blocks"],
                   precision=HI).reshape(m, d)
    y = y * lp["sign"]
    a, b = lp["ha"].shape[0], lp["hb"].shape[0]
    y = jnp.einsum("ij,mjk,lk->mil", lp["ha"], y.reshape(m, a, b),
                   lp["hb"], precision=HI).reshape(m, d)
    return jnp.dot(act_quant(y, act_bits), lp["w"], precision=HI)


def rms_norm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + w)


def rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (S, H, hd) at positions pos (S,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq[None, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v):
    """Causal grouped-query attention: q (S, H, hd), k/v (S, KV, hd)."""
    s, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    q = q * hd ** -0.5
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))
    return out.reshape(s, h, hd)


@functools.partial(jax.jit, static_argnames=("dims", "act_bits"))
def layer(x, lw, ln1, ln2, *, dims: tuple, act_bits: int):
    """One decoder layer over one padded sequence x (S, d)."""
    n_heads, n_kv, hd, theta, kv_bits = dims
    s = x.shape[0]
    pos = jnp.arange(s)
    h = rms_norm(x, ln1)
    q = linear(h, lw["wq"], act_bits).reshape(s, n_heads, hd)
    k = linear(h, lw["wk"], act_bits).reshape(s, n_kv, hd)
    v = linear(h, lw["wv"], act_bits).reshape(s, n_kv, hd)
    q = rope(q, pos, theta)
    k = kv_quant(rope(k, pos, theta), kv_bits)
    v = kv_quant(v, kv_bits)
    x = x + linear(attention(q, k, v).reshape(s, -1), lw["wo"], act_bits)
    h = rms_norm(x, ln2)
    g = linear(h, lw["wg"], act_bits)
    u = linear(h, lw["wu"], act_bits)
    return x + linear(jax.nn.silu(g) * u, lw["wd"], act_bits)


@jax.jit
def head(x, final_norm, unembed):
    return jnp.dot(rms_norm(x, final_norm), unembed, precision=HI)


@functools.partial(jax.jit, static_argnames=("d",))
def embed_rows(embed, tokens, *, d: int):
    return embed[tokens].astype(jnp.float32) * jnp.sqrt(jnp.float32(d))


def _layer_params(layers, i: int) -> dict:
    """Layer ``i`` of the (host or device) stacked tree, dequantized on
    the device."""
    out = {}
    for name in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
        p = jax.tree.map(lambda a: jnp.asarray(a[i]), layers[name])
        out[name] = dequant_linear(p)
    return out


def logits_at(cfg, params, seqs, rows, *, act_bits: int = 8,
              kv_bits: int = 8) -> list:
    """Reference logits of each sequence at the given rows.

    cfg: an object with d_model, n_heads, n_kv_heads, head_dim,
    rope_theta, n_layers, tie_embeddings. params: {"embed", "final_norm",
    "unembed" (when untied), "layers": stacked layer tree (host numpy or
    device)}. seqs: token arrays; rows: for each sequence the positions
    whose next-token logits are wanted. -> list of (len(rows_i), V) f32
    numpy arrays."""
    dims = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            float(cfg.rope_theta), kv_bits)
    embed = jnp.asarray(params["embed"])
    xs = []
    for toks in seqs:
        s = len(toks)
        pad = -(-s // BUCKET) * BUCKET
        t = np.zeros((pad,), np.int32)
        t[:s] = toks
        xs.append(embed_rows(embed, jnp.asarray(t), d=cfg.d_model))
    layers = params["layers"]
    for i in range(cfg.n_layers):
        lw = _layer_params(layers, i)
        ln1 = jnp.asarray(layers["ln1"][i], jnp.float32)
        ln2 = jnp.asarray(layers["ln2"][i], jnp.float32)
        xs = [layer(x, lw, ln1, ln2, dims=dims, act_bits=act_bits)
              for x in xs]
        del lw
    unembed = (jnp.asarray(params["embed"]).T if cfg.tie_embeddings
               else jnp.asarray(params["unembed"])).astype(jnp.float32)
    fn = jnp.asarray(params["final_norm"], jnp.float32)
    out = []
    for x, r in zip(xs, rows):
        r = np.asarray(r, np.int32)
        pad = -(-len(r) // 64) * 64
        rr = np.zeros((pad,), np.int32)
        rr[:len(r)] = r
        out.append(np.asarray(head(x[rr], fn, unembed))[:len(r)])
    return out
