"""CAT-W4A8 serving parameters made on the device from a seed.

The steps are those of ``repro.core.pipeline.quantize_model(transform="cat",
w_bits=4, a_bits=8)``, done one layer at a time so that no layer's float
weights outlive its quantization:

1. draw the layer's float32 weights from the seed (``N(0, 1/d_in)``, as
   ``models.dense.init`` draws them);
2. calibrate: run the float layer (``models.dense._layer_body``) on a
   seeded calibration batch and keep its four tap activations;
3. per group (attn_in, o_in, mlp_in, down_in) accumulate only the k x k
   diagonal blocks of Sigma_w and Sigma_x in float32 on the device, and
   build the block CAT transform from them with the constructor's own
   pieces (``cat.cat_optimal`` per block, ``transforms.BlockDiag``,
   ``transforms.make_hadamard``), which is what
   ``transforms.make_cat_block`` builds from the full matrices;
4. fuse T^-1 into each member weight, RTN-quantize to int4 with the L2.4
   range search, and pack two codes per byte (``pipeline._quantize_weight``
   and ``pipeline._make_qlinear``).

The result has the pytree structure, dtypes and shapes of
``quantize_model``'s output (checked on the CPU in
``bench/tests/test_bench_params.py``). No d x d matrix is ever formed and
nothing goes through the host except the Hadamard signs, which are drawn
from a numpy generator in the pipeline's order.

``serving_params`` then runs the model's own ``make_serving_params`` (the
QKV and gate|up concatenation and the weight-code column sums that
``ServeEngine`` computes when it is built) as one jitted program that
consumes the quantized tree. ``ServeEngine`` finds the work done and
leaves the tree as it is. Run eagerly, as the engine runs it, the step
unpacks each concatenated weight of all layers to int32 at once: at
Mistral-NeMo widths the gate|up weight alone is 2.19 GiB per temporary,
and the chip runs out of memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CALIB_SEQS, CALIB_LEN = 8, 64       # quantize_model's calibration set size
# output channels quantized per chunk: the L2.4 range search evaluates 64
# clipping ranges per weight, so a whole 14336 x 5120 weight at once would
# hold 64 copies of it
QUANT_ROWS = 256


def qconfig(cfg):
    from repro.core.pipeline import QuantizeConfig
    return QuantizeConfig(w_bits=4, a_bits=8, transform="cat",
                          cat_block=min(cfg.cat_block, 32))


def group_dims(cfg) -> dict:
    """group -> (d_in, member weights and their output widths)."""
    from repro.core.pipeline import layer_groups
    widths = {"wq": cfg.q_dim, "wk": cfg.kv_dim, "wv": cfg.kv_dim,
              "wo": cfg.d_model, "wg": cfg.d_ff, "wu": cfg.d_ff,
              "wd": cfg.d_model}
    d_in = {"attn_in": cfg.d_model, "o_in": cfg.q_dim,
            "mlp_in": cfg.d_model, "down_in": cfg.d_ff}
    return {g.tap: (d_in[g.tap], tuple((w, widths[w]) for w in g.weights))
            for g in layer_groups(cfg)}


def hadamards(cfg, seed: int) -> dict:
    """group -> (L,)-stacked Hadamard operands (ha, hb, sign), drawn in
    quantize_model's order (group-major, then layer)."""
    from repro.core import transforms as T
    rng = np.random.default_rng(seed)
    out = {}
    for tap, (d, _) in group_dims(cfg).items():
        hs = [T.make_hadamard(d, rng) for _ in range(cfg.n_layers)]
        out[tap] = tuple(jnp.stack([getattr(h, f) for h in hs])
                         for f in ("ha", "hb", "sign"))
    return out


def _diag_blocks_gram(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """(d, m) -> (d/k, k, k): the diagonal k x k blocks of a @ a.T."""
    d, m = a.shape
    ab = a.reshape(d // k, k, m)
    return jnp.einsum("nkm,njm->nkj", ab, ab,
                      precision=jax.lax.Precision.HIGHEST)


def _quantize(vf: jnp.ndarray, qcfg):
    """Chunked ``pipeline._quantize_weight``: per-output-channel scales make
    chunks of output channels independent, so the codes are the same."""
    from repro.core.pipeline import _quantize_weight
    d_in, d_out = vf.shape
    rows = next(r for r in range(min(QUANT_ROWS, d_out), 0, -1)
                if d_out % r == 0)
    chunks = vf.T.reshape(d_out // rows, rows, d_in)
    codes, scale = jax.lax.map(
        lambda c: _quantize_weight(c.T, None, qcfg), chunks)
    return (codes.transpose(1, 0, 2).reshape(d_in, d_out),
            scale.reshape(1, d_out))


def _cat_transform(sw, sx, had):
    from repro.core import cat
    from repro.core import transforms as T
    blocks = jax.vmap(cat.cat_optimal)(sw, sx)
    return T.Compose((T.BlockDiag(blocks, cat.inv_blocks(blocks)),
                      T.Hadamard(*had)))


def _layer_weights(cfg, key) -> dict:
    dims = {"wq": (cfg.d_model, cfg.q_dim), "wk": (cfg.d_model, cfg.kv_dim),
            "wv": (cfg.d_model, cfg.kv_dim), "wo": (cfg.q_dim, cfg.d_model),
            "wg": (cfg.d_model, cfg.d_ff), "wu": (cfg.d_model, cfg.d_ff),
            "wd": (cfg.d_ff, cfg.d_model)}
    keys = jax.random.split(key, len(dims))
    return {n: jax.random.normal(k, s, jnp.float32) / jnp.sqrt(s[0])
            for k, (n, s) in zip(keys, dims.items())}


@functools.partial(jax.jit, static_argnums=(0,))
def _calibrate_layer(cfg, key, x):
    """One layer's float weights, the k x k diagonal blocks of each
    group's Sigma_w and Sigma_x, and the next layer's input."""
    from repro.models import dense

    k = qconfig(cfg).cat_block
    w = _layer_weights(cfg, key)
    lp = dict(w, ln1=jnp.zeros((cfg.d_model,), jnp.float32),
              ln2=jnp.zeros((cfg.d_model,), jnp.float32))
    taps = _Taps()
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x_next, _, _ = dense._layer_body(cfg, x, lp, None, jnp.bool_(True),
                                     jnp.int32(0), positions, taps=taps,
                                     layer_idx=0)
    sigmas = {}
    for tap, (d, members) in group_dims(cfg).items():
        xs = taps.arrays[tap].astype(jnp.float32).reshape(-1, d)
        sigmas[tap] = (sum(_diag_blocks_gram(w[name], k)
                           for name, _ in members),
                       _diag_blocks_gram(xs.T, k) / xs.shape[0])
    return w, sigmas, x_next


@functools.partial(jax.jit, static_argnums=(0,))
def _quantize_group(qcfg, sw, sx, had, ws):
    """One group: the block CAT transform from its Sigma blocks, fused
    into each member weight, RTN int4 codes packed -> QLinears."""
    from repro.core.pipeline import _make_qlinear
    from repro.core.qlinear import fuse_weight_in

    t = _cat_transform(sw, sx, had)
    return tuple(_make_qlinear(*_quantize(fuse_weight_in(t, w), qcfg), t,
                               qcfg) for w in ws)


def _quantize_layer(cfg, key, x, had):
    """One layer: float weights -> calibration taps -> CAT -> W4 codes.
    Returns (the layer's QLinears by name, the next layer's input).

    Two programs per layer (calibration, then one per group) rather than
    one: each compiles in seconds for the TPU at Mistral-NeMo widths,
    where the single program took about ten minutes."""
    w, sigmas, x_next = _calibrate_layer(cfg, key, x)
    qcfg = qconfig(cfg)
    out = {}
    for tap, (_, members) in group_dims(cfg).items():
        names = [name for name, _ in members]
        qs = _quantize_group(qcfg, *sigmas[tap], had[tap],
                             tuple(w[n] for n in names))
        out.update(zip(names, qs))
    return out, x_next


class _Taps:
    """The calibration tap sink ``_layer_body`` records into: keeps the
    (traced) activations instead of pulling them to the host."""

    def __init__(self):
        self.arrays = {}

    def record(self, name: str, x) -> None:
        self.arrays[name.rsplit(".", 1)[-1]] = x


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_and_head(cfg, key):
    k_emb, k_head, k_cal = jax.random.split(key, 3)
    embed = jax.random.normal(k_emb, (cfg.vocab, cfg.d_model),
                              jnp.float32) * 0.02
    params = {"embed": embed,
              "final_norm": jnp.zeros((cfg.d_model,), jnp.float32)}
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab), jnp.float32)
            / jnp.sqrt(cfg.d_model))
    calib = jax.random.randint(k_cal, (CALIB_SEQS, CALIB_LEN), 0, cfg.vocab)
    cd = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = embed[calib].astype(cd) * jnp.sqrt(float(cfg.d_model)).astype(cd)
    return params, x


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def embed_and_head(cfg, seed: int) -> dict:
    """The float embedding, final norm and (untied) head of ``make_params``
    for ``seed``, made again."""
    return _embed_and_head(cfg, jax.random.fold_in(seed_key(seed), 0))[0]


def make_params(cfg, seed: int) -> dict:
    """The quantized parameter pytree for ``cfg`` from ``seed``."""
    key = seed_key(seed)
    params, x = _embed_and_head(cfg, jax.random.fold_in(key, 0))
    had = hadamards(cfg, seed)
    per_layer = []
    for i in range(cfg.n_layers):
        q, x = _quantize_layer(cfg, jax.random.fold_in(key, i + 1), x,
                               {t: tuple(h[i] for h in ops)
                                for t, ops in had.items()})
        per_layer.append(q)
    layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    layers["ln1"] = jnp.zeros((cfg.n_layers, cfg.d_model), jnp.float32)
    layers["ln2"] = jnp.zeros((cfg.n_layers, cfg.d_model), jnp.float32)
    params["layers"] = layers
    return params


def serving_params(model, params: dict) -> dict:
    """``model.make_serving_params(params)`` in one jitted program that
    takes ``params`` over (its buffers are donated)."""
    fn = jax.jit(lambda p: model.make_serving_params(p), donate_argnums=0)
    return fn(params)
