"""At smoke size, the parameters made on the device have the pytree
structure, dtypes and shapes of ``quantize_model``'s output."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import params as bparams  # noqa: E402


def test_device_params_match_quantize_model():
    from repro.launch.serve import build_served_model

    cfg, _, want, _ = build_served_model("catlm_60m", "cat", 4, 8, 8,
                                         smoke=True, seed=0)
    got = bparams.make_params(cfg, 2**31 + 3)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    # the first layer's QKV codes are int4 nibbles packed two per byte
    q = got["layers"]["wq"]
    assert q.packed and q.w_bits == 4 and q.act_bits == 8
    assert q.qweight.shape[-2] * 2 == cfg.d_model


def test_the_seed_decides_the_params():
    from repro.configs import get_config

    cfg = get_config("catlm_60m").smoke().scaled(kv_quant_bits=8)
    a = bparams.make_params(cfg, 7)
    b = bparams.make_params(cfg, 7)
    c = bparams.make_params(cfg, 7 + 2**32)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(c["embed"]))
    emb = bparams.embed_and_head(cfg, 7)
    assert np.array_equal(np.asarray(emb["embed"]), np.asarray(a["embed"]))
