"""Mistral-NeMo's shape traits at a tiny size on the CPU, served through
the path the ``nemo8.azconv`` cell runs and held to its limit.

The model keeps what sets Mistral-NeMo-12B apart from ``catlm-60m`` in the
program: query heads that are not the hidden size wide (8 heads of 32 =
256 against d_model 320, as 4096 against 5120), two KV heads each serving
a group of 4 query heads, an untied output head, rope_theta 1e6 and int8
KV. Its CAT-W4A8 parameters come from ``params.make_params`` and a seed.
``ServeEngine``, built with the configuration's engine settings (unified,
pipelined, paged, the paged-attention kernels in interpret mode), serves
requests whose prompts span several chunked-prefill steps and then decode
through the KV pool. The reference (``reference.logits_at`` over each
whole sequence) must rank every served token within the cell's
``max_gap`` of its best logit; the control, the reference with 4-bit
activations, must not.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import cell  # noqa: E402
import check  # noqa: E402
import params as bparams  # noqa: E402
import registry  # noqa: E402

CELL, CONFIG = "nemo8.azconv", "mistral-nemo-12b-8l"
SEED = 2**31 + 1207
# prompt lengths span 1 to 3 chunks of the 64-token step budget
PROMPTS, NEW = (70, 23, 131, 9, 48), (12, 20, 6, 16, 9)


def tiny_cfg():
    from repro.configs import get_config
    conf = registry.config(registry.benchmark(), CONFIG)
    cfg = get_config(conf["arch"]).scaled(
        n_layers=2, d_model=320, n_heads=8, n_kv_heads=2, head_dim=32,
        d_ff=640, vocab=512, **{k: v for k, v in conf["program"].items()
                                if k != "n_layers"})
    return conf, cfg


@pytest.fixture(scope="module")
def served():
    """-> (cfg, reference params, finished {rid: (prompt, served)})."""
    from repro.launch.engine import ServeEngine
    from repro.models import build

    conf, cfg = tiny_cfg()
    qparams = bparams.make_params(cfg, SEED)
    ref = dict(bparams.embed_and_head(cfg, SEED),
               layers=jax.device_get(qparams["layers"]))
    kw = cell.engine_kwargs(conf, {"n_slots": 4, "max_batch_tokens": 64},
                            max(p + n for p, n in zip(PROMPTS, NEW)))
    eng = ServeEngine(build(cfg), qparams, **kw)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, p, dtype=np.int32)
               for p in PROMPTS]
    res = eng.run([{"rid": i, "tokens": p, "max_new_tokens": n}
                   for i, (p, n) in enumerate(zip(prompts, NEW))])
    assert eng.paged and eng.pipeline and eng.fused
    finished = {r: (prompts[r], res[r].tokens[res[r].prompt_len:])
                for r in res}
    return cfg, ref, finished


def test_the_model_has_mistral_nemos_shape_traits():
    conf, cfg = tiny_cfg()
    assert cfg.q_dim != cfg.d_model and cfg.n_heads // cfg.n_kv_heads == 4
    assert not cfg.tie_embeddings and cfg.rope_theta == 1e6
    assert cfg.kv_quant_bits == 8
    assert conf["engine"]["paged_kernel"] and conf["engine"]["schedule"] \
        == "unified"


def test_served_tokens_meet_the_cells_limit_the_control_does_not(served):
    cfg, ref, finished = served
    limit = check.limits(CELL)["max_gap"]
    assert {r: len(s) for r, (_, s) in finished.items()} == dict(
        enumerate(NEW))
    rids = sorted(finished)
    got = check.control_gap(cfg, ref, finished, rids)
    assert got["served_tokens"] == sum(NEW)
    assert got["program"] <= limit < got["control"], got
