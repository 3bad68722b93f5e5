"""``ServeEngine`` serves its parameters (``models.dense.
make_serving_params``: QKV and gate|up concatenation, weight-code column
sums) in one jitted program, ``engine.serving_params``: the tokens are
those of parameters served beforehand, and a tree that is served already
is taken as it is."""
import jax
import numpy as np

from repro.data import request_workload
from repro.launch.engine import ServeEngine, serving_params

MAX_LEN = 14 + 5 + 8


def _engine(model, params):
    return ServeEngine(model, params, n_slots=3, max_len=MAX_LEN,
                       max_batch_tokens=16)


def test_jitted_serving_gives_the_tokens_of_pre_served_params(
        tiny_cfg, tiny_model, tiny_quantized):
    reqs = request_workload(tiny_cfg, 6, gen=5, lengths=(6, 10, 14), seed=7)
    pre = tiny_model.make_serving_params(tiny_quantized)   # eager, per op
    jitted = _engine(tiny_model, tiny_quantized)
    got, want = jitted.run(reqs), _engine(tiny_model, pre).run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(got[r["rid"]].tokens,
                                      want[r["rid"]].tokens)
    assert jax.tree.structure(jitted.params) == jax.tree.structure(pre)
    for a, b in zip(jax.tree.leaves(jitted.params), jax.tree.leaves(pre)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_served_params_are_taken_as_they_are(tiny_model, tiny_quantized):
    pre = tiny_model.make_serving_params(tiny_quantized)
    assert serving_params(tiny_model.make_serving_params, pre) is pre
    eng = _engine(tiny_model, pre)
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                      jax.tree.leaves(pre)))
    # the caller's unserved tree stays valid on the CPU
    assert not any(leaf.is_deleted() for leaf in
                   jax.tree.leaves(tiny_quantized))
