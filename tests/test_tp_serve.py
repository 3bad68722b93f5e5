"""Tensor-parallel serving: ServeEngine on a (1, tp) ("data", "model")
device mesh — sharded int4-packed weights, sharded (quantized) KV pool — must
drain the seeded mixed-prompt workload with **token-identical** output to
the single-device engine for fp, int8-KV, and int4-packed configs (which
is itself token-identical to solo greedy_generate, PR 2's contract).

In-process cases need >= 4 local devices — they run under the CI mesh job
(XLA_FLAGS=--xla_force_host_platform_device_count=8) and skip otherwise;
the subprocess case runs everywhere (slow)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.data import request_workload
from repro.launch.engine import ServeEngine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")

GEN = 5
MAX_LEN = 14 + GEN + 8


@pytest.fixture(scope="module")
def mesh():
    from repro.distributed.compat import make_mesh
    return make_mesh((1, 4), ("data", "model"))


@pytest.fixture(scope="module")
def mha_cfg():
    """tp=4-friendly smoke config: every head count divides the mesh
    (the GQA smoke default has n_kv_heads=2, which tp=4 must reject —
    see test_mesh_rejects_unsplittable_heads)."""
    from repro.configs import get_config
    return get_config("catlm_60m").smoke().scaled(n_kv_heads=4)


@pytest.fixture(scope="module")
def mha_params(mha_cfg):
    from repro.models import build
    return build(mha_cfg).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mha_quantized(mha_cfg, mha_params):
    from repro.core.pipeline import QuantizeConfig, quantize_model
    from repro.data import calibration_batches
    from repro.models import build
    qcfg = QuantizeConfig(w_bits=4, a_bits=4, transform="cat", cat_block=16)
    return quantize_model(build(mha_cfg), mha_params, qcfg,
                          calibration_batches(mha_cfg, n_seqs=2,
                                              seq_len=16, batch=2))


def _drain_both(cfg, params, mesh, n_requests=6, n_slots=3, **mesh_kw):
    from repro.models import build
    model = build(cfg)
    reqs = request_workload(cfg, n_requests, gen=GEN, lengths=(6, 10, 14),
                            seed=3)
    solo = ServeEngine(model, params, n_slots=n_slots,
                       max_len=MAX_LEN).run(reqs)
    eng = ServeEngine(model, params, n_slots=n_slots, max_len=MAX_LEN,
                      mesh=mesh, **mesh_kw)
    meshed = eng.run(reqs)
    return reqs, solo, meshed, eng


def _assert_identical(reqs, solo, meshed):
    for r in reqs:
        np.testing.assert_array_equal(
            meshed[r["rid"]].tokens, solo[r["rid"]].tokens,
            err_msg=f"rid={r['rid']}")


# ---------------------------------------------------------- token identity

@needs_mesh
def test_mesh_engine_fp_token_identical(mha_cfg, mha_params, mesh):
    reqs, solo, meshed, eng = _drain_both(mha_cfg, mha_params, mesh)
    assert not eng.quantized_kv
    _assert_identical(reqs, solo, meshed)
    assert eng.summary()["mesh"] == {"data": 1, "model": 4}


@needs_mesh
def test_mesh_engine_int8_kv_token_identical(mha_cfg, mha_params, mesh):
    cfg = mha_cfg.scaled(kv_quant_bits=8)
    reqs, solo, meshed, eng = _drain_both(cfg, mha_params, mesh)
    assert eng.quantized_kv
    _assert_identical(reqs, solo, meshed)


@needs_mesh
def test_mesh_engine_w4_packed_token_identical(mha_cfg, mha_quantized,
                                               mesh):
    """The headline case: int4-packed weights + int8 KV cache, sharded."""
    from repro.core.qlinear import iter_qlinear
    assert any(l.packed for _, l in iter_qlinear(mha_quantized))
    cfg = mha_cfg.scaled(kv_quant_bits=8)
    reqs, solo, meshed, eng = _drain_both(cfg, mha_quantized, mesh)
    assert eng.quantized_kv
    _assert_identical(reqs, solo, meshed)


@needs_mesh
def test_mesh_engine_psum_mode_agrees(mha_cfg, mha_quantized, mesh):
    """True row-parallel (psum) mode is rtol-level, not bitwise: the
    drained workload must still produce near-identical trajectories
    (greedy tokens only flip on bf16-ulp logit ties)."""
    cfg = mha_cfg.scaled(kv_quant_bits=8)
    reqs, solo, meshed, _ = _drain_both(cfg, mha_quantized, mesh,
                                        n_requests=4, tp_mode="psum")
    agree = np.mean([
        float(np.mean(meshed[r["rid"]].tokens == solo[r["rid"]].tokens))
        for r in reqs])
    assert agree >= 0.9, agree


@needs_mesh
def test_mesh_engine_untied_embeddings_token_identical(mesh):
    """tie_embeddings=False serves through a separate unembed, which must
    stay replicated (vocab-sharded logits under a replicated out_spec
    with check_vma=False would silently decode from a vocab slice)."""
    from repro.configs import get_config
    from repro.models import build
    cfg = get_config("catlm_60m").smoke().scaled(n_kv_heads=4,
                                                 tie_embeddings=False,
                                                 kv_quant_bits=8)
    params = build(cfg).init(jax.random.PRNGKey(2))
    reqs, solo, meshed, _ = _drain_both(cfg, params, mesh, n_requests=4)
    _assert_identical(reqs, solo, meshed)


# ------------------------------------------------------------- validation

@needs_mesh
def test_mesh_rejects_unsplittable_heads(mesh):
    """GQA smoke default (n_kv_heads=2) cannot split whole heads over
    tp=4 — the engine must fail loudly at construction."""
    from repro.configs import get_config
    from repro.models import build
    cfg = get_config("catlm_60m").smoke()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="n_kv_heads"):
        ServeEngine(model, params, n_slots=2, max_len=MAX_LEN, mesh=mesh)


@needs_mesh
def test_mesh_rejects_moe(mesh):
    from repro.configs import get_config
    from repro.models import build
    cfg = get_config("granite_moe_1b_a400m").smoke()
    model = build(cfg)
    with pytest.raises(NotImplementedError):
        ServeEngine(model, None, n_slots=1, max_len=16, mesh=mesh)


# ------------------------------------------------- subprocess (any host)

@pytest.mark.slow
def test_mesh_engine_subprocess_token_identity():
    """fp + int4-packed mesh-vs-solo equality on a forced-host 4-device
    tp mesh, runnable from the default single-device tier-1 suite."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax
        from repro.configs import get_config
        from repro.core.pipeline import QuantizeConfig, quantize_model
        from repro.data import calibration_batches, request_workload
        from repro.distributed.compat import make_mesh
        from repro.launch.engine import ServeEngine
        from repro.models import build

        mesh = make_mesh((1, 4), ("data", "model"))
        cfg = get_config("catlm_60m").smoke().scaled(n_kv_heads=4)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        qcfg = QuantizeConfig(w_bits=4, a_bits=4, transform="cat",
                              cat_block=16)
        qparams = quantize_model(model, params, qcfg,
                                 calibration_batches(cfg, n_seqs=2,
                                                     seq_len=16, batch=2))
        cfg8 = cfg.scaled(kv_quant_bits=8)
        for tag, c, p in (("fp", cfg, params),
                          ("w4", cfg8, qparams)):
            m = build(c)
            reqs = request_workload(c, 5, gen=4, lengths=(6, 10), seed=3)
            solo = ServeEngine(m, p, n_slots=2, max_len=24).run(reqs)
            meshed = ServeEngine(m, p, n_slots=2, max_len=24,
                                 mesh=mesh).run(reqs)
            for r in reqs:
                np.testing.assert_array_equal(meshed[r["rid"]].tokens,
                                              solo[r["rid"]].tokens)
            print(tag + "-ok")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=540,
                       env={**os.environ, "PYTHONPATH": os.path.abspath(SRC)})
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "fp-ok" in r.stdout and "w4-ok" in r.stdout
