"""Golden engine-output fixtures: seeded greedy token sequences for the
three serving configs (fp, int8-KV, int4-packed weights + int8 KV) on the
small catlm config, checked into ``tests/golden/*.json``.

``tests/test_golden_outputs.py`` diffs live output of the default
``ServeEngine`` (unified token-budget step over the paged pool) against
these files, so a kernel/engine refactor that silently changes decoded tokens
fails loudly instead of drifting. When a change *intentionally* alters
numerics (new quantizer, different accumulation), regenerate with

    PYTHONPATH=src python tests/golden/regenerate.py

and commit the diff with an explanation. Fixtures are a function of the
pinned CI jax version (bf16 matmul accumulation order is backend
numerics); regenerate under the same pin CI uses (see ci.yml).
"""
from __future__ import annotations

import json
import os

import numpy as np

GOLDEN_DIR = os.path.dirname(os.path.abspath(__file__))

# (kv_quant_bits, quantize-weights) per case — small enough that all
# three run in the not-slow suite.
CASES = {
    "fp": dict(kv_bits=0, quantize=False),
    "int8_kv": dict(kv_bits=8, quantize=False),
    "w4_packed": dict(kv_bits=8, quantize=True),
}
N_REQUESTS, GEN, LENGTHS, N_SLOTS, MAX_LEN, SEED = 4, 4, (6, 10), 2, 24, 9


def build_case(name: str):
    """-> (cfg, model, params) for a golden case, fully seeded."""
    import jax

    from repro.configs import get_config
    from repro.models import build

    spec = CASES[name]
    base = get_config("catlm_60m").smoke()
    model_fp = build(base)
    params = model_fp.init(jax.random.PRNGKey(0))
    if spec["quantize"]:
        from repro.core.pipeline import QuantizeConfig, quantize_model
        from repro.data import calibration_batches
        qcfg = QuantizeConfig(w_bits=4, a_bits=4, transform="cat",
                              cat_block=16)
        params = quantize_model(model_fp, params, qcfg,
                                calibration_batches(base, n_seqs=2,
                                                    seq_len=16, batch=2))
    cfg = base.scaled(kv_quant_bits=spec["kv_bits"])
    return cfg, build(cfg), params


def run_case(name: str, **engine_kw) -> dict:
    """Drain the seeded workload through the engine -> {rid: [tokens]}.

    ``engine_kw`` forwards to ``ServeEngine`` so the bitwise tests can
    replay the same fixture workload through every serving configuration
    (page sizes, token budgets, chunk caps, mesh) — the fixtures
    themselves are always regenerated with the default engine (unified
    token-budget step over the paged pool, pipelined)."""
    from repro.data import request_workload
    from repro.launch.engine import ServeEngine

    cfg, model, params = build_case(name)
    reqs = request_workload(cfg, N_REQUESTS, gen=GEN, lengths=LENGTHS,
                            seed=SEED)
    engine = ServeEngine(model, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                         **engine_kw)
    results = engine.run(reqs)
    return {str(r["rid"]): np.asarray(results[r["rid"]].tokens).tolist()
            for r in reqs}


def fixture_path(name: str) -> str:
    return os.path.join(GOLDEN_DIR, f"{name}.json")


def main() -> None:
    for name in CASES:
        tokens = run_case(name)
        with open(fixture_path(name), "w") as f:
            json.dump({"case": name, "arch": "catlm_60m-smoke",
                       "n_requests": N_REQUESTS, "gen": GEN,
                       "lengths": list(LENGTHS), "seed": SEED,
                       "tokens": tokens}, f, indent=1)
        print(f"wrote {fixture_path(name)}")


if __name__ == "__main__":
    main()
