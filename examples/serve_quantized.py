"""End-to-end serving driver (the paper's deployment): batched requests
against a CAT-quantized model — prefill + continuous greedy decode,
fp-vs-quantized agreement stats and throughput.

    PYTHONPATH=src python examples/serve_quantized.py [--batch 4] [--gen 48]
"""
import argparse
import sys
sys.path.insert(0, ".")
sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from benchmarks.common import trained_model
from repro.core.pipeline import QuantizeConfig, quantize_model
from repro.data import calibration_batches, make_batch
from repro.launch.serve import greedy_generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=48)
    args = ap.parse_args()

    cfg, model, params = trained_model()
    print(f"serving {cfg.name} | batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen}")

    qcfg = QuantizeConfig(w_bits=4, a_bits=4, transform="cat", cat_block=64)
    qparams = quantize_model(
        model, params, qcfg,
        calibration_batches(cfg, n_seqs=16, seq_len=128, batch=4))

    prompts = jnp.asarray(make_batch(cfg, args.prompt_len, args.batch,
                                     seed=11)["tokens"])
    max_len = args.prompt_len + args.gen + 8

    import time
    outs = {}
    for nm, p in (("fp", params), ("cat-w4a4", qparams)):
        t0 = time.time()
        toks = greedy_generate(model, p, prompts, args.gen, max_len)
        toks.block_until_ready()
        dt = time.time() - t0
        outs[nm] = np.asarray(toks)
        print(f"  {nm:10s} {args.batch*args.gen/dt:7.1f} tok/s "
              f"({dt:.2f}s incl. compile)")

    gen_fp = outs["fp"][:, args.prompt_len:]
    gen_q = outs["cat-w4a4"][:, args.prompt_len:]
    agree = float((gen_fp == gen_q).mean())
    print(f"\nfp-vs-quantized greedy token agreement: {100*agree:.1f}%")
    print("sample (request 0):")
    print("  fp :", gen_fp[0][:24].tolist())
    print("  q4 :", gen_q[0][:24].tolist())

    # --- the same quantized model behind the continuous-batching engine:
    # mixed-prompt FIFO queue, int8 paged KV pool, token-budget steps
    import dataclasses
    from repro.data import request_workload
    from repro.launch.engine import ServeEngine
    from repro.models import build
    qcfg8 = dataclasses.replace(cfg, kv_quant_bits=8)
    model8 = build(qcfg8)
    reqs = request_workload(qcfg8, 2 * args.batch, gen=args.gen, seed=11)
    engine = ServeEngine(model8, qparams, n_slots=args.batch,
                         max_len=max(len(r["tokens"]) for r in reqs)
                         + args.gen + 8)
    engine.run(reqs)
    s = engine.summary()
    print(f"\nengine: {s['n_requests']} mixed-length reqs on "
          f"{s['n_slots']} slots (int8 KV cache) -> "
          f"{s['tok_per_s']:.1f} tok/s, ttft {s['ttft_s_mean']*1e3:.0f}ms, "
          f"occupancy {s['occupancy_mean']:.2f}")


if __name__ == "__main__":
    main()
