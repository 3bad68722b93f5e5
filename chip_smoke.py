"""Smoke run of CAT-quantized W4A8 serving on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # tensor-parallel serving on four chips

One chip: quantizes ``catlm_60m`` at its published widths (8 layers,
d_model 512, d_ff 1536, vocab 8192; random weights from ``--seed``) with
the CAT transform, int4 weights, int8 activations and an int8 KV cache,
then serves 16 seeded requests (prompts of 64-512 tokens, 64 new tokens
each) on 8 slots through the unified, pipelined ``ServeEngine``, twice:

* phase A, the default engine: every quantized linear runs the Pallas
  fused CAT -> quant -> W4A8 kernel, attention is XLA;
* phase B, ``paged_kernel=True``: adds the ragged and decode
  paged-attention kernels and the two-launch fused decode (the QKV
  prologue kernel), which pure-decode steps take.

Checks: every request returns exactly its ``max_new_tokens`` in-vocab
tokens after its prompt; each phase's compiled step holds its named
Pallas kernels; prefill logits from the engine's serving params (the
kernel route) agree with the same quantized params evaluated through
plain ``qlinear.dense`` in float32 at highest matmul precision, within
LOGIT_RTOL.

Four chips: serves the same model and workload on a (1, 4)
("data", "model") mesh, checks the outputs as above, checks that each
chip holds a quarter of every sharded weight and KV leaf, and compares
the logits of the first prompt, packed as one prefill chunk of a unified
ragged step, between the tensor-parallel step and the same step on one
device, within TP_RTOL. Nothing else runs on that path.

Every reading goes to stdout on its own line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed check raises, so the exit code is nonzero and that line is never
printed. The script refuses to run without a TPU, and with the
environment switches that would put the kernels in interpret mode or
change the decode route. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ARCH = "catlm_60m"
N_REQUESTS, N_SLOTS, GEN = 16, 8, 64
PROMPT_LENGTHS = (64, 96, 128, 192, 256, 384, 512)
# a 512-token prompt prefills in two steps beside the running decodes
MAX_BATCH_TOKENS = 256

# Relative Frobenius error allowed between the kernel route's prefill
# logits and the plain float32 reference (the same quantized params
# through ``qlinear.dense`` in a float32 copy of the model, at highest
# matmul precision). The kernel route keeps the model's bf16 residual
# stream, and every activation row is quantized to 8 bits, so a value
# that bf16 rounding moves across a code boundary shifts by one code
# step. At smoke width on the CPU backend (8 layers, kernels
# interpreted) that puts the kernel route 3.8% from this reference, and
# the bf16 model through the same plain route 4.4% from it; on a TPU v5e
# at full width the kernel route reads 4.1%. The bound allows three
# times that. A wrong transform, nibble order or scale
# layout gives an error of order 1: logits of an unrelated prompt differ
# by about 141%.
LOGIT_RTOL = 0.15
# Sharded vs one-device prefill logits of the same params on the same
# route (plain ``qlinear.dense``): the tensor-parallel forward slices
# matmul columns and all-gathers activations, so only accumulation order
# can differ, which moves a bf16 rounding and through it at most a few
# 8-bit codes by one step; a misplaced shard or gather order gives an
# error of order 1.
TP_RTOL = 5e-2
# "about a quarter": each sharded leaf's bytes on each of 4 chips
SHARD_FRACTION = (0.24, 0.26)

SWITCHES = ("REPRO_PALLAS_INTERPRET", "REPRO_DECODE_FUSED")


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def workload(cfg, seed: int) -> list:
    from repro.data import request_workload
    return request_workload(cfg, N_REQUESTS, gen=GEN,
                            lengths=PROMPT_LENGTHS, seed=seed)


def check_outputs(results, requests, vocab: int, phase: str) -> None:
    import numpy as np

    check(sorted(results) == sorted(r["rid"] for r in requests),
          f"{phase}: results for {sorted(results)}")
    for r in requests:
        res = results[r["rid"]]
        toks = np.asarray(res.tokens)
        p = len(r["tokens"])
        gen = toks[p:]
        check(res.prompt_len == p and np.array_equal(toks[:p], r["tokens"]),
              f"{phase}: request {r['rid']} lost its prompt")
        check(len(gen) == r["max_new_tokens"],
              f"{phase}: request {r['rid']} returned {len(gen)} tokens, "
              f"wanted {r['max_new_tokens']}")
        check(bool(np.all((gen >= 0) & (gen < vocab))),
              f"{phase}: request {r['rid']} has out-of-vocab tokens")


def token_agreement(a, b) -> float:
    import numpy as np
    return float(np.mean([np.mean(np.asarray(a[k].tokens)
                                  == np.asarray(b[k].tokens)) for k in a]))


def record_calls(obj, name: str) -> list:
    """Wrap ``obj.name`` so every call's arguments are kept (host copies:
    the scheduler reuses its descriptor buffers)."""
    import numpy as np

    calls = []
    orig = getattr(obj, name)

    def copy(v):
        if isinstance(v, dict):
            return {k: copy(x) for k, x in v.items()}
        return np.array(v) if isinstance(v, np.ndarray) else v

    def wrapped(*a, **kw):
        calls.append(([copy(x) for x in a], {k: copy(x)
                                              for k, x in kw.items()}))
        return orig(*a, **kw)

    setattr(obj, name, wrapped)
    return calls


def compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


def unified_step_text(eng, packed: dict) -> str:
    """HLO of the engine's compiled unified step for one packed plan."""
    import jax.numpy as jnp

    from repro.launch import executor

    ex = eng.exec
    desc = packed.get("ragged_desc")
    if desc is not None:
        desc = {k: jnp.asarray(v) for k, v in desc.items()}
    lrows = jnp.asarray(packed["logit_rows"])
    return compiled_text(
        executor._unified_step, eng.model.ragged_step, ex.paged_kernel, True,
        ex.params, ex.cache, jnp.asarray(packed["tokens"]),
        jnp.asarray(packed["pos"]), jnp.asarray(packed["page_table"]),
        lrows, desc, jnp.zeros(lrows.shape, jnp.int32),
        jnp.asarray(packed["tok_src"]))


def decode_step_text(eng, args: list) -> str:
    """HLO of the engine's compiled pure-decode step."""
    import jax.numpy as jnp

    from repro.launch import executor

    ex = eng.exec
    tok, pos, table, src = args
    return compiled_text(
        executor._fused_decode_step, ex._decode_fn, ex.params, ex.cache,
        jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(table),
        jnp.zeros((ex.n_slots,), jnp.int32), jnp.asarray(src))


def kernel_calls(text: str) -> int:
    return sum("tpu_custom_call" in line for line in text.splitlines())


def prefill_logits(model, params, prompt):
    """(P, V) f32 logits at every prompt position from the model's own
    prefill over a one-sequence cache."""
    import jax
    import jax.numpy as jnp

    p = len(prompt)
    cache = model.init_cache(1, p)
    logits, _ = jax.jit(model.prefill)(params, jnp.asarray(prompt)[None],
                                       cache, logits_at=jnp.arange(p))
    return jnp.asarray(logits[0], jnp.float32)


def ragged_prefill_logits(model, params, prompt, mesh=None):
    """(P, V) f32 logits at every prompt position from one unified ragged
    step (``model.ragged_step``) that packs the whole prompt as one
    prefill chunk over a paged pool; under ``mesh`` the step runs
    tensor-parallel in shard_map with the specs ``RaggedExecutor`` uses
    (weights and pools sharded on "model", descriptors replicated)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shlib
    from repro.distributed.compat import shard_map

    page = 16
    p = len(prompt)
    n_ptab = -(-p // page)
    rows = jnp.arange(p, dtype=jnp.int32)
    cache = dict(model.init_paged_cache(1 + n_ptab, page), pos=rows,
                 page_table=jnp.tile(jnp.arange(1, 1 + n_ptab,
                                                dtype=jnp.int32), (p, 1)))
    step = model.ragged_step
    if mesh is not None:
        tp_kw = dict(tp_axis="model", tp_mode="gather", tp_kernels=False)
        cspecs = shlib.tp_cache_specs(cache, mesh, axis="model")
        step = shard_map(
            lambda prm, t, c, r: model.ragged_step(prm, t, c, r, **tp_kw),
            mesh=mesh,
            in_specs=(shlib.tp_param_specs(params, mesh, axis="model",
                                           cfg=model.cfg),
                      P(None, None), cspecs, P(None)),
            out_specs=(P(None, None, None), cspecs), check_vma=False)
    tokens = jnp.asarray(prompt, jnp.int32)[:, None]
    logits, _ = jax.jit(step)(params, tokens, cache, rows)
    return jnp.asarray(logits[:, 0], jnp.float32)


def rel_err(got, want) -> float:
    import jax.numpy as jnp
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def serve_phase(name: str, model, params, requests, cfg, **engine_kw):
    """Build an engine, drain the workload once to compile and once timed
    (``serve.run_steady``), check the outputs, print the readings."""
    from repro.launch.engine import ServeEngine
    from repro.launch.serve import run_steady

    max_len = max(len(r["tokens"]) for r in requests) + GEN + 8
    eng = ServeEngine(model, params, n_slots=N_SLOTS, max_len=max_len,
                      max_batch_tokens=MAX_BATCH_TOKENS,
                      **engine_kw)
    check(eng.pipeline, f"{name}: the pipelined loop is not on")
    steps = record_calls(eng.exec, "step")
    decodes = record_calls(eng.exec, "decode_step")
    results, s = run_steady(eng, requests, passes=1)
    check_outputs(results, requests, cfg.vocab, name)
    say(phase=name, compile_s=s["compile_s"], tok_per_s=s["tok_per_s"],
        ttft_s_mean=s["ttft_s_mean"], ttft_s_max=s["ttft_s_max"],
        itl_p50_s=s["itl_p50_s"], itl_p95_s=s["itl_p95_s"],
        steps=s["steps"], generated_tokens=s["generated_tokens"],
        unified_dispatches=len(steps), decode_dispatches=len(decodes))
    return eng, results, steps, decodes


def one_chip(seed: int, dev) -> None:
    import jax

    from repro.launch.serve import build_served_model
    from repro.models import build

    t0 = time.perf_counter()
    cfg, model, params, mem = build_served_model(
        ARCH, "cat", 4, 8, 8, smoke=False, seed=seed)
    check(mem["packed_int4"], "weights are not int4-packed")
    say(phase="quantize", seconds=time.perf_counter() - t0,
        d_model=cfg.d_model, d_ff=cfg.d_ff, n_layers=cfg.n_layers,
        vocab=cfg.vocab, weight_bytes=mem["weight_bytes"],
        qlinear_layers=mem["qlinear_layers"])
    requests = workload(cfg, seed)
    prompt = requests[0]["tokens"]
    with jax.default_matmul_precision("highest"):
        want = prefill_logits(build(cfg.scaled(dtype="float32")), params,
                              prompt)

    # the engine takes ``params`` over; phase B serves phase A's tree
    eng_a, res_a, steps_a, _ = serve_phase("A", model, params, requests, cfg)
    text = unified_step_text(eng_a, steps_a[0][0][0])
    n_a = kernel_calls(text)
    say(phase="A", unified_step_tpu_custom_calls=n_a)
    check(n_a > 0 and "fused_cat_matmul_w4" in text,
          "phase A: the unified step holds no fused CAT kernel")

    got = prefill_logits(model, eng_a.params, prompt)
    err = rel_err(got, want)
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    say(phase="logits", prompt_len=len(prompt), rel_err=err,
        rtol=LOGIT_RTOL, argmax_agreement=agree)
    check(err <= LOGIT_RTOL, f"kernel-route logits rel err {err:.3g} > "
                             f"{LOGIT_RTOL}")
    params = eng_a.params
    del eng_a

    eng_b, res_b, steps_b, decodes_b = serve_phase(
        "B", model, params, requests, cfg, paged_kernel=True)
    check(bool(decodes_b), "phase B: no step took the fused decode path")
    ragged = next((a for (a, _) in steps_b
                   if a[0].get("ragged_desc") is not None), None)
    check(ragged is not None, "phase B: no unified step carried the "
                              "ragged kernel descriptors")
    text = unified_step_text(eng_b, ragged[0])
    n_b = kernel_calls(text)
    say(phase="B", unified_step_tpu_custom_calls=n_b)
    check("paged_attention_ragged" in text,
          "phase B: the unified step holds no ragged attention kernel")
    text = decode_step_text(eng_b, decodes_b[0][0])
    say(phase="B", decode_step_tpu_custom_calls=kernel_calls(text))
    for name in ("decode_qkv_prologue", "paged_attention_decode"):
        check(name in text, f"phase B: the decode step holds no {name}")
    say(phase="A_vs_B", token_agreement=token_agreement(res_a, res_b))
    say(peak_bytes_in_use=peak_bytes(dev))


def shard_fractions(tree) -> list:
    """Per sharded leaf: the fraction of its bytes on each device."""
    import jax

    out = []
    for leaf in jax.tree.leaves(tree):
        if not isinstance(leaf, jax.Array) or leaf.sharding.is_fully_replicated:
            continue
        per = {}
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
        out.append((leaf.nbytes, {d: b / leaf.nbytes for d, b in per.items()}))
    return out


def four_chips(seed: int, devs) -> None:
    from repro.launch.serve import build_served_model, parse_mesh

    cfg, model, params, mem = build_served_model(
        ARCH, "cat", 4, 8, 8, smoke=False, seed=seed)
    check(cfg.n_kv_heads % 4 == 0, "kv heads do not split over 4 chips")
    requests = workload(cfg, seed)
    mesh = parse_mesh("1,4")
    eng, _, _, _ = serve_phase("TP4", model, params, requests, cfg,
                               mesh=mesh)

    sharded = 0
    for part, tree in (("weights", eng.exec.params),
                       ("kv", eng.exec.cache)):
        leaves = shard_fractions(tree)
        check(bool(leaves), f"no {part} leaf is sharded")
        for nbytes, frac in leaves:
            check(len(frac) == 4 and all(SHARD_FRACTION[0] <= f
                                         <= SHARD_FRACTION[1]
                                         for f in frac.values()),
                  f"a sharded {part} leaf of {nbytes} bytes is split "
                  f"{frac} across devices")
        total = sum(n for n, _ in leaves)
        sharded += total
        say(phase="TP4", part=part, sharded_leaves=len(leaves),
            sharded_bytes=total, bytes_per_chip=total // 4)

    prompt = requests[0]["tokens"]
    got = ragged_prefill_logits(model, params, prompt, mesh)
    want = ragged_prefill_logits(model, params, prompt)
    err = rel_err(got, want)
    say(phase="TP4_logits", prompt_len=len(prompt), rel_err=err,
        rtol=TP_RTOL,
        argmax_agreement=float((got.argmax(-1) == want.argmax(-1)).mean()))
    check(err <= TP_RTOL, f"tp=4 vs one-device logits rel err {err:.3g} > "
                          f"{TP_RTOL}")
    say(peak_bytes_in_use_per_chip=[peak_bytes(d) for d in devs[:4]])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the serving smoke run; 4: tensor-parallel "
                         "serving on a (1, 4) mesh only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    set_switches = [v for v in SWITCHES if v in os.environ]
    if set_switches:
        print(f"chip_smoke: refusing to run with {set_switches} set: they "
              f"change the kernel path this run must exercise",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 2

    from repro.launch.serve import enable_compile_cache

    say(device_kind=json.dumps(dev.device_kind), devices=len(devs),
        compile_cache=enable_compile_cache())
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, devs)
    else:
        one_chip(args.seed, dev)
    say(total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
