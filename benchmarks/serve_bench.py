"""Continuous-batching serving benchmark: the same seeded mixed-prompt
workload drained through the engine with fp, int8, and int4-packed
weights (int8 slot KV cache for the quantized rows). Emits the usual CSV
rows plus a JSON artifact (results/serve_bench.json, stamped with a
``schema_version``) with TTFT, steady-state tok/s, per-step latency
percentiles (ITL p50/p95), and slot-occupancy per variant.

Variant rows are STEADY-STATE (schema v3): each engine drains the
workload twice and only the second, fully-compiled pass is timed —
compilation cost is reported separately as ``compile_s``. (The old
single-pass rows charged jit compilation to tok/s; the quantized
variants trace more distinct XLA programs than fp, so the compile tax
buried exactly the hot-path win this bench exists to show.) Each row
also carries the gap-attribution fields: analytic hot-path HBM
bytes/token (``hot_path_bytes_per_token``, fused vs unfused — see
benchmarks/roofline_report.py), measured ``device_ms_mean`` /
``host_ms_mean`` per step, and ``dispatch_per_step``.

Unified-vs-legacy rows (``schedule_mixed``): a mixed workload of long
prompts among short decodes, drained through the legacy
(prefill-on-admit) engine and the unified token-budget scheduler. The
readout is the ITL tail: legacy admission steps prefill a whole long
prompt before the in-flight decodes run (head-of-line stall -> fat p95),
unified packs at most ``max_batch_tokens`` per step so decode latency
stays flat — with a token-identity check between the two engines.

Paged-vs-slot rows (``kv_paged_50`` / ``kv_paged_100``): the same
workload through the slot cache and the paged pool at ~50% and ~100%
mean sequence occupancy — tok/s, TTFT, and resident KV bytes (allocated
pages vs the slot cache's flat ``n_slots × max_len`` reservation), with
a token-identity check between the two engines.

Prefix-cache rows (``prefix_shared``): a workload of requests sharing a
common system prompt, served with the copy-on-write prefix cache off and
on — TTFT p50/p95, prefill tokens skipped, hit rate, and the resident-KV
dedup ratio, with a token-identity check between the two engines.

Speculative row (``speculative``): self-speculative decoding on the
int4-packed serving config — an int4 draft proposes k tokens per slot
per cycle (one fused k-step scan dispatch), the target verifies all k+1
positions in one ragged step. tok/s and acceptance rate vs the
non-speculative unified baseline at k in {2, 4}, token-identity checked
(greedy acceptance makes identity structural; a false here is a bug and
exits nonzero).

Pipelined row (``pipelined``, schema v4): the depth-1 asynchronous
unified loop (device-resident sampling + one-step-ahead scheduling,
``ServeEngine(pipeline=True)``) vs the synchronous loop on a
decode-heavy workload — tok/s both ways, speedup, ITL p50/p95,
mispredict count, and a token-identity check. Runs in ``--quick`` too.

With >= 4 local devices (XLA_FLAGS=--xla_force_host_platform_device_count
on CPU) it also serves the int4-packed variant tensor-parallel — a tp=1
vs tp=4 pair on an MHA smoke config, token-identity checked row-to-row.

On CPU the absolute tok/s is a correctness-path number (interpret-mode
kernels, smoke model); the interesting readouts are the relative weight /
resident-KV bytes and the scheduler metrics (occupancy, queue drain,
TTFT spread).
"""
from __future__ import annotations

import json
import os

from benchmarks.common import emit
from repro.launch.serve import enable_compile_cache, serve_benchmark

VARIANTS = [
    # name, transform, w_bits, a_bits, kv_bits
    ("fp", "fp", 0, 0, 0),
    ("int8", "cat", 8, 8, 8),
    ("int4_packed", "cat", 4, 4, 8),
]

# tensor-parallel pair: identical MHA config (smoke catlm has
# n_kv_heads=2, which cannot split whole heads over tp=4) served at tp=1
# and on a (1, 4) ("data", "model") mesh.
TP_OVERRIDES = {"n_kv_heads": 4}


def _tp_rows(rows, n_requests, n_slots, gen) -> None:
    import jax

    if len(jax.devices()) < 4:
        emit("serve_int4_tp4", 0.0,
             "skipped=needs-4-devices (XLA_FLAGS="
             "--xla_force_host_platform_device_count=8)")
        return
    from repro.distributed.compat import make_mesh
    outs = {}
    for name, mesh in (("int4_tp1", None),
                       ("int4_tp4", make_mesh((1, 4), ("data", "model")))):
        out = serve_benchmark(arch="catlm_60m", batch=n_slots, gen=gen,
                              transform="cat", w_bits=4, a_bits=4,
                              kv_bits=8, n_requests=n_requests, mixed=True,
                              seed=0, mesh=mesh, cfg_overrides=TP_OVERRIDES)
        eng = out["engine"]
        outs[name] = out
        rows[name] = {
            "transform": "cat", "w_bits": 4, "kv_bits": 8,
            "mesh": eng["mesh"],
            "ttft_s_mean": eng["ttft_s_mean"],
            "tok_per_s": eng["tok_per_s"],
            "occupancy_mean": eng["occupancy_mean"],
            "dispatch_per_step": eng["dispatch_per_step"],
            "launches_per_token": eng["launches_per_token"],
            "n_requests": eng["n_requests"], "n_slots": eng["n_slots"],
        }
        emit(f"serve_{name}", eng["wall_s"] * 1e6,
             f"tok_per_s={eng['tok_per_s']:.1f} "
             f"ttft_ms={eng['ttft_s_mean'] * 1e3:.0f} mesh={eng['mesh']}")
    identical = all(
        (outs["int4_tp1"]["results"][rid].tokens
         == outs["int4_tp4"]["results"][rid].tokens).all()
        for rid in outs["int4_tp1"]["results"])
    rows["int4_tp4"]["token_identical_to_tp1"] = bool(identical)
    emit("serve_tp4_token_identity", 0.0, f"identical={identical}")


def _paged_rows(rows, n_requests: int, n_slots: int) -> None:
    """Slot-vs-paged engine over the same model and workload, at ~50% and
    ~100% mean sequence occupancy of max_len (the paged win is resident
    bytes tracking true lengths; at 100% the two converge)."""
    import numpy as np

    from repro.data import request_workload
    from repro.launch.engine import ServeEngine
    from repro.launch.serve import build_served_model

    cfg, model, params, _ = build_served_model(
        "catlm_60m", "cat", 8, 8, 8, smoke=True, seed=0)
    gen, max_len = 8, 48
    for tag, lengths in (("50", (8, 16, 24)), ("100", (40,))):
        reqs = request_workload(cfg, n_requests, gen=gen, lengths=lengths,
                                seed=0)
        slot = ServeEngine(model, params, n_slots=n_slots, max_len=max_len)
        slot_res = slot.run(reqs)
        ss = slot.summary()
        paged = ServeEngine(model, params, n_slots=n_slots, max_len=max_len,
                            paged=True, page_size=8, prefill_chunk=16)
        paged_res = paged.run(reqs)
        ps = paged.summary()
        identical = all((slot_res[r["rid"]].tokens
                         == paged_res[r["rid"]].tokens).all() for r in reqs)
        ratio = ps["resident_kv_bytes_mean"] / ss["kv_capacity_bytes"]
        mean_seq = float(np.mean([len(r["tokens"]) + gen for r in reqs]))
        rows[f"kv_paged_{tag}"] = {
            "mean_seq_occupancy": mean_seq / max_len,
            "slot_kv_bytes": ss["kv_capacity_bytes"],
            # the slot engine reports resident bytes too (== its capacity:
            # every slot reserves max_len rows up front) so the columns
            # compare like for like
            "slot_resident_kv_bytes_mean": ss["resident_kv_bytes_mean"],
            "paged_resident_kv_bytes_mean": ps["resident_kv_bytes_mean"],
            "paged_resident_kv_bytes_peak": ps["resident_kv_bytes_peak"],
            "paged_over_slot_kv_bytes": ratio,
            "page_size": ps["page_size"],
            "prefill_chunk": ps["prefill_chunk"],
            "slot_tok_per_s": ss["tok_per_s"],
            "paged_tok_per_s": ps["tok_per_s"],
            "dispatch_per_step": ps["dispatch_per_step"],
            "launches_per_token": ps["launches_per_token"],
            "slot_ttft_s_mean": ss["ttft_s_mean"],
            "paged_ttft_s_mean": ps["ttft_s_mean"],
            "token_identical": bool(identical),
            "n_requests": n_requests, "n_slots": n_slots,
            "max_len": max_len,
        }
        emit(f"serve_kv_paged_{tag}", ps["wall_s"] * 1e6,
             f"resident_ratio={ratio:.2f} "
             f"tok_per_s={ps['tok_per_s']:.1f} "
             f"identical={identical}")


def _unified_rows(rows, n_slots: int) -> None:
    """Legacy vs unified schedule over the same mixed long-prompt/decode
    workload: long admissions stall legacy's in-flight decodes (ITL p95
    tail), while the unified token budget caps per-step work. Both
    engines must stay token-identical (they are bitwise so)."""
    from repro.data import request_workload
    from repro.launch.engine import ServeEngine
    from repro.launch.serve import build_served_model

    cfg, model, params, _ = build_served_model(
        "catlm_60m", "fp", 0, 0, 8, smoke=True, seed=0)
    gen, max_len, budget = 8, 72, 12
    reqs = request_workload(cfg, 10, gen=gen, lengths=(4, 48), seed=0)
    outs = {}
    # both engines serve the SAME paged pool with chunked prefill (one
    # prefill compile each) so the only variable is the schedule itself:
    # legacy still prefills a whole admission before its decode dispatch,
    # unified packs at most `budget` tokens per step
    for name, kw in (("legacy", dict(paged=True, page_size=8,
                                     prefill_chunk=8)),
                     ("unified", dict(schedule="unified",
                                      max_batch_tokens=budget,
                                      paged=True, page_size=8))):
        eng = ServeEngine(model, params, n_slots=n_slots, max_len=max_len,
                          **kw)
        res = eng.run(reqs)
        outs[name] = (res, eng.summary())
    identical = all((outs["legacy"][0][r["rid"]].tokens
                     == outs["unified"][0][r["rid"]].tokens).all()
                    for r in reqs)
    ls, us = outs["legacy"][1], outs["unified"][1]
    rows["schedule_mixed"] = {
        "workload": "mixed long-prompt (48t) / short (4t), gen 8",
        "max_batch_tokens": budget,
        "legacy_itl_p50_s": ls["itl_p50_s"],
        "legacy_itl_p95_s": ls["itl_p95_s"],
        "unified_itl_p50_s": us["itl_p50_s"],
        "unified_itl_p95_s": us["itl_p95_s"],
        "itl_p95_unified_over_legacy": (us["itl_p95_s"] / ls["itl_p95_s"]
                                        if ls["itl_p95_s"] else 0.0),
        "legacy_tok_per_s": ls["tok_per_s"],
        "unified_tok_per_s": us["tok_per_s"],
        "unified_packed_tokens_max": us["packed_tokens_max"],
        "dispatch_per_step": us["dispatch_per_step"],
        "launches_per_token": us["launches_per_token"],
        "token_identical": bool(identical),
        "n_requests": len(reqs), "n_slots": n_slots,
    }
    emit("serve_schedule_mixed", us["wall_s"] * 1e6,
         f"itl_p95_ms legacy={ls['itl_p95_s'] * 1e3:.1f} "
         f"unified={us['itl_p95_s'] * 1e3:.1f} "
         f"identical={identical}")


def _prefix_rows(rows, n_slots: int, quick: bool = False) -> None:
    """Shared-system-prompt workload through the unified engine with the
    prefix cache off and on: every request repeats the same S-token
    system prompt, so the cache maps those pages read-only (copy-on-write
    past the shared boundary) instead of re-prefilling and re-storing
    them. Readouts: TTFT p50/p95 both ways, prefill tokens skipped, hit
    rate, and the resident-KV dedup ratio — with a token-identity check
    between the two engines. Steady-state (warmup pass): the measured
    pass runs against a warm trie, i.e. a server that has already seen
    the system prompt."""
    import numpy as np

    n_requests, gen, shared = (4, 4, 24) if quick else (8, 8, 48)
    outs = {}
    for name, on in (("off", False), ("on", True)):
        outs[name] = serve_benchmark(
            arch="catlm_60m", batch=n_slots, gen=gen, transform="cat",
            w_bits=4, a_bits=4, kv_bits=8, n_requests=n_requests, seed=0,
            schedule="unified", shared_prefix=shared, prefix_cache=on,
            warmup=1)
    off, on = outs["off"], outs["on"]
    identical = all((off["results"][rid].tokens
                     == on["results"][rid].tokens).all()
                    for rid in off["results"])

    def _pcts(out):
        t = [r.ttft_s for r in out["results"].values()]
        return (float(np.percentile(t, 50)), float(np.percentile(t, 95)))

    eo, en = off["engine"], on["engine"]
    off_p50, off_p95 = _pcts(off)
    on_p50, on_p95 = _pcts(on)
    # peak, not mean: prefix-on admits faster (skipped prefill), so it
    # holds more concurrent sequences per step and time-weighted means
    # aren't like-for-like; at peak both engines run n_slots sequences
    # and the dedup win is the shared pages counted once
    ratio = (en["resident_kv_bytes_peak"] / eo["resident_kv_bytes_peak"]
             if eo["resident_kv_bytes_peak"] else 0.0)
    rows["prefix_shared"] = {
        "workload": (f"{n_requests} reqs sharing a {shared}t system "
                     f"prompt, gen {gen}, unified schedule"),
        "shared_prefix_tokens": shared,
        "off_ttft_s_p50": off_p50, "off_ttft_s_p95": off_p95,
        "on_ttft_s_p50": on_p50, "on_ttft_s_p95": on_p95,
        "prefill_tokens_skipped": en["prefix_hit_tokens"],
        "prefix_hit_rate": en["prefix_hit_rate"],
        "cow_copies": en["cow_copies"],
        "resident_kv_peak_on_over_off": ratio,
        "off_resident_kv_bytes_peak": eo["resident_kv_bytes_peak"],
        "on_resident_kv_bytes_peak": en["resident_kv_bytes_peak"],
        "on_cached_kv_bytes": en["cached_kv_bytes"],
        "off_tok_per_s": eo["tok_per_s"], "on_tok_per_s": en["tok_per_s"],
        "dispatch_per_step": en["dispatch_per_step"],
        "launches_per_token": en["launches_per_token"],
        "token_identical": bool(identical),
        "n_requests": n_requests, "n_slots": n_slots,
    }
    emit("serve_prefix_shared", on["wall_s"] * 1e6,
         f"hit_rate={en['prefix_hit_rate']:.2f} "
         f"skipped={en['prefix_hit_tokens']}t "
         f"ttft_p95_ms off={off_p95 * 1e3:.0f} on={on_p95 * 1e3:.0f} "
         f"kv_ratio={ratio:.2f} identical={identical}")


def _speculative_rows(rows, quick: bool = False) -> None:
    """Self-speculative decoding on the int4-packed serving config: a
    draft pass runs the int4-packed weights fused into one k-step scan
    dispatch, then the target verifies all k+1 positions per slot in a
    single ragged invocation. The workload is decode-heavy (short
    prompts, long gens) because speculation only pays on the decode
    path — prefill is mirrored into the draft KV pool and so costs
    roughly double. Greedy acceptance keeps the output token-identical
    to the non-speculative unified baseline; the row records the check
    and the run fails loudly if it is ever false."""
    import numpy as np

    n_requests, n_slots, prompt, gen = ((4, 2, 8, 16) if quick
                                        else (8, 4, 8, 48))
    common = dict(arch="catlm_60m", batch=n_requests, prompt_len=prompt,
                  gen=gen, transform="cat", w_bits=4, a_bits=8, kv_bits=8,
                  seed=0, n_slots=n_slots, paged=True, schedule="unified",
                  warmup=1)
    base = serve_benchmark(**common)
    row = {
        "workload": (f"{n_requests} reqs, {prompt}t prompt, gen {gen}, "
                     "cat w4a8 kv8 target, int4-packed draft"),
        "baseline_tok_per_s": base["tok_per_s"],
        "dispatch_per_step": base["engine"]["dispatch_per_step"],
        "launches_per_token": base["engine"]["launches_per_token"],
        "n_requests": n_requests, "n_slots": n_slots,
    }
    identical_all = True
    for k in (2, 4):
        spec = serve_benchmark(**common, speculative=k)
        eng = spec["engine"]
        identical = bool(np.array_equal(base["tokens"], spec["tokens"]))
        identical_all = identical_all and identical
        speedup = spec["tok_per_s"] / base["tok_per_s"]
        row[f"k{k}_tok_per_s"] = spec["tok_per_s"]
        row[f"k{k}_speedup"] = speedup
        row[f"k{k}_acceptance_rate"] = eng["spec_acceptance_rate"]
        row[f"k{k}_drafted_tokens"] = eng["spec_drafted_tokens"]
        row[f"k{k}_accepted_tokens"] = eng["spec_accepted_tokens"]
        row[f"k{k}_launches_per_token"] = eng["launches_per_token"]
        emit(f"serve_speculative_k{k}", spec["wall_s"] * 1e6,
             f"tok_per_s={spec['tok_per_s']:.1f} "
             f"speedup={speedup:.2f}x "
             f"acceptance={eng['spec_acceptance_rate']:.2f} "
             f"identical={identical}")
    row["token_identical"] = identical_all
    rows["speculative"] = row


def _pipelined_rows(rows, quick: bool = False) -> None:
    """Sync vs pipelined unified loop on a decode-heavy workload (short
    prompts, long gens — decode cycles are where per-step host latency
    dominates and the overlap pays). Same engine config both ways; the
    only variable is ``pipeline``. Token identity is structural (the
    pipelined loop replays the same per-row numerics one step ahead) and
    the row records the check; the run fails loudly if it breaks."""
    import numpy as np

    n_requests, n_slots, prompt, gen = ((4, 2, 8, 16) if quick
                                        else (8, 4, 8, 48))
    common = dict(arch="catlm_60m", batch=n_requests, prompt_len=prompt,
                  gen=gen, transform="cat", w_bits=4, a_bits=8, kv_bits=8,
                  seed=0, n_slots=n_slots, paged=True, schedule="unified",
                  warmup=1)
    sync = serve_benchmark(**common, pipeline=False)
    pipe = serve_benchmark(**common, pipeline=True)
    es, ep = sync["engine"], pipe["engine"]
    identical = bool(np.array_equal(sync["tokens"], pipe["tokens"]))
    speedup = (pipe["tok_per_s"] / sync["tok_per_s"]
               if sync["tok_per_s"] else 0.0)
    rows["pipelined"] = {
        "workload": (f"{n_requests} reqs, {prompt}t prompt, gen {gen}, "
                     "cat w4a8 kv8, unified schedule (decode-heavy)"),
        "sync_tok_per_s": sync["tok_per_s"],
        "pipelined_tok_per_s": pipe["tok_per_s"],
        "pipelined_speedup": speedup,
        "sync_itl_p50_s": es["itl_p50_s"],
        "sync_itl_p95_s": es["itl_p95_s"],
        "pipelined_itl_p50_s": ep["itl_p50_s"],
        "pipelined_itl_p95_s": ep["itl_p95_s"],
        "mispredicts": ep["mispredicts"],
        "dispatch_per_step": ep["dispatch_per_step"],
        "launches_per_token": ep["launches_per_token"],
        "token_identical": identical,
        "n_requests": n_requests, "n_slots": n_slots,
    }
    emit("serve_pipelined", pipe["wall_s"] * 1e6,
         f"tok_per_s={pipe['tok_per_s']:.1f} "
         f"speedup={speedup:.2f}x "
         f"identical={identical}")


# results/serve_bench.json layout: {"schema_version": N, "rows": {...}}.
# Bump on any row-shape change so downstream readers can dispatch.
# v3: variant rows are steady-state (untimed warmup pass) and carry
# compile_s + the gap-attribution fields (hot_path_kib_per_token,
# device_ms_mean/host_ms_mean, dispatch_per_step, fused). Engine rows
# additionally carry launches_per_token (host dispatches amortized over
# emitted tokens — the serving-level launch-pressure column the
# two-launch decode work moves).
# v4: adds the ``pipelined`` row (sync vs depth-1 asynchronous unified
# loop: tok/s + speedup, ITL percentiles, mispredicts, token_identical),
# present in --quick artifacts too.
# v5: the ``pipelined`` row no longer carries the host-clock estimate of
# hidden host time; a profiler trace gives the device's idle share.
SCHEMA_VERSION = 5


def _dispatch_gate(rows: dict, out_path: str) -> list:
    """--quick regression gate: compare each row's ``dispatch_per_step``
    against the previously recorded artifact at ``out_path`` (same
    schema, same quick-mode workload). A rise above 5% means the engine
    started issuing more device dispatches per step — exactly the
    launch-pressure regression the fused decode path exists to prevent.
    Returns the offending row descriptions (empty = pass / no
    baseline)."""
    try:
        with open(out_path) as f:
            base = json.load(f)
    except (OSError, ValueError):
        return []           # no baseline recorded yet: nothing to gate
    if (base.get("schema_version") != SCHEMA_VERSION
            or not base.get("quick")):
        return []           # full-run baselines use a different workload
    bad = []
    for name, row in rows.items():
        ref = base.get("rows", {}).get(name, {}).get("dispatch_per_step")
        cur = row.get("dispatch_per_step")
        if ref and cur and cur > ref * 1.05:
            bad.append(f"{name}: {cur:.3f} > baseline {ref:.3f}")
    return bad


def _hot_path_kib(w_bits: int, fused: bool) -> float:
    from repro.configs import get_config

    from benchmarks.roofline_report import hot_path_bytes_per_token
    cfg = get_config("catlm_60m").smoke()
    return hot_path_bytes_per_token(cfg, w_bits=w_bits,
                                    fused=fused)["total"] / 2**10


def main(n_requests: int = 8, n_slots: int = 3, gen: int = 8,
         out_path: str = "results/serve_bench.json",
         quick: bool = False) -> None:
    rows = {}
    for name, transform, w_bits, a_bits, kv_bits in VARIANTS:
        out = serve_benchmark(arch="catlm_60m", batch=n_slots, gen=gen,
                              transform=transform, w_bits=w_bits,
                              a_bits=a_bits, kv_bits=kv_bits,
                              n_requests=n_requests, mixed=True, seed=0,
                              warmup=1 if quick else 3)
        eng = out["engine"]
        rows[name] = {
            "transform": transform, "w_bits": w_bits, "kv_bits": kv_bits,
            "ttft_s_mean": eng["ttft_s_mean"],
            "ttft_s_max": eng["ttft_s_max"],
            "itl_p50_s": eng["itl_p50_s"],
            "itl_p95_s": eng["itl_p95_s"],
            "tok_per_s": eng["tok_per_s"],
            "compile_s": eng["compile_s"],
            "occupancy_mean": eng["occupancy_mean"],
            "queue_depth_max": eng["queue_depth_max"],
            "steps": eng["steps"],
            "n_requests": eng["n_requests"],
            "n_slots": eng["n_slots"],
            "quantized_kv": eng["quantized_kv"],
            "weight_bytes": out.get("weight_bytes", 0),
            "packed_int4": out.get("packed_int4", False),
            # gap attribution: analytic hot-path HBM traffic + measured
            # host/device split and dispatch pressure per step
            "fused": eng["fused"],
            "hot_path_kib_per_token": _hot_path_kib(w_bits, eng["fused"]),
            "device_ms_mean": eng["device_ms_mean"],
            "host_ms_mean": eng["host_ms_mean"],
            "dispatch_per_step": eng["dispatch_per_step"],
            "launches_per_token": eng["launches_per_token"],
        }
        emit(f"serve_{name}", eng["wall_s"] * 1e6,
             f"tok_per_s={eng['tok_per_s']:.1f} "
             f"compile_s={eng['compile_s']:.1f} "
             f"ttft_ms={eng['ttft_s_mean'] * 1e3:.0f} "
             f"occ={eng['occupancy_mean']:.2f} "
             f"wbytes={out.get('weight_bytes', 0)}")
    if rows.get("int8") and rows.get("int4_packed"):
        r = rows["int4_packed"]["weight_bytes"] / rows["int8"]["weight_bytes"]
        emit("serve_w4_vs_w8_weight_bytes", 0.0, f"ratio={r:.2f}")
    for q in ("int8", "int4_packed"):
        if rows.get("fp") and rows.get(q):
            r = rows[q]["tok_per_s"] / rows["fp"]["tok_per_s"]
            emit(f"serve_{q}_vs_fp_steady", 0.0, f"ratio={r:.2f}")
    _prefix_rows(rows, n_slots, quick=quick)
    _speculative_rows(rows, quick=quick)
    _pipelined_rows(rows, quick=quick)
    if not quick:
        _paged_rows(rows, n_requests, n_slots)
        _unified_rows(rows, n_slots)
        _tp_rows(rows, n_requests, n_slots, gen)
    regressed = _dispatch_gate(rows, out_path) if quick else []
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"schema_version": SCHEMA_VERSION, "quick": quick,
                   "rows": rows}, f, indent=2)
    emit("serve_bench_json", 0.0, f"{out_path} schema_v{SCHEMA_VERSION}")
    # hard gate, not just a recorded field: any engine pair drifting out
    # of token identity is a correctness bug and must fail the run
    bad = sorted({name for name, row in rows.items()
                  for key, val in row.items()
                  if "token_identical" in key and val is False})
    if bad:
        raise SystemExit(f"token identity violated in rows: {bad}")
    if regressed:
        raise SystemExit("regressed vs the recorded baseline: "
                         f"{regressed}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 2 requests, variant rows plus small "
                         "prefix_shared, speculative and pipelined rows "
                         "(skips the paged/unified/tp sections); exits "
                         "nonzero if any row reports "
                         "token_identical=false, or dispatch_per_step "
                         "regresses >5% above the previously recorded "
                         "--quick artifact")
    ap.add_argument("--out", default="results/serve_bench.json")
    a = ap.parse_args()
    enable_compile_cache()
    if a.quick:
        main(n_requests=2, n_slots=2, gen=4, out_path=a.out, quick=True)
    else:
        main(out_path=a.out)
