"""Roofline analysis from the dry-run's compiled artifacts (§Roofline).

Terms per (arch × shape) on the single-pod mesh, TPU v5e constants:
    compute    = HLO_FLOPs / (chips · 197e12 FLOP/s)
    memory     = HLO_bytes / (chips · 819e9 B/s)
    collective = collective_bytes / (chips · 50e9 B/s per link)

Scan-body correction: XLA's cost_analysis counts a lax.scan body ONCE.
We therefore lower each cell at L = p and L = 2p layers (p = the arch's
structure period) and extrapolate cost(L) = c(p) + (L/p - 1)·(c(2p)-c(p)).
cost_analysis numbers on the host backend are per-PROGRAM (global);
collective bytes parsed from post-SPMD HLO are per-DEVICE. We normalize
both to per-device terms.

MODEL_FLOPS uses 6·N·D (train) / 2·N·D (serve forward) with N_active for
MoE; the ratio MODEL_FLOPS / HLO_FLOPS flags remat/dispatch overcompute.

Usage: PYTHONPATH=src python -m benchmarks.roofline_report \
           [--dryrun results/dryrun.json] [--measure] [--out results/roofline.json]
`--measure` runs the extra L=p / L=2p lowers (slow); otherwise reads the
cached results/roofline_cells.json produced by an earlier --measure.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

PEAK_FLOPS = 197e12       # bf16 per chip
HBM_BW = 819e9            # B/s per chip
ICI_BW = 50e9             # B/s per link
CHIPS = 256               # single-pod 16x16

CD_BYTES = 2              # compute dtype (bf16)
ACT_CODE_BYTES = 1        # int8 activation codes


def hot_path_bytes_per_token(cfg, w_bits: int = 4,
                             fused: bool = True) -> dict:
    """Analytic HBM bytes per decoded token of the dense serving hot
    path (§Serving) — the per-layer quantized linears (wq/wk/wv, wo,
    wg/wu, wd) plus the fp LM head. Stream accounting, per linear
    (d_in, d_out), per token:

    weights — the dominant term at batch 1:
      * fused (single-launch kernel OR the ``w_eff``-prepared portable
        path): the stored codes cross HBM once — w_bits/8 B per element
        for the Pallas kernel (nibbles unpack in VMEM), CD_BYTES for the
        prebuilt ``w_eff`` copy. We charge the kernel number; pass
        ``fused=False`` for the pre-PR path.
      * unfused: every step unpacks (int8 write + read, packed only) and
        dequantizes (CD write + matmul read) a fresh weight copy on top
        of reading the stored codes.

    activations — fused reads x once and writes y once; the unfused
    chain makes ~4 extra round trips over x (block-diag out, two
    Hadamard dot stages, quant codes), each an HBM write + read at
    CD_BYTES (codes at 1 B).

    fp weights (w_bits=0) read CD_BYTES per element either way; the
    'fused' savings there are dispatch/activation-traffic only.
    Returns {"weight_bytes", "act_bytes", "total"} per token."""
    d, f = cfg.d_model, cfg.d_ff
    linears = [(d, cfg.q_dim), (d, cfg.kv_dim), (d, cfg.kv_dim),
               (cfg.q_dim, d)]
    linears += [(d, f), (d, f), (f, d)] if cfg.gated_mlp else [(d, f),
                                                               (f, d)]
    w_elem = sum(di * do for di, do in linears) * cfg.n_layers
    if not w_bits:
        w_bytes_per_elem = float(CD_BYTES)
    elif fused:
        w_bytes_per_elem = w_bits / 8.0
    else:
        unpack = 2.0 if w_bits == 4 else 0.0         # int8 write + read
        w_bytes_per_elem = w_bits / 8.0 + unpack + 2.0 * CD_BYTES
    weight_bytes = w_elem * w_bytes_per_elem
    weight_bytes += cfg.d_model * cfg.vocab * CD_BYTES   # fp LM head
    act = 0.0
    for di, do in linears:
        if fused or not w_bits:
            act += (di + do) * CD_BYTES
        else:
            act += di * (7 * CD_BYTES + 2 * ACT_CODE_BYTES) + do * CD_BYTES
    act *= cfg.n_layers
    return {"weight_bytes": weight_bytes, "act_bytes": act,
            "total": weight_bytes + act}


def decode_launches_per_layer(fused_prologue: bool = True) -> dict:
    """Analytic device-launch count per transformer layer per decode
    step on the paged serving path (§Serving). The attention block is
    where the launch pressure lives at batch 1 — each launch is a
    kernel dispatch whose fixed overhead rivals the tiny per-token
    compute:

      * composed (``fused_prologue=False``): the CAT->quant->W4A8 QKV
        GEMV kernel, then an XLA glue program (RoPE rotation + int8 KV
        quantize + paged-pool scatter), then the online-softmax paged
        attention kernel — 3 launches.
      * fused (``fused_prologue=True``): the QKV prologue kernel
        absorbs the transform, activation quant, GEMV, RoPE, KV
        quantize and pool scatter behind one scalar-prefetched grid,
        leaving prologue + paged attention — the two-launch decode.

    The epilogue (o-proj and the MLP) already runs through the fused
    CAT GEMV kernels either way and is listed for the per-layer total.
    HBM bytes/token are unchanged by the fusion (same weights, same KV
    writes — see ``hot_path_bytes_per_token``); the win is launches.
    Returns {"attention", "epilogue", "total"} launches per layer."""
    attention = 2 if fused_prologue else 3
    epilogue = 2                   # o-proj GEMV + fused MLP GEMV chain
    return {"attention": attention, "epilogue": epilogue,
            "total": attention + epilogue}


def decode_launch_table() -> str:
    """Launches per decode layer, composed vs fused-prologue — the
    companion column to ``serve_bytes_table`` (bytes/token identical,
    launch count is the mover)."""
    hdr = (f"{'path':18s} {'attention':>10s} {'epilogue':>9s} "
           f"{'total':>6s}")
    lines = ["device launches per decode layer (paged serving path)",
             hdr, "-" * len(hdr)]
    for name, fused in (("composed", False), ("fused prologue", True)):
        c = decode_launches_per_layer(fused_prologue=fused)
        lines.append(f"{name:18s} {c['attention']:>10d} "
                     f"{c['epilogue']:>9d} {c['total']:>6d}")
    return "\n".join(lines)


def serve_bytes_table(arch: str = "catlm_60m", smoke: bool = True) -> str:
    """Per-token HBM traffic of the serving hot path, fused vs unfused,
    at the bench's weight widths (``python -m benchmarks.roofline_report
    --serve-bytes``)."""
    from repro.configs import get_config
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    hdr = (f"{'variant':14s} {'w_kiB/tok':>10s} {'act_kiB/tok':>12s} "
           f"{'total_kiB':>10s} {'vs fp':>6s}")
    lines = [f"serving hot-path HBM bytes/token — {arch}"
             f"{' (smoke)' if smoke else ''}", hdr, "-" * len(hdr)]
    fp = hot_path_bytes_per_token(cfg, w_bits=0)
    for name, w_bits, fused in (("fp", 0, True),
                                ("int8 unfused", 8, False),
                                ("int8 fused", 8, True),
                                ("int4 unfused", 4, False),
                                ("int4 fused", 4, True)):
        b = hot_path_bytes_per_token(cfg, w_bits=w_bits, fused=fused)
        lines.append(f"{name:14s} {b['weight_bytes'] / 2**10:10.1f} "
                     f"{b['act_bytes'] / 2**10:12.2f} "
                     f"{b['total'] / 2**10:10.1f} "
                     f"{b['total'] / fp['total']:6.2f}")
    return "\n".join(lines)


def model_flops(arch: str, shape: str, n_params: float,
                n_active: float) -> float:
    """6·N·D train, 2·N·D forward-only (D = tokens processed)."""
    from repro.launch.specs import SHAPES
    info = SHAPES[shape]
    if info["kind"] == "train":
        toks = info["batch"] * info["seq"]
        return 6.0 * n_active * toks
    if info["kind"] == "prefill":
        toks = info["batch"] * info["seq"]
        return 2.0 * n_active * toks
    return 2.0 * n_active * info["batch"]  # decode: one token per slot


def param_counts(arch: str):
    import jax
    from repro.configs import get_config
    from repro.models import build
    from repro.models.model import active_param_count, param_count
    cfg = get_config(arch)
    model = build(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    if cfg.n_experts:
        expert = sum(int(np.prod(shapes["layers"][k].shape))
                     for k in ("we_g", "we_u", "we_d"))
        act = n - expert + int(expert * cfg.top_k / cfg.n_experts)
    else:
        act = n
    return n, act


def extrapolate(c_p: dict, c_2p: dict, n_layers: int, period: int) -> dict:
    """cost(L) = c(p) + (L/p - 1)·Δ for flops/bytes/collectives."""
    reps = n_layers / period - 1.0

    def ex(a, b):
        return a + reps * (b - a)

    def mem(c, f):
        return float(c["memory"].get(f, 0))

    out = {
        "flops": ex(c_p["flops"], c_2p["flops"]),
        "bytes_accessed": ex(c_p["bytes_accessed"], c_2p["bytes_accessed"]),
        # fusion-floor traffic: every arg/output crosses HBM once, every
        # temp buffer is written+read (temp extrapolates with L; args are
        # dominated by params, which do NOT scale with our L override for
        # the stacked leaves — they do, actually: stacked (L, ...) leaves
        # scale linearly, so plain extrapolation is right for both)
        "bytes_floor": ex(mem(c_p, "argument_size_in_bytes")
                          + mem(c_p, "output_size_in_bytes")
                          + 2 * mem(c_p, "temp_size_in_bytes"),
                          mem(c_2p, "argument_size_in_bytes")
                          + mem(c_2p, "output_size_in_bytes")
                          + 2 * mem(c_2p, "temp_size_in_bytes")),
        "collective_bytes": {},
    }
    keys = set(c_p["collective_bytes"]) | set(c_2p["collective_bytes"])
    for k in keys:
        out["collective_bytes"][k] = ex(
            c_p["collective_bytes"].get(k, 0.0),
            c_2p["collective_bytes"].get(k, 0.0))
    return out


def measure_cells(out_path: str, archs=None, shapes=None) -> dict:
    """Runs the L=p / L=2p lowers for every runnable cell (single pod)."""
    from repro.launch.dryrun import run_cell
    from repro.launch.specs import ARCHS, SHAPES, cell_config, \
        cell_runnable, layer_period
    cells = {}
    if os.path.exists(out_path):
        cells = json.load(open(out_path))
    for arch in archs or ARCHS:
        for shape in shapes or list(SHAPES):
            ok, _ = cell_runnable(arch, shape)
            if not ok:
                continue
            key = f"{arch}|{shape}"
            if key in cells:
                continue
            cfg = cell_config(arch, shape)
            p = layer_period(cfg)
            try:
                # exact_cost unrolls every scan (layers, attention chunks,
                # GLA chunks, loss chunks) so HLO op counts are exact at
                # these small layer counts — see repro/models/flags.py
                c_p = run_cell(arch, shape, False, n_layers=p,
                               exact_cost=True)
                c_2p = run_cell(arch, shape, False, n_layers=2 * p,
                                exact_cost=True)
                cells[key] = {"p": p, "c_p": c_p, "c_2p": c_2p,
                              "n_layers": cfg.n_layers}
            except Exception as e:  # noqa: BLE001
                cells[key] = {"error": f"{type(e).__name__}: {e}"}
            json.dump(cells, open(out_path, "w"), indent=1, sort_keys=True)
            print(f"measured {key}", flush=True)
    return cells


def build_report(dryrun: dict, cells: dict) -> list:
    rows = []
    pc_cache: dict = {}
    for key, cell in sorted(cells.items()):
        if "error" in cell:
            rows.append({"cell": key, "error": cell["error"]})
            continue
        arch, shape = key.split("|")
        full = dryrun.get(f"{arch}|{shape}|single", {})
        ex = extrapolate(cell["c_p"], cell["c_2p"], cell["n_layers"],
                         cell["p"])
        # cost_analysis flops/bytes and HLO collective shapes are all
        # per-DEVICE (the compiled module is the SPMD per-device program —
        # verified against hand-computed catlm numbers, DESIGN.md §6).
        flops_dev = ex["flops"]
        coll_dev = ex["collective_bytes"].get("total", 0.0)
        t_comp = flops_dev / PEAK_FLOPS
        # memory: cost_analysis bytes ignore fusion (10-20x ceiling); the
        # floor assumes perfect fusion (args+outputs once, temps twice).
        t_mem_hi = ex["bytes_accessed"] / HBM_BW
        t_mem = ex["bytes_floor"] / HBM_BW
        t_coll = coll_dev / ICI_BW
        dominant = max((t_comp, "compute"), (t_mem, "memory"),
                       (t_coll, "collective"))[1]
        if arch not in pc_cache:
            pc_cache[arch] = param_counts(arch)
        n, act = pc_cache[arch]
        mf = model_flops(arch, shape, n, act)
        useful = (mf / CHIPS) / max(flops_dev, 1.0)
        bound = max(t_comp, t_mem, t_coll)
        rows.append({
            "cell": key,
            "t_compute_s": t_comp, "t_memory_s": t_mem,
            "t_memory_nofusion_s": t_mem_hi,
            "t_collective_s": t_coll,
            "dominant": dominant,
            "model_flops": mf, "hlo_flops": ex["flops"],
            "useful_flops_ratio": useful,
            "roofline_fraction": (mf / CHIPS / PEAK_FLOPS) / bound
            if bound > 0 else 0.0,
            "mem_gib_per_dev": (full.get("memory", {})
                                .get("argument_size_in_bytes", 0)
                                + full.get("memory", {})
                                .get("temp_size_in_bytes", 0)) / 2**30,
            "collective_breakdown": ex["collective_bytes"],
        })
    return rows


def fmt_table(rows: list) -> str:
    hdr = (f"{'cell':38s} {'compute':>10s} {'memory':>10s} {'collect':>10s}"
           f" {'bound':>10s} {'useful':>7s} {'roofl%':>7s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        if "error" in r:
            lines.append(f"{r['cell']:38s} ERROR {r['error'][:60]}")
            continue
        lines.append(
            f"{r['cell']:38s} {r['t_compute_s']:10.3e} "
            f"{r['t_memory_s']:10.3e} {r['t_collective_s']:10.3e} "
            f"{r['dominant']:>10s} {r['useful_flops_ratio']:7.2f} "
            f"{100*r['roofline_fraction']:7.1f}")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="results/dryrun.json")
    ap.add_argument("--cells", default="results/roofline_cells.json")
    ap.add_argument("--out", default="results/roofline.json")
    ap.add_argument("--measure", action="store_true")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--serve-bytes", action="store_true",
                    help="print the analytic serving hot-path HBM "
                         "bytes/token table (fused vs unfused) and exit")
    ap.add_argument("--launches", action="store_true",
                    help="print the per-decode-layer device-launch "
                         "table (composed vs fused QKV prologue) and "
                         "exit")
    args = ap.parse_args()

    if args.serve_bytes or args.launches:
        if args.serve_bytes:
            print(serve_bytes_table(args.arch or "catlm_60m"))
        if args.launches:
            print(decode_launch_table())
        return
    if args.measure:
        measure_cells(args.cells,
                      archs=[args.arch] if args.arch else None,
                      shapes=[args.shape] if args.shape else None)
    dryrun = json.load(open(args.dryrun)) if os.path.exists(args.dryrun) \
        else {}
    cells = json.load(open(args.cells)) if os.path.exists(args.cells) else {}
    rows = build_report(dryrun, cells)
    json.dump(rows, open(args.out, "w"), indent=1)
    print(fmt_table(rows))


if __name__ == "__main__":
    main()
