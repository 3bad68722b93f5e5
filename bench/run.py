"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name
(``BENCHMARK.json``, ``bench/configs``, ``bench/traffic``). One process:

1. refuses to run without a TPU, with fewer chips than the cell asks
   for, or with ``REPRO_PALLAS_INTERPRET`` / ``REPRO_DECODE_FUSED`` set;
2. turns on JAX's persistent compilation cache (``.jax_cache/`` in the
   checkout, or ``JAX_COMPILATION_CACHE_DIR``);
3. set-up: makes the CAT-W4A8 parameters on the device from the seed
   (``params.py``), builds the ``ServeEngine`` with the configuration's
   and the mix's keywords, serves batches of short requests until both
   step programs have run (that compiles every shape the window uses),
   then starts the open loop's arrivals and lets them settle;
4. measures the open loop for ``--seconds`` (``window.py``); with
   ``--trace 1`` the last ``TRACE_SECONDS`` of the window are traced
   and the per-layer metrics are read from that part (and from the
   whole window's stamps); stopping the profiler and reading its trace
   come after the window has closed;
5. reads the peak device memory, frees the engine, and compares a sample
   of the window's finished requests with the plain reference
   (``check.py``);
6. prints the comparison's numbers beside their limits on stderr, then
   the result as one JSON line, last on stdout.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SWITCHES = ("REPRO_PALLAS_INTERPRET", "REPRO_DECODE_FUSED")
TRACE_SECONDS = 4.0
# new tokens per request while warm-up compiles the step programs
WARM_NEW_TOKENS = 4
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


class Run:
    """What the per-layer metric readers read (``metrics/<name>.py``)."""

    def __init__(self, dims, max_batch_tokens, peaks, kernels):
        self.dims, self.max_batch_tokens = dims, max_batch_tokens
        self.peaks, self.kernels = peaks, kernels
        self.steps, self.dispatches = [], []
        self.counts = {"unified": 0, "decode": 0}
        self.engine_step_s, self.engine_device_s = [], []
        self.events = None
        self.busy_s = self.window_s = 0.0
        self.window = {}   # the whole window's end-to-end numbers

    def kernel_s(self, kernel: str):
        import tracefile
        if self.events is None:
            return None
        t = tracefile.kernel_seconds(self.events, self.kernels[kernel].NAMES)
        return t or None

    def min_time(self, kernel: str) -> tuple:
        """(least seconds for the window's real work, "compute" | "memory")."""
        mod = self.kernels[kernel]
        ops = byts = 0.0
        for rec in self.dispatches:
            o, b = mod.cost(self.dims, rec)
            ops, byts = ops + o, byts + b
        tc = ops / self.peaks[mod.PEAK]
        tm = byts / self.peaks["hbm_bytes_per_s"]
        return max(tc, tm), ("compute" if tc >= tm else "memory")

    def roofline(self, kernel: str):
        t = self.kernel_s(kernel)
        if t is None:
            return None
        least, bound = self.min_time(kernel)
        if least <= 0:
            return None
        say(f"{kernel}_roofline bound={bound} least_s={least} "
            f"kernel_s={t}")
        return 100.0 * least / t


def dims_of(cfg, kw) -> dict:
    return {"d_model": cfg.d_model, "q_dim": cfg.q_dim,
            "kv_dim": cfg.kv_dim, "d_ff": cfg.d_ff,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "n_layers": cfg.n_layers,
            "vocab": cfg.vocab, "n_slots": kw["n_slots"]}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load(workload: str) -> tuple:
    """-> (BENCHMARK.json, the cell, its configuration, its mix), found
    by the cell's name; puts ``bench/`` and ``src/`` on the path."""
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import registry

    bm = registry.benchmark(ROOT)
    cell = registry.workload(bm, workload)
    return (bm, cell, registry.config(bm, cell["config"], ROOT),
            registry.traffic(cell["traffic"]))


def chips(cell: dict):
    """The devices, or None (and why, on stderr) where the environment
    switches a kernel path or JAX finds fewer TPU chips than the cell
    asks for."""
    set_switches = [v for v in SWITCHES if v in os.environ]
    if set_switches:
        say(f"refusing to run with {set_switches} set: they change the "
            f"kernel path the cell measures")
        return None
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        say(f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return None
    return devs


def main(argv=None) -> int:
    args = parse(argv)
    bm, cell, conf, mix = load(args.workload)
    devs = chips(cell)
    if devs is None:
        return 2
    import check
    import registry

    peaks = registry.peaks(devs[0].device_kind)
    result = run_cell(args, bm, cell, conf, mix, devs, peaks,
                      check.limits(cell["name"]))
    print(json.dumps(result), flush=True)
    return 0


class Served:
    """One cell's engine, built and warmed up: everything set-up makes."""

    def __init__(self, conf: dict, mix: dict, seed: int):
        import jax

        import cell as cellmod
        import driver
        import params as bparams
        from repro.launch.engine import ServeEngine
        from repro.models import build
        from traffic import Traffic

        self.seed, self.mix = seed, mix
        self.cfg = cellmod.program_config(conf)
        self.traffic = Traffic(mix, seed, self.cfg.vocab)
        self.kw = cellmod.engine_kwargs(conf, mix, self.traffic.max_len)
        t = time.perf_counter()
        qparams = bparams.make_params(self.cfg, seed)
        jax.block_until_ready(qparams)
        say(f"params {time.perf_counter() - t:.3f}s")
        # the reference reads the layers from the host after the window
        self.host_layers = jax.device_get(qparams["layers"])
        model = build(self.cfg)
        sparams = bparams.serving_params(model, qparams)
        del qparams
        self.engine = ServeEngine(model, sparams, **self.kw)
        del sparams
        gc.collect()
        self.disp = driver.Dispatches(self.engine.exec)
        self.loop = None

    def warm_up(self) -> int:
        """Compile, then settle. Batches of ``n_slots`` requests of the
        cell's traffic, cut to ``WARM_NEW_TOKENS`` new tokens, are served
        until drained, until each step program has run twice (they compile
        here); then the open loop starts (``start``). -> engine cycles."""
        eng = self.engine
        kinds = ["unified"] + (["decode"] if
                               eng.exec.supports_decode_step else [])
        n = i = 0
        while any(self.disp.counts[k] < 2 for k in kinds):
            if i >= 8 * self.kw["n_slots"]:
                raise RuntimeError(f"warm-up ran {self.disp.counts}")
            for _ in range(self.kw["n_slots"]):
                prompt, max_new = self.traffic.request(i)
                eng.submit(prompt, min(max_new, WARM_NEW_TOKENS))
                i += 1
            while not eng.idle:
                eng.step()
                n += 1
        self.t0 = self.start(self.traffic)
        return n

    def start(self, traffic) -> float:
        """Drain and reset the engine, and start ``traffic``'s open loop
        about ``settle_s`` before the first request of its second block
        of ``pool`` requests is due; run it until then, so that the window
        opens on the system at its load. -> that due time, where the
        window starts: a window of ``pool / rate`` seconds then holds the
        arrivals of exactly one block, the same sizes for every seed."""
        import driver

        while not self.engine.idle:
            self.engine.step()
        self.engine.reset()
        self.loop = driver.OpenLoop(self.engine, traffic)
        pool = traffic.pool
        first = max(0, pool - round(self.mix["settle_s"] * traffic.rate))
        self.loop.start(time.perf_counter(), first)
        t0 = self.loop.due(pool)
        self.loop.run_until(t0)
        return t0

    def close(self, t0: float, t1: float) -> dict:
        """After the window: the finished requests, counts, and the
        engine freed. -> {"finished", "attempted", "failed"}."""
        import jax

        loop = self.loop
        finished = loop.finished(t0, t1)
        out = {"finished": finished, "attempted": loop.in_window(t0, t1),
               "failed": loop.malformed(finished, self.cfg.vocab)}
        jax.block_until_ready(self.engine.exec.cache)
        del self.loop, self.engine, self.disp, loop
        gc.collect()
        return out

    def reference_params(self) -> dict:
        """The parameters the reference reads: the float embedding and
        head made again from the seed, and the host copy of the layers."""
        import params as bparams

        return dict(bparams.embed_and_head(self.cfg, self.seed),
                    layers=self.host_layers)


COMPILES: list = []   # the time of each backend compile in this process
_LISTENING = []


def compile_cache() -> list:
    """Persistent compilation cache on, every program kept; -> the list
    that collects the time of each backend compile (set up once per
    process: ``control.py`` runs the cell many times)."""
    import jax

    from repro.launch.serve import enable_compile_cache

    if not _LISTENING:
        _LISTENING.append(True)
        say("compile cache", enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, secs, **kw: COMPILES.append(time.perf_counter())
            if ev == "/jax/core/compile/backend_compile_duration" else None)
    return COMPILES


def gc_pauses():
    """Records the length of each of Python's garbage collections from
    now on (``.seconds``), so that a run can tell a collection from a
    stall of the machine; ``.callback`` is in ``gc.callbacks``."""
    rec = argparse.Namespace(seconds=[], start=0.0)

    def callback(phase, info):
        if phase == "start":
            rec.start = time.perf_counter()
        else:
            rec.seconds.append(time.perf_counter() - rec.start)

    rec.callback = callback
    gc.callbacks.append(callback)
    return rec


def run_cell(args, bm, cell, conf, mix, devs, peaks, lim,
             control: bool = False, readings: dict | None = None) -> dict:
    """One run of the cell -> its result line. ``control``: the number
    compared is the control's (``check.compare``), so the run must come
    out not correct; ``readings`` collects what the comparison read."""
    import check
    import registry
    import window

    compiles = compile_cache()
    srv = Served(conf, mix, args.seed)
    n_warm = srv.warm_up()
    # what set-up made lives on: keep it out of the window's collections
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    say(f"setup {setup_s:.3f}s warm-up steps {n_warm} "
        f"dispatches {srv.disp.counts}")

    run = Run(dims_of(srv.cfg, srv.kw), srv.kw["max_batch_tokens"], peaks,
              registry.kernels())
    n_compiles = len(compiles)
    pauses = gc_pauses()
    t0 = srv.t0
    t1 = t0 + args.seconds
    if args.trace:
        srv.loop.run_until(t1 - min(TRACE_SECONDS, args.seconds))
        traced(srv.loop, srv.disp, srv.engine, run, t1)
    srv.loop.run_until(t1)
    records = srv.loop.records
    e2e = run.window = window.metrics(records, t0, t1)
    gc.callbacks.remove(pauses.callback)
    say(f"window {t1 - t0:.3f}s {window.counts(records, t0, t1)} "
        f"generator late by at most {srv.loop.late_max(t0, t1):.6f}s "
        f"compiles in window {len(compiles) - n_compiles} "
        f"dispatches {srv.disp.counts} garbage collections "
        f"{len(pauses.seconds)}, longest {max(pauses.seconds, default=0)}s")
    stats = devs[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    done = srv.close(t0, t1)

    rids = check.sample(done["finished"], args.seed, lim["sample_tokens"],
                        lim["sample_requests"])
    t = time.perf_counter()
    got = check.compare(srv.cfg, srv.reference_params(), done["finished"],
                        rids, control) if rids else {
        "max_gap": float("inf"), "requests": 0, "served_tokens": 0}
    say(f"reference {time.perf_counter() - t:.3f}s over {got['requests']} "
        f"requests, {got['served_tokens']} served tokens"
        + (f"; program's own gap {got['program']}" if control else ""))
    if readings is not None:
        readings.update(got, failed=done["failed"])
    checks = {"max_gap": {"value": got["max_gap"], "limit": lim["max_gap"]},
              "failed": {"value": done["failed"], "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        say(f"check {name} {c['value']} limit {c['limit']}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": done["attempted"],
           "failed": done["failed"]}
    if args.trace:
        out["metrics"] = layer_metrics(bm, cell, run)
        device.update(busy_s=run.busy_s, window_s=run.window_s)
        out["breakdown"] = run.breakdown
    else:
        metrics = dict(e2e, setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                      "unit": m["unit"]}
                          for m in registry.cell_metrics(bm, cell["name"],
                                                         "end_to_end")}
    out["device"] = device
    out["check"] = checks
    return out


def traced(loop, disp, engine, run, t1) -> None:
    """Trace the window from now until ``t1``, its close, and fill
    ``run``."""
    import jax

    import driver
    import tracefile

    if engine._inflight is not None:
        jax.block_until_ready(engine._inflight.toks)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    spans = driver.Spans(engine)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    n_step, n_dev = len(engine.metrics["step_s"]), len(
        engine.metrics["device_s"])
    counts0 = dict(disp.counts)
    disp.detail = True
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    spans.install()
    with jax.profiler.TraceAnnotation(tracefile.WINDOW):
        loop.run_until(t1, run.steps)
        spans.remove()
        if engine._inflight is not None:
            jax.block_until_ready(engine._inflight.toks)
    jax.profiler.stop_trace()
    disp.detail = False
    run.dispatches = disp.records
    run.counts = {k: disp.counts[k] - counts0[k] for k in disp.counts}
    run.engine_step_s = engine.metrics["step_s"][n_step:]
    run.engine_device_s = engine.metrics["device_s"][n_dev:]
    t = time.perf_counter()
    run.events = tracefile.load(TRACE_DIR, driver.SPAN_NAMES)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    run.busy_s = tracefile.busy_s(run.events)
    run.window_s = tracefile.window_s(run.events)
    run.breakdown = {"device_ops": tracefile.top_ops(run.events),
                     "idle_gaps": tracefile.idle_gaps(run.events)}
    say(f"trace read in {time.perf_counter() - t:.3f}s: "
        f"{len(run.events['device'])} device events, "
        f"{len(run.events['host'])} host spans, busy {run.busy_s:.6f}s "
        f"of {run.window_s:.6f}s")


def layer_metrics(bm, cell, run) -> dict:
    import registry

    out = {}
    for m in registry.cell_metrics(bm, cell["name"], "per_layer"):
        v = registry.metric_reader(m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


if __name__ == "__main__":
    sys.exit(main())
