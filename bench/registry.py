"""Finds a cell's configuration, traffic mix, metric readers and kernel
cost functions by name.

Everything that belongs to one configuration, one mix or one metric sits
in a file of its own under ``bench/``; ``BENCHMARK.json`` at the root of
the checkout ties them together:

- ``configs/<config>.json``: the file named by the config's ``file``;
- ``traffic/<mix>.json``: the parameters ``bench/traffic.py`` reads;
- ``metrics/<metric>.py``: one reader, ``read(run) -> float | None``;
- ``kernels/<kernel>.py``: ``NAMES`` (the trace's op names) and
  ``cost(call) -> (ops, bytes)``, with ``PEAK`` naming the peak it uses.

Adding a cell, a mix, a configuration or a metric adds files and entries;
no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bm['workloads']]})")


def config(bm: dict, name: str, root: str = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, bench: str = BENCH) -> dict:
    with open(os.path.join(bench, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: str = BENCH):
    return _load(os.path.join(bench, "metrics", f"{name}.py"), name)


def kernels(bench: str = BENCH) -> dict:
    """kernel name -> its cost module, for every file under kernels/."""
    d = os.path.join(bench, "kernels")
    return {f[:-3]: _load(os.path.join(d, f), f[:-3])
            for f in sorted(os.listdir(d))
            if f.endswith(".py") and not f.startswith("_")}


def cell_metrics(bm: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` ("end_to_end" | "per_layer") this cell
    reports: those without a ``workloads`` list, and those that name it."""
    return [m for m in bm[section]
            if "workloads" not in m or cell in m["workloads"]]


def peaks(device_kind: str, bench: str = BENCH) -> dict:
    with open(os.path.join(bench, "peaks.json")) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have "
                       f"{sorted(table['devices'])})") from None
