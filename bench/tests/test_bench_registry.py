"""Finding configurations, mixes, metrics and peaks by name."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import pytest  # noqa: E402

import registry  # noqa: E402


def test_peaks_reject_an_unknown_device_kind():
    assert registry.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="no peaks for device kind"):
        registry.peaks("TPU v9 imaginary")


def test_the_benchmark_names_files_that_exist():
    bm = registry.benchmark()
    for c in bm["configs"]:
        assert registry.config(bm, c["name"])["name"] == c["name"]
    for w in bm["workloads"]:
        mix = registry.traffic(w["traffic"])
        assert mix["rate"] > 0 and mix["n_slots"] >= 1
        # a window holds the arrivals of exactly one block of the pool
        assert mix["pool"] / mix["rate"] == pytest.approx(bm["run_seconds"])
        with open(os.path.join(registry.BENCH, "checks",
                               f"{w['name']}.json")) as f:
            assert json.load(f)["max_gap"] > 0
        for m in registry.cell_metrics(bm, w["name"], "per_layer"):
            assert callable(registry.metric_reader(m["name"]).read)
    assert set(registry.kernels()) >= {"fused_cat_w4", "paged_attn"}


def test_new_files_are_found_by_name_alone(tmp_path):
    """A later PR adds a configuration, a mix, a cell and a metric as new
    files and entries; nothing already there changes."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "new-model.json").write_text(
        json.dumps({"name": "new-model", "arch": "catlm_60m"}))
    (bench / "traffic" / "newmix.json").write_text(
        json.dumps({"rate": 3}))
    (bench / "metrics" / "new.metric.py").write_text(
        "def read(run):\n    return run['x'] * 2\n")
    bm = {"configs": [{"name": "new-model",
                       "file": "bench/configs/new-model.json"}],
          "workloads": [{"name": "new.cell", "config": "new-model",
                         "traffic": "newmix", "chips": 1}],
          "end_to_end": [{"name": "setup_s"}],
          "per_layer": [{"name": "new.metric", "workloads": ["new.cell"]},
                        {"name": "other", "workloads": ["old.cell"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    got = registry.benchmark(str(tmp_path))
    cell = registry.workload(got, "new.cell")
    assert registry.config(got, cell["config"], str(tmp_path))["arch"] \
        == "catlm_60m"
    assert registry.traffic(cell["traffic"], str(bench))["rate"] == 3
    names = [m["name"] for m in registry.cell_metrics(got, "new.cell",
                                                      "per_layer")]
    assert names == ["new.metric"]
    assert registry.metric_reader("new.metric", str(bench)).read(
        {"x": 21}) == 42
    with pytest.raises(KeyError):
        registry.workload(got, "missing.cell")
