"""The trace reduction on a small hand-made trace whose answers are
counted by hand (milliseconds in the comments), and on one engine cycle
recorded on a TPU v5e in a ``catlm.decode8`` run (``trace_tpu_decode8.json``:
the device's ``XLA Ops`` events and the benchmark's host spans, times in ns
from the cycle's start, names as ``tracefile.load`` keeps them)."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

import tracefile  # noqa: E402


@pytest.fixture
def events():
    with open(os.path.join(HERE, "data", "trace_hand.json")) as f:
        return json.load(f)


def test_busy_union_clips_and_merges(events):
    # [0,5] + [10,40] (a while and the two ops inside it) + [60,70]
    # + [95,100]
    assert tracefile.busy_s(events) == pytest.approx(0.050)
    assert tracefile.window_s(events) == pytest.approx(0.100)
    assert tracefile.idle_intervals(events) == [
        (5e6, 10e6), (40e6, 60e6), (70e6, 95e6)]


def test_idle_gaps_go_to_the_innermost_host_span(events):
    gaps = dict(tracefile.idle_gaps(events))
    # [5,8] inside sched.plan; [8,10], [40,45], [50,60], [70,80], [90,95]
    # inside an engine.step only; [45,50] engine.fetch; [80,90] observe
    assert gaps == pytest.approx({"engine.step": 0.032, "sched.plan": 0.003,
                                  "engine.fetch": 0.005,
                                  "sched.observe": 0.010})
    assert sum(gaps.values()) == pytest.approx(
        tracefile.window_s(events) - tracefile.busy_s(events))


def test_idle_without_a_span_is_named_so(events):
    events["host"] = []
    assert tracefile.idle_gaps(events) == [
        [tracefile.NO_SPAN, pytest.approx(0.050)]]


def test_per_kernel_sums_and_top_ops(events):
    assert tracefile.kernel_seconds(
        events, ("fused_cat_matmul_w4", "fused_cat_gemv_w4")) \
        == pytest.approx(0.023)    # 18 + the 5 inside the window
    assert tracefile.kernel_seconds(
        events, ("paged_attention_ragged",)) == pytest.approx(0.008)
    assert tracefile.kernel_seconds(events, ("decode_qkv_prologue",)) == 0
    # the while's own time is what its two ops leave: 30 - 18 - 8
    assert tracefile.op_times(events)["while.5"] == pytest.approx(0.004)
    top = tracefile.top_ops(events, top=3)
    assert [n for n, _ in top] == ["fused_cat_matmul_w4.36", "fusion.3",
                                   "paged_attention_ragged.9"]
    assert [v for _, v in top] == pytest.approx([0.018, 0.010, 0.008])
    assert sum(tracefile.op_times(events).values()) == pytest.approx(
        tracefile.busy_s(events))


@pytest.fixture
def recorded():
    with open(os.path.join(HERE, "data", "trace_tpu_decode8.json")) as f:
        return json.load(f)


def test_a_recorded_decode_cycle(recorded):
    busy, win = tracefile.busy_s(recorded), tracefile.window_s(recorded)
    assert 0 < busy <= win
    # nested ops (the layer loop's while and its body) are counted once
    assert sum(tracefile.op_times(recorded).values()) == pytest.approx(busy)
    # a pure-decode step on 8 slots runs the three kernels of the fast
    # path once per layer (8 layers); the device runs one step behind the
    # host, so the cycle also catches an op of the next step
    for kernel in ("decode_qkv_prologue", "fused_cat_gemv_w4",
                   "paged_attention_decode"):
        n = sum(tracefile.base_name(name) == kernel
                for name, _, _ in recorded["device"])
        assert n >= 8, (kernel, n)
        assert tracefile.kernel_seconds(recorded, (kernel,)) > 0
    gaps = dict(tracefile.idle_gaps(recorded, top=100))
    assert sum(gaps.values()) == pytest.approx(win - busy)
    assert all(" " not in n for n, _ in tracefile.top_ops(recorded))


@pytest.mark.parametrize("name,base", [
    ("fused_cat_matmul_w4.36", "fused_cat_matmul_w4"),
    ("%paged_attention_decode.2.1", "paged_attention_decode"),
    ("decode_qkv_prologue", "decode_qkv_prologue"),
    ("%fused_cat_gemv_w4.3 = f32[8,6144]{1,0} custom-call(bf16[8,512]{1,0} "
     "%fusion.51)", "fused_cat_gemv_w4")])
def test_base_name(name, base):
    assert tracefile.base_name(name) == base
