"""A whole run of a cell on the CPU at a tiny size, without the chip: the
open loop, the window, and the comparison that decides ``correct``.

- a sound run comes out correct;
- with the timed path broken underneath, ``correct`` comes out false: once
  with a token altered where the executor produces it, once with steps
  that leave the KV pool (the serving state) unchanged;
- the control, the reference with 4-bit activations in the program's
  place, goes through the same comparison and comes out not correct,
  while the program's own gap over the same positions meets the limit.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

import check  # noqa: E402
import registry  # noqa: E402
import run  # noqa: E402

CONF = {"name": "tiny", "arch": "catlm_60m", "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 256, "rope_theta": 10000.0,
        "tie_word_embeddings": True,
        "program": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                    "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                    "vocab": 256, "kv_quant_bits": 8},
        "engine": {"schedule": "unified", "paged_kernel": True,
                   "page_size": 16, "prefix_cache": False}}
MIX = {"rate": 4.0, "n_slots": 4, "max_batch_tokens": 64,
       "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                  "min": 8, "max": 64},
       "output": {"dist": "uniform", "min": 4, "max": 16}, "pool": 16,
       "settle_s": 0.5}
# at this size sound runs read widest gaps of 0.002-0.013 and the
# control 0.08-0.16 (3 seeds each, CPU)
LIMITS = {"max_gap": 0.04, "sample_tokens": 64, "sample_requests": 2}
SEED = 2**31 + 11


def cell_run(seed=SEED, seconds=1.5, **kw):
    bm = dict(registry.benchmark(),
              workloads=[{"name": "tiny.cell", "config": "tiny",
                          "traffic": "tiny", "chips": 1}])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    return run.run_cell(args, bm, bm["workloads"][0], CONF, MIX,
                        jax.devices(), registry.peaks("TPU v5 lite"),
                        LIMITS, **kw)


def test_a_sound_run_is_correct_and_reports_every_metric():
    out = cell_run()
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   registry.benchmark()["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "check"


def _altered_token(orig):
    """The first token of each step's output altered where the executor
    produces it."""
    def step(self, *a):
        toks = orig(self, *a)
        return toks.at[0].set((toks[0] + 1) % CONF["vocab_size"])
    return step


def _state_unchanged(orig):
    """Each step returns the KV pool as it found it (nothing written)."""
    def step(self, *a):
        old = self.cache
        toks = orig(self, *a)
        self.cache = old
        return toks
    return step


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["altered_token", "state_unchanged"])
def test_a_broken_timed_path_makes_the_run_incorrect(monkeypatch, fault):
    from repro.launch.executor import RaggedExecutor

    for name in ("step", "decode_step"):
        monkeypatch.setattr(RaggedExecutor, name,
                            fault(getattr(RaggedExecutor, name)))
    out = cell_run()
    assert not out["correct"]
    assert out["check"]["max_gap"]["value"] > LIMITS["max_gap"]


def test_the_control_fails_the_limit_the_program_meets():
    got = {}
    out = cell_run(seed=5, control=True, readings=got)
    assert not out["correct"]
    assert out["check"]["max_gap"]["value"] == got["control"]
    assert got["program"] <= LIMITS["max_gap"] < got["control"]


def test_no_chip_no_result(capsys):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sys, "argv", ["run.py"])
        rc = run.main(["--workload", "catlm.azconv", "--seed", "1",
                       "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_the_sample_holds_the_longest_and_enough_requests():
    import numpy as np
    finished = {r: (np.zeros(10 + r), np.zeros(100)) for r in range(12)}
    got = check.sample(finished, SEED, tokens=150, requests=5)
    assert got[0] == 11 and len(got) == 5 and len(set(got)) == 5
    assert len(check.sample(finished, SEED, tokens=450, requests=1)) == 5
    assert sorted(check.sample(finished, SEED, 10**6, 99)) == list(range(12))
