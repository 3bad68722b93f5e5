"""Operations and bytes of each kernel cost function against counts made
by hand, at one Mistral-NeMo shape and one catlm-60m shape."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import pytest  # noqa: E402

import registry  # noqa: E402

NEMO = {"d_model": 5120, "q_dim": 4096, "kv_dim": 1024, "d_ff": 14336,
        "n_heads": 32, "n_kv_heads": 8, "head_dim": 128, "n_layers": 8,
        "vocab": 131072, "n_slots": 32}
CATLM = {"d_model": 512, "q_dim": 512, "kv_dim": 256, "d_ff": 1536,
         "n_heads": 8, "n_kv_heads": 4, "head_dim": 64, "n_layers": 8,
         "vocab": 8192, "n_slots": 8}
# a unified step: one 500-row prefill chunk at positions 0..499 and two
# decode rows at context lengths 1,000 and 2,000
NEMO_STEP = {"kind": "unified", "q_len": [500, 1, 1],
             "kv_len": [500, 1000, 2000], "logits": 3}
# a pure-decode step with 5 of the 8 slots decoding
CATLM_DECODE = {"kind": "decode", "q_len": [1] * 5,
                "kv_len": [10, 20, 30, 40, 50], "logits": 5}


@pytest.fixture(scope="module")
def kernels():
    return registry.kernels()


def test_fused_cat_w4_nemo_unified(kernels):
    ops, byts = kernels["fused_cat_w4"].cost(NEMO, NEMO_STEP)
    # K·N summed: 5120·6144 + 4096·5120 + 5120·28672 + 14336·5120
    kn = 31_457_280 + 20_971_520 + 146_800_640 + 73_400_320
    assert ops == 2 * 502 * kn * 8
    # per layer: int4 weights kn/2, f32 scales 4·ΣN, bf16 rows in
    # 2·502·ΣK, f32 rows out 4·502·ΣN; ΣK = 28,672, ΣN = 45,056
    assert byts == 8 * (kn / 2 + 4 * 45_056 + 2 * 502 * 28_672
                        + 4 * 502 * 45_056)


def test_fused_cat_w4_catlm_decode_leaves_qkv_to_the_prologue(kernels):
    ops, byts = kernels["fused_cat_w4"].cost(CATLM, CATLM_DECODE)
    kn = 262_144 + 1_572_864 + 786_432      # o, gate|up, down
    assert ops == 2 * 5 * kn * 8
    assert byts == 8 * (1_310_720 + 16_384 + 25_600 + 81_920)


def test_paged_attn(kernels):
    ops, byts = kernels["paged_attn"].cost(CATLM, CATLM_DECODE)
    # one query per item, 10+20+30+40+50 = 150 keys, 8 heads of 64
    assert ops == 8 * 4 * 64 * 8 * 150
    # k and v: 150 rows · 4 heads · (64 codes + 4 scale bytes), twice;
    # q in and out: 5 · 8 · 64 · 2 bytes, twice
    assert byts == 8 * (2 * 150 * 4 * 68 + 5 * 2 * 2 * 8 * 64)
    ops, byts = kernels["paged_attn"].cost(NEMO, NEMO_STEP)
    # causal chunk: 500·501/2 = 125,250 (query, key) pairs; + 1000 + 2000
    pairs = 125_250 + 1_000 + 2_000
    assert ops == 8 * 4 * 128 * 32 * pairs
    assert byts == 8 * (2 * 3_500 * 8 * 132 + 2 * 2 * 502 * 32 * 128)


def test_fused_cat_w4_catlm_decode_at_32_slots_runs_qkv_too(kernels):
    """Above 8 slots the decode prologue is off: QKV goes through the
    fused linears like the other three."""
    ops, byts = kernels["fused_cat_w4"].cost(dict(CATLM, n_slots=32),
                                             CATLM_DECODE)
    kn = 524_288 + 262_144 + 1_572_864 + 786_432  # qkv, o, gate|up, down
    assert ops == 2 * 5 * kn * 8
    # ΣN = 1,024 + 512 + 3,072 + 512 = 5,120; ΣK = 512 + 512 + 512 + 1,536
    assert byts == 8 * (kn / 2 + 4 * 5_120 + 2 * 5 * 3_072 + 4 * 5 * 5_120)


def test_decode_prologue_catlm(kernels):
    ops, byts = kernels["decode_prologue"].cost(CATLM, CATLM_DECODE)
    # QKV: 512 -> 512 + 2·256 = 1,024 columns, 5 real rows
    assert ops == 8 * 2 * 5 * 512 * 1_024
    # int4 weight 262,144, scales 4,096, bf16 rows in 5,120, f32 q out
    # 10,240, int8 k and v codes 2,560 with 4 heads' f32 scales 160
    assert byts == 8 * (262_144 + 4_096 + 5_120 + 10_240 + 2_560 + 160)


def test_decode_prologue_only_in_small_decode_steps(kernels):
    cost = kernels["decode_prologue"].cost
    assert cost(CATLM, dict(CATLM_DECODE, kind="unified")) == (0.0, 0.0)
    assert cost(dict(CATLM, n_slots=32), CATLM_DECODE) == (0.0, 0.0)
