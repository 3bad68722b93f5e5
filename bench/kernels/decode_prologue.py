"""The decode QKV prologue (``kernels/decode_layer.py``:
``decode_qkv_prologue``): in a pure-decode dispatch on at most 8 slots,
one launch per layer takes the normed rows through the CAT transform,
int8 activation quant and the packed W4A8 QKV linear (d -> q + 2 kv),
applies RoPE, and writes the step's int8 keys and values into the paged
pool.

Rows are the dispatch's real rows, never its padding. Operations: 2 per
int8 multiply-add of the QKV linear (the transform's are left out, so
the count is a floor). Bytes: the packed int4 weight and its f32 scales,
the bf16 input rows, the f32 queries out, and each row's int8 key and
value codes with their f32 per-head scales written to the pool.
"""
NAMES = ("decode_qkv_prologue",)
PEAK = "int8_ops_per_s"
# the batch up to which models/dense.py sends decode QKV to the prologue
DECODE_PROLOGUE_ROWS = 8


def cost(dims: dict, rec: dict) -> tuple:
    if rec["kind"] != "decode" or dims["n_slots"] > DECODE_PROLOGUE_ROWS:
        return 0.0, 0.0
    d, q, kv = dims["d_model"], dims["q_dim"], dims["kv_dim"]
    n = q + 2 * kv
    rows = sum(rec["q_len"])
    ops = 2.0 * rows * d * n
    byts = (d * n / 2 + 4 * n + 2 * rows * d + 4 * rows * q
            + rows * 2 * (kv + 4 * dims["n_kv_heads"]))
    return ops * dims["n_layers"], byts * dims["n_layers"]
