"""The fused CAT -> int8 activation quant -> W4A8 linears
(``kernels/fused_cat_matmul.py``: ``fused_cat_matmul_w4`` for more than 8
rows, ``fused_cat_gemv_w4`` for up to 8).

Per layer a dispatch runs four of them: QKV (d -> q + 2 kv), output
(q -> d), gate|up (d -> 2 ff) and down (ff -> d). A pure-decode dispatch
on at most 8 slots (``DECODE_PROLOGUE_ROWS``) runs QKV in the decode
prologue kernel instead. Rows are the dispatch's real rows, never its
padding. Operations: 2 per int8 multiply-add. Bytes: the packed int4
weight and its f32 scales, the bf16 input rows and the f32 output rows.
"""
NAMES = ("fused_cat_matmul_w4", "fused_cat_gemv_w4")
PEAK = "int8_ops_per_s"
# the batch up to which models/dense.py sends decode QKV to the prologue
DECODE_PROLOGUE_ROWS = 8


def linears(dims: dict, rec: dict) -> list:
    d, q, kv, ff = dims["d_model"], dims["q_dim"], dims["kv_dim"], dims["d_ff"]
    shapes = [(d, q + 2 * kv), (q, d), (d, 2 * ff), (ff, d)]
    if rec["kind"] == "decode" and dims["n_slots"] <= DECODE_PROLOGUE_ROWS:
        shapes = shapes[1:]
    return shapes


def cost(dims: dict, rec: dict) -> tuple:
    rows = sum(rec["q_len"])
    ops = byts = 0.0
    for k, n in linears(dims, rec):
        ops += 2.0 * rows * k * n
        byts += k * n / 2 + 4 * n + 2 * rows * k + 4 * rows * n
    return ops * dims["n_layers"], byts * dims["n_layers"]
