"""Attention over the int8 paged KV pool (``kernels/paged_attention.py``:
``paged_attention_ragged`` for unified steps, ``paged_attention_decode``
for pure-decode steps).

Counted over each work item's real queries and real context only: a
query at position p attends p + 1 keys, and an item of n queries ending
at context length L reads L rows of keys and values. Operations: 2 for
each multiply-add of the scores and of the weighted sum, over every
query head. Bytes: int8 key and value codes with their f32 per-(token,
head) scales for the context, and the bf16 queries in and outputs out.
"""
NAMES = ("paged_attention_ragged", "paged_attention_decode")
PEAK = "bf16_flops_per_s"


def cost(dims: dict, rec: dict) -> tuple:
    hq, kvh, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    ops = byts = 0.0
    for n, length in zip(rec["q_len"], rec["kv_len"]):
        pairs = n * length - n * (n - 1) / 2
        ops += 4.0 * hd * hq * pairs
        byts += 2 * length * kvh * (hd + 4) + 2 * 2 * n * hq * hd
    return ops * dims["n_layers"], byts * dims["n_layers"]
