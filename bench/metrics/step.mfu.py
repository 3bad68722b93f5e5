"""Model step (``models/dense.py`` through ``launch/executor.py``): the
model's operations in the traced window at the chip's peak, as a share of
the window. Linears count 2 operations per parameter per real row
against the int8 peak; attention over each real query's context and the
output head at the logit rows count against the bf16 peak. Moves
``itl_p50_ms``: the decode step sets the gap between tokens."""


def read(run):
    if not run.dispatches:
        return None
    d, q, kv, ff = (run.dims[k] for k in ("d_model", "q_dim", "kv_dim",
                                          "d_ff"))
    params = (d * (q + 2 * kv) + q * d + 2 * d * ff + ff * d) \
        * run.dims["n_layers"]
    attn = run.kernels["paged_attn"]
    t = 0.0
    for rec in run.dispatches:
        rows = sum(rec["q_len"])
        t += 2.0 * params * rows / run.peaks["int8_ops_per_s"]
        head = 2.0 * rec["logits"] * d * run.dims["vocab"]
        t += (attn.cost(run.dims, rec)[0] + head) \
            / run.peaks["bf16_flops_per_s"]
    return 100.0 * t / run.window_s
