"""Mistral-Nemo 12B [hf:mistralai/Mistral-Nemo-Base-2407]: llama-arch,
explicit head_dim 128, 128k context, vocab 131072 (tekken).

Published (``config.json``): 40 layers, hidden 5120, intermediate 14336
(SiLU-gated MLP), 32 attention heads of 128 (q_dim 4096, not the hidden
size) over 8 KV heads (groups of 4), vocab 131072, rope_theta 1e6,
rms_norm_eps 1e-5, untied output head. The numbers below are those.

Where the program departs from the published model, as the plain
reference of the benchmark (``bench/reference.py``) also does; none of
these changes the work a step does:

- RMS-norm eps 1e-6 (``models.layers.rms_norm``), published 1e-5;
- the norm gain is parametrised as (1 + w), with w initialised at 0: the
  same function as Mistral's w initialised at 1;
- the embedding is scaled by sqrt(d_model) (``models.dense.forward``);
  Mistral does not scale it;
- the embedding and the output head are stored float32 and cast to
  bfloat16 where a step uses them; the published weights are bfloat16.
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="mistral-nemo-12b", family="dense",
    n_layers=40, d_model=5120, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=14336, vocab=131_072,
    rope_theta=1_000_000.0, tie_embeddings=False,
)
