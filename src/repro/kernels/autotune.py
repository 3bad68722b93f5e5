"""Per-shape block-size selection for the Pallas serving kernels.

The serving hot loop calls the same handful of (M, K, N) shapes thousands
of times, so block sizes are worth picking once per shape and memoizing.
Two modes:

* default — an analytic VMEM-budget heuristic (`heuristic_blocks`):
  largest power-of-two M tile whose working set (x tile + int8 code
  scratch + weight block + f32 output block + scales) fits the budget,
  with N/K blocks clamped to the operand.
* ``REPRO_AUTOTUNE=measure`` — time each heuristic candidate once via a
  caller-supplied runner and keep the fastest (`pick`). Useful on real
  TPUs where the heuristic's VMEM model is approximate; never on by
  default because it compiles every candidate.

The cache is process-local and keyed on the caller's shape tuple; entries
are never evicted (a serving process sees a few dozen shapes at most).

``vmem_params`` turns a kernel's VMEM estimate for its chosen blocks
(``tile_bytes`` counts the chip's tile padding) into the scoped-VMEM limit
the kernel asks Mosaic for, where the default is too small: at
Mistral-NeMo-12B's widths the fused CAT linears and the ragged attention
kernel need more than 16 MiB.
"""
from __future__ import annotations

import os
import time
from typing import Callable, Sequence

# Conservative slice of the ~16 MiB/core VMEM: leaves headroom for
# Mosaic's own double-buffering of the streamed weight blocks.
VMEM_BUDGET = 8 * 2**20
# Mosaic's scoped VMEM limit for a kernel that sets none, and the most a
# kernel asks for (a v5e TensorCore has 128 MiB of VMEM).
DEFAULT_SCOPED_VMEM = 16 * 2**20
MAX_SCOPED_VMEM = 96 * 2**20

_CACHE: dict = {}


def cache_info() -> dict:
    """Snapshot of the memoized choices (for tests / debugging)."""
    return dict(_CACHE)


def cache_clear() -> None:
    _CACHE.clear()


def _fused_working_set(tm: int, tn: int, tk: int, d: int, packed: bool) -> int:
    k_pad = -(-d // tk) * tk
    x_tile = tm * d * 4                       # f32 activation tile
    scratch = tm * k_pad + tm * 2 * 4         # int8 codes + scale/zp
    w_blk = (tk // 2 if packed else tk) * tn  # int8/packed weight block
    out = tm * tn * 4
    return x_tile + scratch + w_blk + out


def tile_bytes(rows: int, lanes: int, itemsize: int) -> int:
    """VMEM bytes of one (rows, lanes) minor slab after tile padding."""
    sub = 8 * 4 // itemsize
    return (-(-rows // sub) * sub) * (-(-lanes // 128) * 128) * itemsize


def vmem_params(need: int):
    """Mosaic compiler parameters for a kernel whose blocks, scratch and
    in-kernel values take about ``need`` bytes of VMEM: None where the
    default scoped limit holds that, so the kernel compiles as it always
    has, else a limit of twice ``need`` (the estimate leaves out Mosaic's
    own temporaries), at most ``MAX_SCOPED_VMEM``."""
    if need <= DEFAULT_SCOPED_VMEM:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(2 * need, MAX_SCOPED_VMEM))


def heuristic_blocks(m: int, d: int, n: int, packed: bool,
                     budget: int = VMEM_BUDGET) -> tuple[int, int, int]:
    """-> (block_m, block_n, block_k) for the fused CAT matmul shape."""
    tk = min(512, d + d % 2)
    tk += tk % 2
    tn = min(256, n)
    for tm in (256, 128, 64, 32, 16, 8):
        if _fused_working_set(tm, tn, tk, d, packed) <= budget:
            return tm, tn, tk
    return 8, tn, tk


def _candidates(m: int, d: int, n: int, packed: bool):
    tm0, tn0, tk0 = heuristic_blocks(m, d, n, packed)
    seen, out = set(), []
    for tm in (tm0, max(8, tm0 // 2), min(256, tm0 * 2)):
        for tn in (tn0, max(128, tn0 // 2)):
            c = (tm, min(tn, max(8, n)), tk0)
            if c not in seen:
                seen.add(c)
                out.append(c)
    return out


def pick(key: tuple, m: int, d: int, n: int, packed: bool,
         run: Callable[[tuple[int, int, int]], None] | None = None,
         ) -> tuple[int, int, int]:
    """Memoized block-size choice for ``key`` (caller's shape tuple).

    With ``REPRO_AUTOTUNE=measure`` and a ``run`` callback, times each
    candidate (one warmup + one timed call) and caches the fastest;
    otherwise caches the heuristic. A candidate the backend refuses is
    skipped; when every candidate is refused the kernel cannot run at
    this shape, and the last refusal is raised instead of falling back
    to a block size that was never shown to compile.
    """
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    choice = heuristic_blocks(m, d, n, packed)
    if run is not None and os.environ.get("REPRO_AUTOTUNE") == "measure":
        best_t, err = None, None
        for cand in _candidates(m, d, n, packed):
            try:
                run(cand)           # compile + warm
                t0 = time.perf_counter()
                run(cand)
                dt = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001 - candidate refused here
                err = e
                continue
            if best_t is None or dt < best_t:
                best_t, choice = dt, cand
        if best_t is None:
            raise RuntimeError(
                f"every block-size candidate failed for {key}") from err
    _CACHE[key] = choice
    return choice


def gemv_blocks(d: int, n: int, packed: bool,
                budget: int = VMEM_BUDGET) -> tuple[int, int]:
    """-> (block_n, block_k) for the fused GEMV (M fixed at 8)."""
    _, tn, tk = heuristic_blocks(8, d, n, packed, budget)
    return tn, tk


def prologue_blocks(d: int, n: int, n_kv: int, packed: bool,
                    budget: int = VMEM_BUDGET) -> tuple[int, int]:
    """-> (block_n, block_k) for the fused decode QKV prologue.

    Same shape family as the GEMV (M fixed at 8), but the kernel keeps
    extra VMEM resident for the whole launch: the full-N f32 QKV
    accumulator (the RoPE/KV epilogue reads all columns at once) and the
    K/V code+scale epilogue scratches — carve those out of the budget
    before sizing the streamed weight block.
    """
    acc = 8 * n * 4                       # (8, N_pad) f32 accumulator
    kv = 2 * (8 * n_kv + 8 * n_kv * 4)    # int8 codes + f32 scale bound
    return gemv_blocks(d, n, packed, budget=max(budget - acc - kv,
                                                budget // 8))


__all__: Sequence[str] = ("pick", "heuristic_blocks", "gemv_blocks",
                          "prologue_blocks", "cache_info", "cache_clear",
                          "tile_bytes", "vmem_params", "VMEM_BUDGET")
