"""Post-SPMD HLO analysis: collective byte accounting + cost extraction.

collective_bytes is not in cost_analysis() — we parse the OPTIMIZED HLO
(compiled.as_text(), after GSPMD partitioning) and sum operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op. Shapes in HLO are per-DEVICE, so the totals are
per-device wire bytes (what the roofline's collective term wants).
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %ag = bf16[4,128,256]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^a-z]*\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
# tuple-result collectives:  (bf16[..], bf16[..]) all-reduce(...)
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """-> {op_kind: per-device bytes} + {"total": ...}. '-start' ops are
    counted; their '-done' twins are skipped (same transfer)."""
    out: dict = defaultdict(int)
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        stripped = line.strip()
        m = _OP_RE.search(stripped)
        if m:
            dtype, dims, kind = m.groups()
            out[kind] += _shape_bytes(dtype, dims)
            continue
        m = _TUPLE_RE.search(stripped)
        if m:
            shapes, kind = m.groups()
            for dt, dm in _SHAPE_RE.findall(shapes):
                out[kind] += _shape_bytes(dt, dm)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return dict(out)


def cost_summary(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[f] = int(getattr(ma, f, 0) or 0)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "memory": mem,
    }
