"""Kernel roofline of ``kernels/decode_prologue.py``'s operations: the least
time the chip needs for the real rows' operations and bytes of the traced
window (the larger of operations over peak and bytes over HBM bandwidth)
over the kernel's device time in the trace. Read only where pure-decode
steps run on at most 8 slots. Moves ``itl_p50_ms``: the decode step sets
the gap between tokens."""


def read(run):
    return run.roofline("decode_prologue")
