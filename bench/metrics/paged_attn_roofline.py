"""Kernel roofline of ``kernels/paged_attn.py``'s operations: the least time
the chip needs for the real rows' operations and bytes of the traced
window (the larger of operations over peak and bytes over HBM bandwidth)
over the kernel's device time in the trace. Moves ``itl_p50_ms``: the
decode step sets the gap between tokens."""


def read(run):
    return run.roofline("paged_attn")
