"""chain_kernel_roofline_pct.deploy

Least time the chip needs for the chained kernel's logical bytes (peaks.chain_kernel_bytes) over its device time in the trace.  Bound by bytes.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.chain_kernel_roofline_pct(obs)
