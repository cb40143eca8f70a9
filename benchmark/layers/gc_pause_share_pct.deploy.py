"""gc_pause_share_pct.deploy

Share of the window Python's collector held the process (gc.callbacks start/stop).
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.gc_pause_share_pct(obs)
