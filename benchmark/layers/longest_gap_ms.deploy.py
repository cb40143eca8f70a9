"""longest_gap_ms.deploy

The longest time in the window in which no registration completed: a
full collection, a slow launch or a stalled host shows here first.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.longest_gap_ms(obs)
