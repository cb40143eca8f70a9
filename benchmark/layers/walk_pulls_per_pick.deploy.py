"""walk_pulls_per_pick.deploy

Ring positions the kernel's limit walk drew per prescored pick: batch_worker.walk_pulls / batch_worker.walk_picks from /v1/metrics over the window.
"""
from benchmark.layers import _lib


def read(obs):
    picks = _lib.counter(obs, "batch_worker.walk_picks")
    # a program without the two counters, or a window with no pick
    if not picks:
        return None
    return _lib.counter(obs, "batch_worker.walk_pulls") / picks
