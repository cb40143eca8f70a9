"""pair_decided_per_mpick.deploy

Picks per million prescored picks that the lo half of a float32 trace's pair score chose (one float32 a score ties them, and the earlier node would win): batch_worker.pair_decided_picks / batch_worker.walk_picks x 1e6 from /v1/metrics over the window.  0 on a float64 trace.
"""
from benchmark.layers import _lib

COUNTER = "batch_worker.pair_decided_picks"


def read(obs):
    picks = _lib.counter(obs, "batch_worker.walk_picks")
    # a program without the counter, or a window with no pick
    if COUNTER not in obs["counters"] or not picks:
        return None
    return 1e6 * _lib.counter(obs, COUNTER) / picks
