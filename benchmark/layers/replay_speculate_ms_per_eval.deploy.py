"""replay_speculate_ms_per_eval.deploy

Self time of replay.speculate, scheduler.process on the replay pool's threads (trace.self.replay_pool), per evaluation folded in the window.
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.self_ms_per_eval(obs, "replay_pool")
