"""bw_replay_ms_per_eval.deploy

Batch worker host time replaying prescored picks into plans (batch_worker.replay) per evaluation completed.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.ms_per_eval(obs, "batch_worker.replay")
