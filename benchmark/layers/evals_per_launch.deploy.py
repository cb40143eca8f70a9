"""evals_per_launch.deploy

Evaluations prescored on the device per kernel launch (the chunk ladder is 2, 4, 8).
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.evals_per_launch(obs)
