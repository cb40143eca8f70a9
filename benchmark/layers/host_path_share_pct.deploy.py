"""host_path_share_pct.deploy

Evaluations that went down the sequential host path (fallbacks, cold shapes, sequential evaluations) over evaluations processed.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.host_path_share_pct(obs)
