"""device_idle_share_pct.deploy

One minus the union of device operation intervals over the traced window.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.device_idle_share_pct(obs)
