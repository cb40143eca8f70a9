"""device_unfed_share_pct.deploy

Share of the window in which the batch worker had no launch in flight (batch_worker.device_unfed_ms); its complement bounds the device's busy share from above over the whole window.
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.device_unfed_share_pct(obs)
