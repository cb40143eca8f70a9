"""host_offcpu_share_pct.deploy

Of the self time of the host layers' spans (batch worker, replay pool, plan applier, store; device waits left out), the share in which the recording thread was off the CPU: 100 x (1 - sum of trace.cpu.* / trace.cpu_wall).
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.host_offcpu_share_pct(obs)
