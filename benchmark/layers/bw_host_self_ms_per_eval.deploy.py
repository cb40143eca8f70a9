"""bw_host_self_ms_per_eval.deploy

Self time of the batch worker's own stages (simulate, assemble, launch, fetch at 1/members each, replay.commit less the plan it waits on, explain.publish: trace.self.bw_host) per evaluation folded in the window.
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.self_ms_per_eval(obs, "bw_host")
