"""pipeline_wait_ms_per_eval.deploy

Time an evaluation was dequeued, alive and inside no stage: the trace root's own time, replay.commit_wait, and the chunk-mates' share of chunk-wide stages (trace.self.pipeline_wait) per evaluation folded in the window.
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.self_ms_per_eval(obs, "pipeline_wait")
