"""plan_handoff_ms_per_eval.deploy

The three thread hand-offs of a plan: plan.queue_wait, plan.stage_wait, plan.respond_wait (trace.self.plan_handoff) per evaluation folded in the window.
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.self_ms_per_eval(obs, "plan_handoff")
