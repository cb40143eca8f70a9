"""plan_apply_self_ms_per_eval.deploy

Self time of plan.evaluate and plan.apply on the applier's threads, the store's commit taken out (trace.self.plan_applier), per evaluation folded in the window.
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.self_ms_per_eval(obs, "plan_applier")
