"""plan_apply_ms_per_eval.deploy

Plan applier time (plan.evaluate plus plan.apply) per evaluation completed.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.ms_per_eval(obs, "plan.evaluate", "plan.apply")
