"""chain_kernel_ms_per_eval.deploy

Device time of the jit_chained_plan_picks_cols modules in the profiler trace per evaluation launched in the traced part.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.chain_kernel_ms_per_eval(obs)
