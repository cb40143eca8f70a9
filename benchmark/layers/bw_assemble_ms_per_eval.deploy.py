"""bw_assemble_ms_per_eval.deploy

Batch worker host time staging launch inputs and syncing the device mirror (batch_worker.assemble) per evaluation completed.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.ms_per_eval(obs, "batch_worker.assemble")
