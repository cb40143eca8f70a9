"""store_commit_ms_per_eval.deploy

State store time: store.commit (lock taken to index published) and store.upsert_evals (trace.self.store) per evaluation folded in the window.
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.self_ms_per_eval(obs, "store")
