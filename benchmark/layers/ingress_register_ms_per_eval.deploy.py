"""ingress_register_ms_per_eval.deploy

HTTP handler time from the parsed request to the eval's hand-off to the broker (trace.self.ingress: ingress.register) per evaluation folded in the window.
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.self_ms_per_eval(obs, "ingress")
