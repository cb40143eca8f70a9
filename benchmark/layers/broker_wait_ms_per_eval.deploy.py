"""broker_wait_ms_per_eval.deploy

Time an evaluation spent in the eval broker, enqueue to dequeue, plus its ack (trace.self.broker: broker.wait, broker.ack) per evaluation folded in the window.
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.self_ms_per_eval(obs, "broker")
