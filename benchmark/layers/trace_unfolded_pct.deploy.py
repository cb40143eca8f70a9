"""trace_unfolded_pct.deploy

Acked evaluations whose trace could not be folded (evicted from the ring, spans dropped, a span left open) over all acked: trace.unfolded / (trace.folded + trace.unfolded).
"""
from benchmark.layers import _spans


def read(obs):
    return _spans.trace_unfolded_pct(obs)
