"""shed_share_pct.deploy

Registrations the ingress shed or refused (overload.shed from /v1/metrics, plus non-200 answers) over registrations attempted in the window.
"""
from benchmark.layers import _lib


def read(obs):
    return _lib.shed_share_pct(obs)
