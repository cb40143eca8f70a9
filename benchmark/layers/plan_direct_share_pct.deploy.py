"""plan_direct_share_pct.deploy

Plans that found the plan applier idle and were verified and committed on their submitter's thread, over all plans submitted in the window: plan.direct / (plan.direct + plan.queued) from /v1/metrics.
"""
from benchmark.layers import _lib


def read(obs):
    direct = _lib.counter(obs, "plan.direct")
    plans = direct + _lib.counter(obs, "plan.queued")
    # a program without the two counters, or a window with no plan
    if not plans:
        return None
    return 100.0 * direct / plans
