"""What the flight recorder's self-time readers share.

The program folds acked evaluations' traces where they close
(``nomad_tpu/trace.py`` ``Trace.fold``; one ack in ``FOLD_SAMPLE``, so
that the recorder stays cheap) and adds the result to its telemetry,
so these readers see the WHOLE window through the same ``/v1/metrics``
deltas as the counter readers (``obs["samples"]`` as ``{"count",
"sum_ms"}``, ``obs["counters"]``):

    trace.life              create -> ack, one sample a folded trace
    trace.self.<layer>      self time by layer; the eight sum to the life
    trace.cpu.<layer>       thread CPU time of the spans that record it
    trace.cpu_wall          self time of exactly those spans
    trace.folded/.unfolded  the meter's own health (counters)
    batch_worker.device_unfed_ms   no launch in flight (counter)

A program without the fold (the parent of the PR that brought it, or
``NOMAD_TPU_TRACE=0``) leaves every sample empty and every reader
returns None.  A ``*_ms_per_eval`` here is the mean over the
evaluations FOLDED in the window (the sample's own count): the
program folds a fixed share of its acks, so the count of completions
the generator saw (the outside-timed readers' denominator) would
divide a part by the whole.
"""
from __future__ import annotations

from benchmark.layers import _lib

# the layers whose spans record thread CPU time (trace.cpu.<layer>)
CPU_LAYERS = ("bw_host", "replay_pool", "plan_applier", "store")


def self_ms_per_eval(obs, layer: str):
    """A layer's mean self time over the evaluations folded in the
    window."""
    s = _lib.sample(obs, "trace.self." + layer)
    if not s["count"]:
        return None
    return s["sum_ms"] / s["count"]


def host_offcpu_share_pct(obs):
    """Of the time the host layers' spans were open (their self time,
    device waits left out), the share their thread was NOT on a CPU:
    waiting for the GIL, a lock or another thread."""
    wall = _lib.sample(obs, "trace.cpu_wall")
    if not wall["count"] or wall["sum_ms"] <= 0:
        return None
    cpu = sum(
        _lib.sample(obs, "trace.cpu." + layer)["sum_ms"]
        for layer in CPU_LAYERS
    )
    return 100.0 * (1.0 - cpu / wall["sum_ms"])


def trace_unfolded_pct(obs):
    folded = _lib.counter(obs, "trace.folded")
    unfolded = _lib.counter(obs, "trace.unfolded")
    if not folded + unfolded:
        return None
    return 100.0 * unfolded / (folded + unfolded)


def device_unfed_share_pct(obs):
    if "batch_worker.device_unfed_ms" not in obs["counters"]:
        return None
    if not obs["window_s"]:
        return None
    return (
        100.0
        * _lib.counter(obs, "batch_worker.device_unfed_ms")
        / (1000.0 * obs["window_s"])
    )
