"""What the per-layer readers share.  A reader takes the run's
observations (``obs``, built by ``run.run``) and returns a number, or
None when its source holds nothing to read; the harness then leaves the
metric out of the line.

``obs`` covers the WHOLE window, except ``trace``, which covers the
traced part (the window's last seconds).  Keys: ``window_s``; ``evals``
(completions the generator saw); ``attempted`` (registrations sent, or
due, in the window) and ``refused`` of them; ``counters`` and
``samples`` (``/v1/metrics`` deltas between the window's edges: samples
as ``{"count", "sum_ms"}``); ``gen`` (``late_ms`` list); ``latency_ms``
(open loop: due-time latency of every request due in the window, ``inf``
for a failed one); ``gc`` (``pause_s``); ``longest_gap_s`` (between
completions); ``compiles`` (backend compiles); ``trace``
(``tracered.reduce_trace`` of the traced part, with ``launch_evals``,
the evaluations launched in that part) or None; ``device_kind``;
``arena_rows``; ``column_bytes`` (of one entry of a usage column);
``picks_per_eval``.
"""
from __future__ import annotations

from benchmark import peaks, tracered

KERNEL_PREFIX = "jit_chained_plan_picks_cols"


def counter(obs, name: str) -> float:
    return float(obs["counters"].get(name, 0.0))


def sample(obs, name: str):
    return obs["samples"].get(name) or {"count": 0, "sum_ms": 0.0}


def ms_per_eval(obs, *sample_names: str):
    """Stage milliseconds in the window over evaluations completed."""
    if not obs["evals"]:
        return None
    if not any(sample(obs, n)["count"] for n in sample_names):
        return None
    return sum(sample(obs, n)["sum_ms"] for n in sample_names) / obs["evals"]


def shed_share_pct(obs):
    shed = counter(obs, "overload.shed") + obs.get("refused", 0)
    attempted = obs["attempted"]
    if not attempted:
        return None
    return 100.0 * shed / attempted


def evals_per_launch(obs):
    launches = sample(obs, "batch_worker.launch")["count"] + sample(
        obs, "batch_worker.mesh_launch"
    )["count"]
    if not launches:
        return None
    return counter(obs, "batch_worker.prescored") / launches


def host_path_share_pct(obs):
    evals = counter(obs, "batch_worker.prescored") + sample(
        obs, "batch_worker.sequential"
    )["count"]
    if not evals:
        return None
    host = (
        counter(obs, "batch_worker.fallbacks")
        + counter(obs, "batch_worker.cold_shape_fallbacks")
        + sample(obs, "batch_worker.sequential")["count"]
    )
    return 100.0 * host / evals


def gc_pause_share_pct(obs):
    return 100.0 * obs["gc"]["pause_s"] / obs["window_s"]


def longest_gap_ms(obs):
    gap = obs.get("longest_gap_s")
    return None if gap is None else 1000.0 * gap


def compiles_in_window(obs):
    return float(obs["compiles"])


def _kernel(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    count, seconds = tracered.module_seconds(trace, KERNEL_PREFIX)
    if not count or seconds <= 0 or not trace.get("launch_evals"):
        return None
    return count, seconds, trace["launch_evals"]


def chain_kernel_ms_per_eval(obs):
    k = _kernel(obs)
    if k is None:
        return None
    return 1000.0 * k[1] / k[2]


def chain_kernel_roofline_pct(obs):
    k = _kernel(obs)
    if k is None:
        return None
    moved = peaks.chain_kernel_bytes(
        int(k[2]), int(round(obs["picks_per_eval"])), int(obs["arena_rows"]),
        int(obs["column_bytes"]),
    )
    return peaks.roofline_pct(moved, k[1], obs["device_kind"])


def device_idle_share_pct(obs):
    trace = obs.get("trace")
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
