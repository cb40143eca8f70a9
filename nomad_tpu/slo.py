"""SLO engine: declared objectives + multi-window burn rates.

Declares what "meeting its objectives" means for this control plane —
interactive placement latency, zero lost evals, bounded shed rate,
bounded storm-fallback rate, failover detect-to-resume — and grades
each over the retained metric history ring
(``NOMAD_TPU_OBS_HISTORY``), SRE-alerting style: a **fast** window
(the last ``NOMAD_TPU_SLO_FAST_N`` snapshots — "is it happening
now?") and a **slow** window (``NOMAD_TPU_SLO_SLOW_N`` — "is it
material?").  Each objective's burn rate is its observed
badness divided by its error budget; status is

* ``BURNING`` when BOTH windows burn at >= ``BURN_AT``
  (fast alone is noise, slow alone is history),
* ``WARN`` when EITHER window reaches ``WARN_AT``,
* ``OK`` otherwise (including "not enough history yet": the engine
  never pages on an empty ring).

The engine is read-path only — ``status()`` folds over snapshot
windows already paid for by the history thread, so there is no
steady-state cost and nothing to instrument on the hot path.  The
decision ledger (``nomad_tpu/decisions.py``) is the matching write
path; together they are the flight data ROADMAP item 6's self-tuning
controller consumes: objectives to optimize, decisions to tune.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

__all__ = [
    "SLO_COUNTERS",
    "SLO_GAUGES",
    "SLOEngine",
    "slo_enabled",
]

# zero-registered at Server construction (slo-metrics lint): absence
# of a series must mean "never evaluated", not "not exported"
SLO_COUNTERS = ("slo.evaluations",)
SLO_GAUGES = ("slo.worst", "slo.burning", "slo.warn")

# a zero-tolerance objective with any violation burns at this rate —
# far past any sane threshold, finite so JSON stays plain
_ZERO_TOLERANCE_BURN = 1000.0

_STATUS_RANK = {"OK": 0, "WARN": 1, "BURNING": 2}

# burn-rate thresholds: WARN when either window reaches WARN_AT,
# BURNING when both reach BURN_AT
WARN_AT = 1.0
BURN_AT = 2.0
# objective targets: the interactive placement budget (windowed
# eval-latency p99) and device failover-to-restored p99
P99_TARGET_MS = 250.0
FAILOVER_TARGET_MS = 60000.0


def slo_enabled() -> bool:
    return os.environ.get("NOMAD_TPU_SLO", "1") != "0"


def _knob_int(name: str, default: int, lo: int) -> int:
    try:
        return max(lo, int(os.environ.get(name, str(default))))
    except ValueError:
        return default


class SLOEngine:
    """Grades declared objectives over the metric history ring."""

    def __init__(self, metrics, history) -> None:
        self.metrics = metrics
        self.history = history
        self.enabled = slo_enabled()
        self.fast_n = _knob_int("NOMAD_TPU_SLO_FAST_N", 6, 2)
        self.slow_n = _knob_int("NOMAD_TPU_SLO_SLOW_N", 30, 2)
        # The declared objectives.  "budget" is the error budget the
        # burn rate is normalized against: for latency objectives the
        # tolerated fraction of windows over target, for ratio
        # objectives the tolerated bad-event fraction; zero-tolerance
        # objectives have no budget (any violation burns at the cap).
        self.objectives: List[Dict[str, Any]] = [
            {
                "name": "interactive_placement_p99",
                "kind": "latency_p99",
                "sample": "batch_worker.eval_latency_ms",
                "target_ms": P99_TARGET_MS,
                "budget": 0.05,
                "doc": "windowed eval-latency p99 stays within the "
                       "interactive placement budget",
            },
            {
                "name": "zero_lost_evals",
                "kind": "zero",
                "counter": "broker.delivery_failures",
                "doc": "no eval exhausts delivery and parks in the "
                       "failed queue",
            },
            {
                "name": "shed_rate",
                "kind": "ratio",
                "num": "overload.shed",
                "den": ("overload.shed", "overload.accepted"),
                "budget": 0.05,
                "doc": "overload ladder sheds a bounded fraction of "
                       "ingress writes",
            },
            {
                "name": "storm_fallback_rate",
                "kind": "ratio",
                "num": "storm.fallbacks",
                "den": ("storm.evals",),
                "budget": 0.10,
                "doc": "storm members solved in-wave, not demoted to "
                       "the serial fallback",
            },
            {
                "name": "failover_detect_to_resume",
                "kind": "latency_p99",
                "sample": "device.failover_resume_ms",
                "target_ms": FAILOVER_TARGET_MS,
                "budget": 0.05,
                "doc": "device failover detect-to-resume stays "
                       "within budget",
            },
        ]

    # -- burn-rate math (pure folds over snapshot windows) ------------

    @staticmethod
    def _counter_delta(windows, name: str) -> int:
        if len(windows) < 2:
            return 0
        first = windows[0].get("counters", {}).get(name, 0)
        last = windows[-1].get("counters", {}).get(name, 0)
        return max(0, last - first)

    def _burn(self, obj: Dict[str, Any], windows) -> float:
        """One objective's burn rate over one window range."""
        if len(windows) < 2:
            return 0.0
        kind = obj["kind"]
        if kind == "latency_p99":
            bad = 0
            for w in windows:
                s = w.get("samples", {}).get(obj["sample"])
                if s and s.get("p99", 0.0) > obj["target_ms"]:
                    bad += 1
            return (bad / len(windows)) / obj["budget"]
        if kind == "zero":
            delta = self._counter_delta(windows, obj["counter"])
            return _ZERO_TOLERANCE_BURN if delta > 0 else 0.0
        if kind == "ratio":
            num = self._counter_delta(windows, obj["num"])
            den = sum(
                self._counter_delta(windows, n) for n in obj["den"]
            )
            if den <= 0:
                return 0.0
            return (num / den) / obj["budget"]
        raise ValueError(f"unknown objective kind {kind!r}")

    def _grade(self, burn_fast: float, burn_slow: float) -> str:
        if burn_fast >= BURN_AT and burn_slow >= BURN_AT:
            return "BURNING"
        if burn_fast >= WARN_AT or burn_slow >= WARN_AT:
            return "WARN"
        return "OK"

    # -- the /v1/slo payload ------------------------------------------

    def status(self) -> Dict[str, Any]:
        hist = self.history.to_dict() if self.history else {}
        windows = hist.get("windows", [])
        fast = windows[-self.fast_n:]
        slow = windows[-self.slow_n:]
        out: List[Dict[str, Any]] = []
        worst = "OK"
        for obj in self.objectives:
            if not self.enabled:
                burn_fast = burn_slow = 0.0
                state = "OK"
            else:
                burn_fast = self._burn(obj, fast)
                burn_slow = self._burn(obj, slow)
                state = self._grade(burn_fast, burn_slow)
            if _STATUS_RANK[state] > _STATUS_RANK[worst]:
                worst = state
            entry = {
                "name": obj["name"],
                "kind": obj["kind"],
                "doc": obj["doc"],
                "burn_fast": round(burn_fast, 4),
                "burn_slow": round(burn_slow, 4),
                "status": state,
            }
            if "target_ms" in obj:
                entry["target_ms"] = obj["target_ms"]
            if "budget" in obj:
                entry["budget"] = obj["budget"]
            out.append(entry)
        payload = {
            "enabled": self.enabled,
            "windows": {
                "retained": len(windows),
                "fast_n": self.fast_n,
                "slow_n": self.slow_n,
                "interval_s": hist.get("interval_s", 0),
            },
            "thresholds": {
                "warn": WARN_AT,
                "burning": BURN_AT,
            },
            "objectives": out,
            "worst": worst,
        }
        if self.metrics is not None:
            self.metrics.incr("slo.evaluations")
            self.metrics.set_gauge(
                "slo.worst", _STATUS_RANK[worst]
            )
            self.metrics.set_gauge(
                "slo.burning",
                sum(1 for o in out if o["status"] == "BURNING"),
            )
            self.metrics.set_gauge(
                "slo.warn",
                sum(1 for o in out if o["status"] == "WARN"),
            )
        return payload
