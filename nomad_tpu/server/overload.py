"""Ingress backpressure: overload as a first-class, observable server
state (reference model: nomad's pending-eval limits + the classic
Breakwater/SEDA admission-control shape).

The control plane's failure mode under a traffic storm is not a crash
— it is an unbounded broker backlog whose queueing delay blows every
SLO while the server still answers 200s.  The
:class:`OverloadController` makes that state explicit: a three-rung
degradation ladder driven by the broker's backlog signals and the
flight recorder's latency tail, with **priority-classed shedding** at
the HTTP ingress.

Mode ladder (the ``overload.mode`` gauge)::

    NORMAL (0)     everything admitted
    SHEDDING (1)   job submissions (class >= shed floor) shed with
                   429 + Retry-After; blocking queries degrade to
                   non-blocking (counted as overload.deferred)
    EMERGENCY (2)  every class except node heartbeats shed

Priority classes (lower = more protected)::

    PRI_HEARTBEAT (0)  node heartbeats / registrations / alloc-status
                       pushes — the cluster's liveness plane.  NEVER
                       shed below EMERGENCY (an overloaded leader that
                       drops heartbeats manufactures a false mass
                       node-death wave, turning overload into a
                       replanning storm); this build never sheds them
                       at EMERGENCY either — the class exists so a
                       future rung above EMERGENCY has somewhere to go.
    PRI_QUERY (1)      reads, blocking queries, plan dry-runs
    PRI_SUBMIT (2)     job submissions / scaling / operator writes

Ladder inputs, each with a NORMAL->SHEDDING threshold and a 4x
EMERGENCY threshold:

* **broker depth** (``EvalBroker.pending_depth()``): ready backlog +
  per-job pending heaps — the work already accepted but not started;
* **oldest pending age** (``EvalBroker.oldest_pending_age()``): the
  commit-wave lag the next accepted eval will experience before its
  wave even starts — queueing delay measured, not modeled.

Escalation is immediate; de-escalation drops one rung at a time after
the signals have stayed below the lower rung's thresholds for a
cooldown, so the mode gauge can't flap at threshold noise.  Every
excursion from NORMAL is recorded as ONE flight-recorder incident
trace (``overload:<n>``, rooted at the ``ingress.shed`` span) whose
annotations carry the trigger signals and final shed counts.

The controller is passive (no thread): the mode re-evaluates lazily —
at most every ``_EVAL_INTERVAL_S`` — from the admission path, which
under overload is exactly the path that runs hottest.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Dict, Optional, Tuple

# modes (the overload.mode gauge values)
MODE_NORMAL = 0
MODE_SHEDDING = 1
MODE_EMERGENCY = 2
MODE_NAMES = ("NORMAL", "SHEDDING", "EMERGENCY")

# ingress priority classes (lower = more protected)
PRI_HEARTBEAT = 0
PRI_QUERY = 1
PRI_SUBMIT = 2

# overload.* telemetry, zero-registered at Server construction (the
# `overload-metrics` nomadlint rule enforces registry membership for
# every emission across overload.py / server.py / api/http.py):
# absence of an overload.* series must mean "never overloaded", not
# "not exported"
OVERLOAD_COUNTERS = (
    "overload.accepted",
    "overload.shed",
    "overload.deferred",
    "overload.node_down_waves",
)
OVERLOAD_GAUGES = (
    "overload.mode",
    "overload.broker_depth",
    "overload.oldest_age_s",
    "overload.last_wave_nodes",
)

# mode recompute cadence: signals are cheap (two O(1)-ish broker
# reads), but not per-request cheap at thousands of req/s
_EVAL_INTERVAL_S = 0.05
# de-escalation hold: signals must stay below the lower rung this
# long before the mode drops one rung (escalation is immediate)
_COOLDOWN_S = 1.0
# EMERGENCY engages at this multiple of the SHEDDING thresholds
_EMERGENCY_FACTOR = 4.0
# Retry-After advice per mode (seconds); SHEDDING backs clients off
# briefly, EMERGENCY tells them the backlog needs real draining
_RETRY_AFTER_S = {MODE_SHEDDING: 1.0, MODE_EMERGENCY: 5.0}

# observability/liveness endpoints that must answer DURING overload —
# shedding the endpoints an operator needs to see the overload would
# make every incident a blind one
_EXEMPT_PREFIXES = (
    "/v1/metrics",
    "/v1/overload",
    "/v1/device",
    "/v1/agent",
    "/v1/status",
    "/v1/operator",
    "/v1/traces",
    # control-loop flight data: the SLO burn-rate view and the
    # decision ledger are exactly what an operator reads to judge an
    # overload excursion — shedding them defeats their purpose
    "/v1/slo",
    "/v1/decisions",
    # cluster fan-in queries: an overloaded leader shedding the
    # cluster-wide views would blind the operator to the overload
    "/v1/cluster",
)

# the liveness plane: heartbeats, node/client registration and
# client alloc-status pushes (dropping those turns overload into
# false alloc-loss churn)
_HEARTBEAT_SUFFIXES = ("/heartbeat", "/allocs")
_HEARTBEAT_PATHS = ("/v1/node/register", "/v1/client/register")

# read-shaped write endpoints that belong with the query class
_QUERY_PATHS = ("/v1/search", "/v1/validate/job")


def classify_request(method: str, path: str) -> Optional[int]:
    """Priority class of one HTTP request, or None for exempt
    (observability/liveness) endpoints that are never shed."""
    if path.startswith(_EXEMPT_PREFIXES):
        return None
    if path in _HEARTBEAT_PATHS or (
        path.startswith("/v1/node/")
        and path.endswith(_HEARTBEAT_SUFFIXES)
    ):
        return PRI_HEARTBEAT
    if method == "GET" or path in _QUERY_PATHS or path.endswith(
        "/plan"
    ):
        return PRI_QUERY
    return PRI_SUBMIT


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


class OverloadController:
    """Admission backpressure + the NORMAL->SHEDDING->EMERGENCY mode
    ladder for one server.  Passive: no thread; the mode re-evaluates
    lazily from the admission path (throttled to
    ``_EVAL_INTERVAL_S``)."""

    def __init__(self, server) -> None:
        self.server = server
        self.enabled = (
            os.environ.get("NOMAD_TPU_OVERLOAD", "1") != "0"
        )
        # SHEDDING thresholds (EMERGENCY = 4x each)
        self.depth_threshold = max(
            1.0, _env_float("NOMAD_TPU_OVERLOAD_DEPTH", 512.0)
        )
        self.age_threshold_s = max(
            0.1, _env_float("NOMAD_TPU_OVERLOAD_AGE_S", 30.0)
        )
        self._lock = threading.Lock()
        self._mode = MODE_NORMAL
        self._last_eval = 0.0
        # monotonic instant the signals last SUPPORTED the current
        # mode (de-escalation cooldown anchor)
        self._last_supported = time.monotonic()
        self._incident_seq = itertools.count(1)
        self._incident_id: Optional[str] = None
        self._incident_shed_at_start = 0.0
        # last computed signals, for /v1/overload
        self._signals: Dict[str, float] = {"depth": 0.0, "age_s": 0.0}

    # -- signals -------------------------------------------------------

    def _read_signals(self) -> Tuple[float, float]:
        broker = getattr(self.server, "broker", None)
        depth = float(broker.pending_depth()) if broker else 0.0
        age = float(broker.oldest_pending_age()) if broker else 0.0
        return depth, age

    def _severity(self, depth: float, age: float) -> int:
        """Worst rung any single signal supports."""

        def rung(value: float, threshold: float) -> int:
            if value < threshold:
                return MODE_NORMAL
            if value < threshold * _EMERGENCY_FACTOR:
                return MODE_SHEDDING
            return MODE_EMERGENCY

        return max(
            rung(depth, self.depth_threshold),
            rung(age, self.age_threshold_s),
        )

    # -- mode ladder ---------------------------------------------------

    def evaluate(self, force: bool = False) -> int:
        """Recompute (throttled) and return the current mode."""
        if not self.enabled:
            return MODE_NORMAL
        now = time.monotonic()
        with self._lock:
            if not force and now - self._last_eval < _EVAL_INTERVAL_S:
                return self._mode
            self._last_eval = now
        # signals are read OUTSIDE self._lock: pending_depth /
        # oldest_pending_age take the broker lock, and holding two
        # locks across modules here would add an edge to the static
        # lock graph for no benefit (a stale signal read costs one
        # _EVAL_INTERVAL_S of mode lag)
        depth, age = self._read_signals()
        target = self._severity(depth, age)
        with self._lock:
            self._signals = {"depth": depth, "age_s": age}
            mode = self._mode
            if target >= mode:
                # the signals support (or exceed) the current rung
                self._last_supported = now
            if target > mode:
                self._transition_locked(target, depth, age)
            elif (
                target < mode
                and now - self._last_supported >= _COOLDOWN_S
            ):
                # one rung at a time, re-anchoring the cooldown, so a
                # deep EMERGENCY walks down through SHEDDING instead
                # of snapping open the floodgates
                self._transition_locked(mode - 1, depth, age)
                self._last_supported = now
            return self._mode

    def _transition_locked(
        self, new_mode: int, depth: float, age: float
    ) -> None:
        from ..decisions import DECISIONS
        from ..trace import TRACE

        old = self._mode
        self._mode = new_mode
        metrics = getattr(self.server, "metrics", None)
        if metrics is not None:
            metrics.set_gauge("overload.mode", float(new_mode))
            metrics.set_gauge("overload.broker_depth", depth)
            metrics.set_gauge("overload.oldest_age_s", age)
        # every eval in flight right now ran through this regime
        # shift — stamp its waterfall (bounded broadcast) so a
        # shed/degraded eval explains itself without a /v1/overload
        # join; the incident trace gets the same mark below via its
        # annotations
        for eid in TRACE.in_flight_ids(limit=64):
            TRACE.event(
                eid, "overload.mode_change",
                old=MODE_NAMES[old], new=MODE_NAMES[new_mode],
            )
        prev_incident = self._incident_id
        if old == MODE_NORMAL and new_mode > MODE_NORMAL:
            # one incident trace per excursion from NORMAL: the
            # operator's post-mortem handle for "what shed, and why"
            n = next(self._incident_seq)
            self._incident_id = f"overload:{n}"
            self._incident_shed_at_start = (
                metrics.get_counter("overload.shed")
                if metrics is not None
                else 0.0
            )
            TRACE.begin(
                self._incident_id,
                root_span="ingress.shed",
                mode=MODE_NAMES[new_mode],
                broker_depth=depth,
                oldest_age_s=round(age, 3),
            )
        elif self._incident_id is not None:
            TRACE.annotate(
                self._incident_id,
                mode=MODE_NAMES[new_mode],
                broker_depth=depth,
                oldest_age_s=round(age, 3),
            )
            if new_mode == MODE_NORMAL:
                shed = (
                    metrics.get_counter("overload.shed")
                    - self._incident_shed_at_start
                    if metrics is not None
                    else 0.0
                )
                TRACE.annotate(self._incident_id, shed_total=shed)
                TRACE.finish(self._incident_id, "recovered")
                self._incident_id = None
        DECISIONS.record(
            "overload_mode",
            f"{MODE_NAMES[old]}->{MODE_NAMES[new_mode]}",
            inputs={
                "broker_depth": depth,
                "oldest_age_s": round(age, 3),
                "leader_gen": getattr(
                    self.server, "_leadership_gen", 0
                ),
            },
            alternatives=[
                name
                for i, name in enumerate(MODE_NAMES)
                if i != new_mode
            ],
            outcome="escalate" if new_mode > old else "recover",
            # joins the excursion's incident trace: the id minted on
            # the way up, retained here on the final walk-down too
            trace_id=self._incident_id or prev_incident or "",
            metrics=metrics,
        )

    @property
    def mode(self) -> int:
        return self._mode

    def close_incident(self) -> None:
        """Teardown hook (server stop / leadership revoke): an
        excursion that never walked back to NORMAL would otherwise
        leave its incident trace dangling in flight forever — settle
        it with an explicit `shed` outcome so /v1/traces?outcome=
        filters and trace_report's in-flight header stay honest."""
        from ..trace import TRACE

        with self._lock:
            incident = self._incident_id
            self._incident_id = None
            if incident is None:
                return
            metrics = getattr(self.server, "metrics", None)
            shed = (
                metrics.get_counter("overload.shed")
                - self._incident_shed_at_start
                if metrics is not None
                else 0.0
            )
        TRACE.annotate(incident, shed_total=shed)
        TRACE.finish(incident, "shed")

    # -- admission -----------------------------------------------------

    def admit(self, pclass: Optional[int]) -> Tuple[bool, float]:
        """(admitted, retry_after_s) for one ingress request.
        ``pclass=None`` (exempt endpoints) always admits without
        counting."""
        if pclass is None:
            return True, 0.0
        mode = self.evaluate()
        metrics = getattr(self.server, "metrics", None)
        shed = False
        if mode == MODE_SHEDDING:
            shed = pclass >= PRI_SUBMIT
        elif mode == MODE_EMERGENCY:
            # heartbeats are the one class an overloaded leader must
            # keep answering: shedding them converts ingress overload
            # into a false mass node-death wave — strictly more work
            shed = pclass >= PRI_QUERY
        if shed:
            if metrics is not None:
                metrics.incr("overload.shed")
            return False, _RETRY_AFTER_S.get(mode, 1.0)
        if metrics is not None:
            metrics.incr("overload.accepted")
        return True, 0.0

    def blocking_wait_budget(self, wait_s: float) -> float:
        """Long-poll budget under the current mode: at SHEDDING and
        above, blocking queries degrade to non-blocking (answer the
        current state immediately) so overload can't also pin server
        threads for the full wait — the degradation between "served
        normally" and "shed"."""
        if wait_s <= 0 or self.evaluate() == MODE_NORMAL:
            return wait_s
        metrics = getattr(self.server, "metrics", None)
        if metrics is not None:
            metrics.incr("overload.deferred")
        return 0.0

    # -- surfaces ------------------------------------------------------

    def status(self) -> Dict:
        """/v1/overload payload."""
        mode = self.evaluate(force=True)
        with self._lock:
            signals = dict(self._signals)
            incident = self._incident_id
        return {
            "enabled": self.enabled,
            "mode": mode,
            "mode_name": MODE_NAMES[mode],
            "signals": signals,
            "thresholds": {
                "depth": self.depth_threshold,
                "age_s": self.age_threshold_s,
                "emergency_factor": _EMERGENCY_FACTOR,
            },
            "incident": incident,
        }
