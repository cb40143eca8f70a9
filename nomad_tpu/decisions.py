"""Adaptive-decision ledger: flight data for the control loops.

The stack is steered by a web of EWMA heuristics — chunk-width
planning, the adaptive gulp cap, admission gating, storm triggers and
settle beats, the overload mode ladder, fan-out lease sizing,
watchdog budgets, federation retry-region selection.  Each of those
sites picks an action from alternatives using a snapshot of signals,
and until this module none of them recorded *why*.  The ledger is a
process-wide bounded ring of structured :class:`DecisionRecord` dicts
(site slug, inputs snapshot, chosen action, alternatives considered,
outcome, trace-id link) so an operator — or the future self-tuning
controller (ROADMAP item 6) — can join "what the system did" to "what
it saw when it did it".

Every site MUST be declared in :data:`DECISION_SITES` (slug →
nomadlint path key); the ``decision-ledger`` lint rule statically
checks both directions: a registered slug must be recorded by its
owning module, and a ``record("slug", ...)`` call site must be
registered.  Per-site counters (``decision.site.<slug>``) make the
coverage observable at runtime too — absence of a series must mean
"site never fired", not "not exported", so Server zero-registers
:data:`DECISION_COUNTERS` / :data:`DECISION_GAUGES` at construction.

``NOMAD_TPU_DECISIONS=0`` opts out: ``record()`` returns before
touching the ring or any metric, and hot paths additionally gate on
``DECISIONS.enabled`` so they skip building the inputs dict at all.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = [
    "DECISIONS",
    "DECISION_COUNTERS",
    "DECISION_GAUGES",
    "DECISION_SITES",
    "DecisionLedger",
    "decisions_enabled",
]

# Registry of every adaptive decision site: slug -> the nomadlint
# DEFAULT_PATHS key of the module that owns (records) it.  The
# decision-ledger rule parses this literal dict, so it must stay a
# plain literal — no comprehensions, no computed keys.
DECISION_SITES: Dict[str, str] = {
    "chunk_width": "batch_worker",
    "adaptive_cap": "batch_worker",
    "admission_defer": "batch_worker",
    "storm_trigger": "batch_worker",
    "storm_settle": "batch_worker",
    "overload_mode": "overload",
    "fanout_lease": "fanout",
    "fanout_nack": "fanout",
    "watchdog_budget": "device_supervisor",
    "federation_retry": "federation",
}

# Literal tuples (the metric-family lint reads them via
# ast-literal extraction — keep them spelled out, one name per site).
DECISION_COUNTERS = (
    "decision.recorded",
    "decision.evicted",
    "decision.site.chunk_width",
    "decision.site.adaptive_cap",
    "decision.site.admission_defer",
    "decision.site.storm_trigger",
    "decision.site.storm_settle",
    "decision.site.overload_mode",
    "decision.site.fanout_lease",
    "decision.site.fanout_nack",
    "decision.site.watchdog_budget",
    "decision.site.federation_retry",
)
DECISION_GAUGES = ("decision.ring_depth",)


def decisions_enabled() -> bool:
    return os.environ.get("NOMAD_TPU_DECISIONS", "1") != "0"


# ring depth: the newest-wins retention bound
RING_DEPTH = 512


class DecisionLedger:
    """Process-wide bounded ring of adaptive-decision records.

    Like ``TRACE`` this is a module singleton shared by every Server
    in the process (TestCluster servers report the same ledger; the
    cluster fan-in dedups by ``seq``).  All mutation happens under
    ``_lock``; reads snapshot under the same lock and return copies,
    so callers can serialize without racing writers.
    """

    def __init__(self, ring: int = RING_DEPTH) -> None:
        self.enabled = decisions_enabled()
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=ring)
        self._seq = 0
        self._evicted = 0
        from .tsan import maybe_instrument

        maybe_instrument(self, "DecisionLedger")

    # -- write path ---------------------------------------------------

    def record(
        self,
        site: str,
        action: str,
        *,
        inputs: Optional[Dict[str, Any]] = None,
        alternatives: Optional[List[Any]] = None,
        outcome: str = "applied",
        trace_id: str = "",
        metrics=None,
    ) -> Optional[Dict[str, Any]]:
        """Append one record; returns it, or None when opted out.

        ``metrics`` is the calling server's Metrics handle — passed
        per call because the ledger is process-wide but counters are
        per-server.  Cheap by design: one dict build + a lock'd
        append; hot paths should still gate on ``.enabled`` to skip
        assembling ``inputs``.
        """
        if not self.enabled:
            return None
        rec: Dict[str, Any] = {
            "seq": 0,  # assigned under the lock below
            "t": time.time(),
            "site": site,
            "action": action,
            "inputs": dict(inputs or {}),
            "alternatives": list(alternatives or ()),
            "outcome": outcome,
            "trace_id": trace_id or "",
        }
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            evicting = len(self._ring) == self._ring.maxlen
            if evicting:
                self._evicted += 1
            self._ring.append(rec)
            depth = len(self._ring)
        if metrics is not None:
            metrics.incr("decision.recorded")
            if site in DECISION_SITES:
                metrics.incr("decision.site." + site)
            if evicting:
                metrics.incr("decision.evicted")
            metrics.set_gauge("decision.ring_depth", depth)
        return rec

    # -- read path ----------------------------------------------------

    def recent(
        self,
        site: Optional[str] = None,
        outcome: Optional[str] = None,
        trace: Optional[str] = None,
        limit: int = 64,
    ) -> List[Dict[str, Any]]:
        """Newest-first records, optionally filtered."""
        with self._lock:
            records = list(self._ring)
        out: List[Dict[str, Any]] = []
        for rec in reversed(records):
            if site and rec["site"] != site:
                continue
            if outcome and rec["outcome"] != outcome:
                continue
            if trace and rec["trace_id"] != trace:
                continue
            out.append(dict(rec))
            if len(out) >= limit:
                break
        return out

    def counts(self) -> Dict[str, int]:
        """Per-site record counts currently retained in the ring."""
        with self._lock:
            records = list(self._ring)
        by_site: Dict[str, int] = {}
        for rec in records:
            by_site[rec["site"]] = by_site.get(rec["site"], 0) + 1
        return by_site

    def to_dict(
        self,
        site: Optional[str] = None,
        outcome: Optional[str] = None,
        trace: Optional[str] = None,
        limit: int = 64,
    ) -> Dict[str, Any]:
        with self._lock:
            depth = len(self._ring)
            cap = self._ring.maxlen
            evicted = self._evicted
        return {
            "enabled": self.enabled,
            "ring": {"depth": depth, "cap": cap, "evicted": evicted},
            "sites": sorted(DECISION_SITES),
            "counts": self.counts(),
            "decisions": self.recent(
                site=site, outcome=outcome, trace=trace, limit=limit
            ),
        }

    # -- test / bench hooks -------------------------------------------

    def set_enabled(self, enabled: bool) -> None:
        self.enabled = bool(enabled)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._evicted = 0


DECISIONS = DecisionLedger()
