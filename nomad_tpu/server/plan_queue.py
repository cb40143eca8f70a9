"""Plan queue (reference nomad/plan_queue.go): priority heap of pending
plans awaiting the serialized applier; each entry carries a future the
submitting worker blocks on.
"""
from __future__ import annotations

import heapq
import itertools
import threading
import time
from typing import List, Optional, Tuple

from ..raft import NotLeaderError
from ..structs import Plan, PlanResult


class PendingPlan:
    """A plan on its way through the applier's pipeline (one that found
    the applier idle never becomes one: ``PlanApplier.apply`` runs it
    on the submitter's thread).  It crosses three
    threads and back (submitter -> verifier -> committer -> submitter),
    so it carries what the flight recorder needs to keep the eval's
    trace one tree: ``cause``, the id of the submitter's open span
    (every plan.* span names it as its parent), and the instants of
    the hand-offs, from which the three waits are read."""

    def __init__(self, plan: Plan, cause: Optional[int] = None) -> None:
        self.plan = plan
        self.cause = cause
        self.t_enqueued = time.monotonic()
        self.t_evaluated: Optional[float] = None
        self.t_responded: Optional[float] = None
        self._event = threading.Event()
        self._result: Optional[PlanResult] = None
        self._error: Optional[Exception] = None

    def respond(
        self, result: Optional[PlanResult], error: Optional[Exception]
    ) -> None:
        self._result = result
        self._error = error
        self.t_responded = time.monotonic()
        self._event.set()

    def wait(self, timeout: Optional[float] = None) -> PlanResult:
        if not self._event.wait(timeout):
            raise TimeoutError("plan apply timed out")
        if self._error is not None:
            raise self._error
        return self._result


class PlanQueue:
    def __init__(self) -> None:
        self._lock = threading.Condition()
        self._enabled = False
        self._heap: List[Tuple[int, int, PendingPlan]] = []
        self._counter = itertools.count()
        self.stats = {"depth": 0}

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            self._enabled = enabled
            if not enabled:
                self.flush()
            self._lock.notify_all()

    def idle(self) -> bool:
        """Enabled and holding no plan: half of what a submitter has
        to observe (the other half is the applier's own) to apply its
        plan on its own thread."""
        with self._lock:
            return self._enabled and not self._heap

    def flush(self) -> None:
        # the queue only runs on a leader: a flush IS a leadership
        # (or lifecycle) boundary, and pending submitters must nack
        # their evals for redelivery rather than fail them
        for _, _, pending in self._heap:
            pending.respond(None, NotLeaderError(None))
        self._heap = []
        self.stats["depth"] = 0

    def enqueue(
        self, plan: Plan, cause: Optional[int] = None
    ) -> PendingPlan:
        with self._lock:
            if not self._enabled:
                raise NotLeaderError(None)
            pending = PendingPlan(plan, cause)
            heapq.heappush(
                self._heap,
                (-plan.priority, next(self._counter), pending),
            )
            self.stats["depth"] += 1
            self._lock.notify_all()
            return pending

    def dequeue(self, timeout: Optional[float] = None) -> Optional[PendingPlan]:
        with self._lock:
            if not self._heap:
                self._lock.wait(timeout)
            if not self._heap:
                return None
            _, _, pending = heapq.heappop(self._heap)
            self.stats["depth"] -= 1
            return pending
