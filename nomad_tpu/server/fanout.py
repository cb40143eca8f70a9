"""Follower scheduling fan-out (reference nomad/worker.go on every
server + plan_queue.go serialization on the leader).

The reference's central scaling argument for the worker/plan-queue
split is that PLANNING scales horizontally — every server runs
scheduling workers against its own replicated state snapshot — while
COMMIT stays serialized on the leader's plan applier.  Until this
module, followers here only replicated and forwarded: every placement
was planned on the leader, so adding servers added commit durability
and zero scheduling throughput.

With ``NOMAD_TPU_FANOUT=1`` every follower runs the full TPU batch
pipeline (chunk chains, continuous admission, storm solves) against
its LOCAL replicated store and its own device:

* **Remote broker leases** — followers dequeue over the cluster
  transport (``broker_dequeue`` / ``broker_ack`` / ``broker_nack`` /
  ``broker_drain_family`` RPCs, batched up to
  ``NOMAD_TPU_FANOUT_LEASE_N`` leases per round trip).  Leases are
  stamped with the LEADER's leadership generation and tracked
  per-server on the leader's broker, where the existing nack-timeout
  sweeper reclaims a dead follower's leases like any other expired
  delivery.  The broker's one-outstanding-eval-per-job pending heaps
  are untouched, so same-job evals can never race across servers.
* **Local planning, serialized commit** — the follower waits
  ``snapshot_min_index(eval.modify_index)`` for its local FSM apply
  to catch up (the same fence the reference worker runs,
  worker.go:228), runs the unchanged assemble/launch/fetch/replay
  chunk chain — and whole-family storm solves, since
  ``drain_family`` gulps are atomic on the leader and so land on ONE
  server — on its local backend, then submits the plan through the
  ``submit_plan`` RPC into the leader's plan queue.  A partial
  commit's ``refresh_index`` is honored by waiting for LOCAL apply
  before the scheduler retries; stale-snapshot plans are exactly
  what ``evaluate_plan`` and the optimistic applier pipeline already
  handle.
* **Generation-fenced end to end** — follower plans carry the
  lease's leadership generation, so the replicated
  ``StaleLeadershipError`` fence (server/fsm.py) rejects work leased
  under a dead leadership on every replica deterministically.  A
  follower death mid-lease is just a nack-timeout redelivery; a
  leader death mid-submit is a structured not-leader response the
  worker converts to nack-for-redelivery.
"""
from __future__ import annotations

import logging
import os
import pickle
import threading
import time
from collections import deque
from typing import Deque, List, Optional, Tuple

from ..decisions import DECISIONS
from ..raft import NotLeaderError
from ..raft.transport import TransportError
from ..structs import Evaluation
from ..trace import TRACE
from .eval_broker import job_family
from .fsm import StaleLeadershipError

LOG = logging.getLogger("nomad_tpu.server.fanout")

# fan-out telemetry, zero-registered at Server construction (the
# `fanout-metrics` nomadlint rule enforces registry membership for
# every fanout.* emission across fanout.py / cluster.py / server.py)
FANOUT_COUNTERS = (
    # follower side
    "fanout.remote_dequeues",  # dequeue RPC round trips with >=1 lease
    "fanout.leases",  # leases received over RPC
    "fanout.acks",
    "fanout.nacks",
    "fanout.plans_submitted",  # plans submitted through the RPC
    "fanout.plan_refresh_waits",  # partial commits waited out locally
    "fanout.plan_not_leader",  # submits rejected by a leadership move
    "fanout.lease_gen_flips",  # leadership generation changed under us
    "fanout.stale_lease_drops",  # buffered leases dropped on a flip
    "fanout.apply_wait_timeouts",  # local FSM apply lagged past budget
    "fanout.segments_shipped",  # trace segments shipped to the leader
    # leader side
    "fanout.remote_leases_granted",
    "fanout.remote_plans",
)
FANOUT_GAUGES = (
    "fanout.workers",  # live fan-out workers on this (follower) server
    "fanout.lease_gen",  # leadership generation of the held leases
    "fanout.remote_unacked",  # leader: leases currently held by peers
)


def fanout_enabled() -> bool:
    return os.environ.get("NOMAD_TPU_FANOUT") == "1"


def fanout_workers() -> int:
    try:
        return max(
            1, int(os.environ.get("NOMAD_TPU_FANOUT_WORKERS", "1"))
        )
    except ValueError:
        return 1


def fanout_lease_n() -> int:
    try:
        return max(
            1, int(os.environ.get("NOMAD_TPU_FANOUT_LEASE_N", "8"))
        )
    except ValueError:
        return 8


# budget a follower waits for its local FSM apply to catch up (the
# eval fence at the gulp boundary, the refresh index after a partial
# commit, its own commit's alloc index); past it the leases nack for
# redelivery
REFRESH_WAIT_S = 5.0


class RemoteBrokerClient:
    """The follower's view of the LEADER's eval broker.

    Implements exactly the broker surface the batch worker uses —
    ``dequeue`` / ``ack`` / ``nack`` / ``drain_family`` /
    ``ready_count`` — over the cluster transport.  Dequeues are
    batched: one RPC leases up to ``NOMAD_TPU_FANOUT_LEASE_N`` evals
    and the surplus is buffered locally, so the gulp-fill loop's
    per-eval dequeues are mostly buffer pops, not round trips.

    Every lease carries the leadership generation the leader stamped
    it with.  ``lease_gen`` is the newest stamp seen; buffered leases
    from an older generation are dropped (and best-effort nacked) the
    moment a newer stamp arrives — their tokens died with the old
    leadership's broker flush anyway.
    """

    def __init__(self, server) -> None:
        self._server = server  # the follower ClusterServer
        self._lock = threading.Lock()
        # buffered (ev, token, gen) leases not yet handed to a worker
        self._buffer: Deque[Tuple[Evaluation, str, int]] = deque()
        # newest leadership generation a lease RPC reported; the
        # follower view's `_leadership_gen` and every submitted
        # plan's `leader_gen` stamp read this
        self.lease_gen = 0
        # leader-reported ready backlog (piggybacked on lease RPCs):
        # feeds the worker's adaptive gulp/chunk sizing without a
        # dedicated RPC per sizing decision
        self._ready_hint = 0
        self.lease_n = fanout_lease_n()
        # decision-ledger dedup: lease absorption is per-RPC hot, so
        # the fanout_lease site ledgers only when the grant size
        # changes (or the generation flips) — the steady drip of
        # identical full grants is one record, not thousands
        self._last_lease_grant = -1

    # -- plumbing ------------------------------------------------------

    def _leader(self) -> Optional[str]:
        leader = self._server.raft.leader_hint()
        if leader == self._server.addr:
            return None  # we ARE the leader: fan-out must not self-RPC
        return leader

    def _rpc(self, method: str, payload: dict) -> dict:
        leader = self._leader()
        if leader is None:
            raise TransportError("no known leader")
        payload = dict(payload, server=self._server.addr)
        return self._server.transport.rpc(
            self._server.addr, leader, method, payload
        )

    def _metrics(self):
        return getattr(self._server, "metrics", None)

    def _count(self, kind: str, n: float = 1.0) -> None:
        metrics = self._metrics()
        if metrics is not None:
            metrics.incr(f"fanout.{kind}", n)

    def _absorb_leases(
        self, resp: dict, buffer: bool = True
    ) -> List[Tuple[Evaluation, str]]:
        """Fold one lease-granting RPC response into the local state:
        generation bookkeeping, ready-backlog hint, and (for plain
        dequeues) the shared lease buffer.  ``buffer=False`` returns
        the leases WITHOUT buffering — the storm path's drained
        family members belong to the draining worker alone and must
        never be visible to a sibling worker's buffer pops."""
        gen = int(resp.get("gen", 0))
        leases: List[Tuple[Evaluation, str]] = pickle.loads(
            resp["leases"]
        )
        # apply fence (see _lease_response): the eval objects the
        # leader enqueues carry modify_index=0, so the lease-time
        # leader index is the fence the follower's planning must wait
        # out — stamped on OUR unpickled copies as snapshot_index,
        # which both _await_local_apply and the sequential path's
        # snapshot_min_index already honor
        min_index = int(resp.get("min_index", 0))
        for ev, _token in leases:
            ev.snapshot_index = max(
                ev.snapshot_index or 0, min_index
            )
        # distributed trace propagation: each lease carries the
        # LEADER's trace context — open a local recording segment
        # under the leader's trace id so every pipeline span this
        # server records for the eval lands in the segment and ships
        # back on settle/submit (stale leases nacked below close
        # their segments through the same ship path)
        ctxs = resp.get("trace_ctx") or {}
        for ev, _token in leases:
            ctx = ctxs.get(ev.id)
            if ctx:
                TRACE.begin_segment(
                    ev.id, ctx, server_id=self._server.addr
                )
        stale: List[Tuple[Evaluation, str]] = []
        with self._lock:
            self._ready_hint = int(resp.get("ready", 0))
            if gen < self.lease_gen:
                # a DELAYED response from a deposed-but-not-yet-
                # stepped-down leader: its generation must never roll
                # ours back (that would nack valid newer-generation
                # buffered leases and trip the leadership fence on a
                # live chain).  The stale grants themselves go
                # straight back for redelivery below.
                stale.extend(leases)
                leases = []
            elif gen > self.lease_gen:
                if self.lease_gen:
                    self._count("lease_gen_flips")
                self.lease_gen = gen
                metrics = self._metrics()
                if metrics is not None:
                    metrics.set_gauge("fanout.lease_gen", float(gen))
                # buffered leases of an older generation died with
                # that leadership's broker flush: drop them here so a
                # worker can never start a chain on a dead token
                # (stale entries are always a prefix — stamps are
                # monotone and the buffer is append-ordered)
                while self._buffer and self._buffer[0][2] != gen:
                    b_ev, b_token, _g = self._buffer.popleft()
                    stale.append((b_ev, b_token))
            if buffer:
                for ev, token in leases:
                    self._buffer.append((ev, token, gen))
        if stale:
            self._count("stale_lease_drops", float(len(stale)))
        for ev, token in stale:
            try:
                self.nack(ev.id, token)
            except ValueError:
                pass
        if leases:
            self._count("remote_dequeues")
            self._count("leases", float(len(leases)))
        if DECISIONS.enabled and (
            len(leases) != self._last_lease_grant or stale
        ):
            self._last_lease_grant = len(leases)
            DECISIONS.record(
                "fanout_lease",
                f"granted={len(leases)}",
                inputs={
                    "requested": self.lease_n,
                    "ready_hint": self._ready_hint,
                    "lease_gen": self.lease_gen,
                    "stale_dropped": len(stale),
                    "buffered": buffer,
                },
                alternatives=[f"requested={self.lease_n}"],
                outcome="stale_drop" if stale else "absorbed",
                metrics=self._metrics(),
            )
        return leases

    def _pop_buffered(self) -> Tuple[Optional[Evaluation], str]:
        with self._lock:
            while self._buffer:
                ev, token, gen = self._buffer.popleft()
                if gen == self.lease_gen:
                    return ev, token
                # stale generation: token is already dead, drop it
            return None, ""

    # -- the broker surface the workers consume ------------------------

    def dequeue(
        self, schedulers: List[str], timeout: Optional[float] = None
    ) -> Tuple[Optional[Evaluation], str]:
        deadline = (
            time.monotonic() + timeout if timeout is not None else None
        )
        while True:
            ev, token = self._pop_buffered()
            if ev is not None:
                return ev, token
            remaining = None
            if deadline is not None:
                remaining = deadline - time.monotonic()
            rpc_timeout = min(
                0.1, remaining if remaining is not None else 0.1
            )
            t0 = time.monotonic()
            try:
                resp = self._rpc(
                    "broker_dequeue",
                    {
                        "schedulers": list(schedulers),
                        "timeout": max(0.0, rpc_timeout),
                        "n": self.lease_n,
                    },
                )
            except (TransportError, TimeoutError):
                resp = None
            if resp is None or resp.get("not_leader"):
                # leaderless interregnum (or a leader we can't see):
                # back off briefly and let the caller's timeout bound
                # the wait — the fan-out monitor tears workers down if
                # this server itself takes leadership
                if deadline is not None and (
                    time.monotonic() >= deadline
                ):
                    return None, ""
                time.sleep(0.02)
                continue
            leases = self._absorb_leases(resp)
            for l_ev, _tok in leases:
                # the dequeue RPC interval, attributed on each leased
                # eval's trace (the trace root was begun by the
                # leader-side broker dequeue)
                TRACE.add_span(
                    l_ev.id,
                    "fanout.remote_dequeue",
                    t0,
                    time.monotonic() - t0,
                    members=len(leases),
                    server=self._server.addr,
                )
            if not leases and deadline is not None and (
                time.monotonic() >= deadline
            ):
                return None, ""

    def _ship_segment(
        self, eval_id: str, close: bool
    ) -> Optional[dict]:
        """Export the eval's recorded trace segment for piggybacking
        on the settle/submit RPC (``close=True`` on settle retires the
        local buffer — the eval is leaving this server for good)."""
        segment = TRACE.export_segment(
            eval_id, self._server.addr, close=close
        )
        if segment is not None:
            self._count("segments_shipped")
        return segment

    def ack(self, eval_id: str, token: str) -> None:
        payload = {"eval_id": eval_id, "token": token}
        segment = self._ship_segment(eval_id, close=True)
        if segment is not None:
            payload["segment"] = segment
        try:
            resp = self._rpc("broker_ack", payload)
        except (TransportError, TimeoutError) as exc:
            # the lease holder is unreachable: the lease will expire
            # into a nack-timeout redelivery, and re-running the eval
            # is idempotent at the reconciler — same contract as a
            # leader-side crash between commit and ack
            raise ValueError(f"remote ack failed: {exc}") from exc
        if resp.get("not_leader") or resp.get("error"):
            raise ValueError(f"remote ack rejected for {eval_id}")
        self._count("acks")

    def nack(self, eval_id: str, token: str) -> None:
        payload = {"eval_id": eval_id, "token": token}
        segment = self._ship_segment(eval_id, close=True)
        if segment is not None:
            payload["segment"] = segment
        try:
            resp = self._rpc("broker_nack", payload)
        except (TransportError, TimeoutError) as exc:
            raise ValueError(f"remote nack failed: {exc}") from exc
        if resp.get("not_leader") or resp.get("error"):
            raise ValueError(f"remote nack rejected for {eval_id}")
        self._count("nacks")

    def drain_family(
        self,
        schedulers: List[str],
        family: Tuple[str, str],
        max_n: int,
        min_n: int = 1,
    ) -> List[Tuple[Evaluation, str]]:
        """The storm detector's atomic family drain, leased remotely.

        Batched dequeues mean this client's BUFFER may already hold
        the family's FIFO continuation — so the drain first claims
        the contiguous same-family prefix of the buffer, then (only
        if the buffer didn't hit a different-family boundary, which
        the no-leapfrog rule forbids jumping) extends it from the
        leader's broker, where ``drain_family`` is atomic.  Without
        the buffer phase a mass family would fragment: each lease
        batch would strand members in follower buffers below the
        storm trigger, and a coalescible 300-eval drain would decay
        into per-eval chunk chains.  All-or-nothing below ``min_n``
        is preserved — claimed buffer entries are re-prepended
        untouched, so a too-short prefix leaves the pop order
        byte-identical."""
        taken: List[Tuple[Evaluation, str, int]] = []
        stale: List[Tuple[Evaluation, str]] = []
        with self._lock:
            boundary = False
            while self._buffer and len(taken) < max_n:
                ev, token, gen = self._buffer[0]
                if gen != self.lease_gen:
                    # dead-generation stragglers: drop like
                    # _pop_buffered does (nacked below, best-effort)
                    self._buffer.popleft()
                    stale.append((ev, token))
                    continue
                if job_family(ev) != family:
                    boundary = True
                    break
                self._buffer.popleft()
                taken.append((ev, token, gen))

        def _restore() -> None:
            with self._lock:
                for entry in reversed(taken):
                    self._buffer.appendleft(entry)

        for ev, token in stale:
            try:
                self.nack(ev.id, token)
            except ValueError:
                pass
        out = [(ev, token) for ev, token, _gen in taken]
        remote: List[Tuple[Evaluation, str]] = []
        want_more = len(out) < max_n and not (
            boundary
            # a different-family eval buffered behind the prefix (or
            # still buffered at all) fences the walk exactly like the
            # broker's own no-leapfrog rule
            or self._buffered_count() > 0
        )
        if want_more:
            t0 = time.monotonic()
            try:
                resp = self._rpc(
                    "broker_drain_family",
                    {
                        "schedulers": list(schedulers),
                        "family": tuple(family),
                        "max_n": max_n - len(out),
                        "min_n": max(0, min_n - len(out)),
                    },
                )
            except (TransportError, TimeoutError):
                resp = {"not_leader": True}
            if not resp.get("not_leader"):
                # remote members bypass the shared buffer: the storm
                # path owns them exclusively (a sibling worker's pop
                # must never split a family gulp)
                remote = self._absorb_leases(resp, buffer=False)
                for ev, _tok in remote:
                    TRACE.add_span(
                        ev.id,
                        "fanout.remote_dequeue",
                        t0,
                        time.monotonic() - t0,
                        members=len(remote),
                        server=self._server.addr,
                    )
        total = out + remote
        if len(total) < min_n:
            # too short for the trigger: leave the pop order exactly
            # as it was (remote members can only exist here if the
            # leader's own all-or-nothing already passed its share,
            # so a short total means no remote members were taken)
            _restore()
            return []
        return total

    def _buffered_count(self) -> int:
        with self._lock:
            return sum(
                1
                for _ev, _tok, gen in self._buffer
                if gen == self.lease_gen
            )

    def ready_count(self, schedulers=None) -> int:
        """Leader-reported backlog hint + locally buffered leases —
        the adaptive gulp/chunk sizing signal, refreshed by every
        lease RPC instead of a dedicated round trip."""
        with self._lock:
            return self._ready_hint + len(self._buffer)

    def outstanding_buffered(self) -> List[Tuple[Evaluation, str]]:
        """Drain the local lease buffer (teardown path): the caller
        nacks these so a stopping worker never strands buffered
        leases until the nack timeout."""
        with self._lock:
            out = [(ev, token) for ev, token, _g in self._buffer]
            self._buffer.clear()
        return out


class _DonePending:
    """A ``PendingPlan``-shaped result for the synchronous remote
    submit: the RPC already round-tripped, so ``wait`` just hands the
    result back."""

    __slots__ = ("_result",)

    def __init__(self, result) -> None:
        self._result = result

    def wait(self, timeout: Optional[float] = None):
        return self._result


class RemotePlanQueue:
    """The follower's view of the LEADER's plan queue: ``enqueue``
    submits the plan over the ``submit_plan`` RPC (the leader
    enqueues it into its real plan queue and blocks for the
    serialized applier's verdict) and returns a pre-resolved pending.
    The plan and result pickle through the transport, so the follower
    and leader never alias one object graph."""

    def __init__(self, server, broker: RemoteBrokerClient) -> None:
        self._server = server
        self._broker = broker

    def enqueue(self, plan) -> _DonePending:
        payload = {"plan": pickle.dumps(plan)}
        eval_id = getattr(plan, "eval_id", None)
        if eval_id:
            # ship the spans closed so far (assemble/launch/fetch/
            # replay) with the submit — if this server dies between
            # submit and settle, the leader's stitched trace still
            # shows where the planning time went
            segment = self._broker._ship_segment(eval_id, close=False)
            if segment is not None:
                payload["segment"] = segment
        try:
            resp = self._broker._rpc("submit_plan", payload)
        except (TransportError, TimeoutError) as exc:
            # leader unreachable mid-submit: nothing committed that we
            # know of — surface as a leadership problem so the worker
            # nacks the eval for redelivery (an ambiguous commit is
            # idempotent to re-run at the reconciler)
            self._broker._count("plan_not_leader")
            raise NotLeaderError(None) from exc
        if resp.get("stale_leadership"):
            gen, fence = resp["stale_leadership"]
            self._broker._count("plan_not_leader")
            # definitive replicated verdict: the plan was produced
            # under a deposed leadership — never re-forwarded
            raise StaleLeadershipError(gen, fence)
        if resp.get("not_leader"):
            self._broker._count("plan_not_leader")
            raise NotLeaderError(resp.get("leader"))
        if resp.get("timeout"):
            raise TimeoutError("remote plan apply timed out")
        if resp.get("rejected"):
            return _DonePending(None)
        return _DonePending(pickle.loads(resp["result"]))


class _RemoteBlocked:
    """Blocked-eval tracking is a leader-only service: a follower
    worker's ``reblock_eval`` routes the (already replicated) eval to
    the leader, whose ``on_eval_update`` blocks or re-enqueues it."""

    def __init__(self, server) -> None:
        self._server = server

    def block(self, ev) -> None:
        # ClusterServer.on_eval_update forwards route_eval to the
        # leader (and swallows interregnum errors: the next
        # election's restore_evals re-tracks it from state)
        self._server.on_eval_update(ev)


class FollowerView:
    """What a fan-out worker sees as its ``server``: the follower
    ClusterServer with the broker/plan-queue/blocked surfaces
    replaced by their remote (leader-backed) clients, and the
    leadership fence re-derived from the LEASE generation.

    ``_leadership_gen`` is the generation the held leases were
    stamped with — the generation every submitted plan must carry so
    the replicated fence judges it by the leadership it ran under.
    ``_leader_established`` turns False the moment this server's own
    raft term advances past the lease generation (leadership
    definitively moved) or the fan-out manager stops — tripping the
    batch worker's `_check_leadership` fence exactly like a
    leader-side revoke."""

    def __init__(self, server, manager: "FanoutManager") -> None:
        self._server = server
        self._manager = manager
        self.broker = RemoteBrokerClient(server)
        self.plan_queue = RemotePlanQueue(server, self.broker)
        self.blocked = _RemoteBlocked(server)

    def __getattr__(self, name):
        return getattr(self._server, name)

    @property
    def _leadership_gen(self) -> int:
        return self.broker.lease_gen

    @property
    def _leader_established(self) -> bool:
        if not self._manager.active():
            return False
        gen = self.broker.lease_gen
        if gen <= 0:
            return False
        try:
            term = self._server.raft.stats()["term"]
        except Exception:  # noqa: BLE001 — fence fails safe
            return False
        return term <= gen


def _make_fanout_worker(view: FollowerView, seed=None):
    """Construct the follower-mode batch worker (lazy import: the
    batch worker pulls in the jax stack, which module import must not
    force on processes that never fan out)."""
    from .batch_worker import BatchWorker

    class FanoutBatchWorker(BatchWorker):
        """The full TPU batch pipeline, running on a FOLLOWER: local
        replicated state + local device for planning, remote leases
        and remote (serialized) plan commit."""

        # under NOMAD_TPU_FANOUT_MESH=1 this is the one worker class
        # allowed to bring up the device mesh (and head the pod) —
        # see BatchWorker._mesh_allowed
        _is_fanout_worker = True

        def _count_fanout(self, kind: str) -> None:
            metrics = getattr(self.server, "metrics", None)
            if metrics is not None:
                metrics.incr(f"fanout.{kind}")

        def _await_local_apply(self, held) -> bool:
            """The follower analogue of worker.go:228's
            snapshot_min_index fence, hoisted to the gulp boundary:
            wait for the local FSM apply to reach every held eval's
            modify index before the batched pipeline simulates
            against local state.  On timeout every lease is nacked
            for redelivery (False) — planning from state older than
            the eval's trigger could re-place allocations the lagging
            snapshot doesn't show yet."""
            target = 0
            for ev, _token in held:
                target = max(
                    target,
                    ev.modify_index or 0,
                    ev.snapshot_index or 0,
                )
            if target <= self.store.latest_index():
                return True
            try:
                self.store.snapshot_min_index(
                    target, timeout=REFRESH_WAIT_S
                )
                return True
            except TimeoutError:
                self._count_fanout("apply_wait_timeouts")
                DECISIONS.record(
                    "fanout_nack",
                    "nack_redeliver",
                    inputs={
                        "held": len(held),
                        "target_index": target,
                        "local_index": self.store.latest_index(),
                        "wait_s": REFRESH_WAIT_S,
                        "leader_gen": self._leader_gen(),
                    },
                    alternatives=["keep_waiting"],
                    outcome="apply_wait_timeout",
                    trace_id=held[0][0].id if held else "",
                    metrics=getattr(self.server, "metrics", None),
                )
                for ev, token in held:
                    self._nack_quietly(ev, token)
                return False

        def _process_batch(self, batch):
            if not self._await_local_apply(batch):
                return []
            return super()._process_batch(batch)

        def _process_storm(self, members):
            if not self._await_local_apply(members):
                return []
            return super()._process_storm(members)

        def submit_plan(self, plan):
            """Worker.submit_plan with the remote commit protocol:
            the plan carries the LEASE generation, the enqueue is the
            synchronous ``submit_plan`` RPC, and both the partial-
            commit ``refresh_index`` and our own full commit's
            ``alloc_index`` are honored by waiting for LOCAL apply —
            the next chain member must see this plan's allocations
            in follower state, or its conflict fences would demote
            every subsequent wave member to a serial re-replay."""
            import time as _time

            if getattr(plan, "leader_gen", None) is None:
                plan.leader_gen = self._leader_gen()
            plan.snapshot_index = self.store.latest_index()
            t0 = _time.monotonic()
            try:
                pending = self.server.plan_queue.enqueue(plan)
                result = pending.wait(timeout=10.0)
                if result is None:
                    raise RuntimeError("plan rejected")
                self._count_fanout("plans_submitted")
                if result.refresh_index:
                    self._count_fanout("plan_refresh_waits")
                    DECISIONS.record(
                        "fanout_nack",
                        "refresh_wait",
                        inputs={
                            "refresh_index": result.refresh_index,
                            "local_index": self.store.latest_index(),
                            "wait_s": REFRESH_WAIT_S,
                            "leader_gen": self._leader_gen(),
                        },
                        alternatives=["plan_on_stale_snapshot"],
                        outcome="partial_commit",
                        trace_id=plan.eval_id or "",
                        metrics=getattr(
                            self.server, "metrics", None
                        ),
                    )
                    snap = self.store.snapshot_min_index(
                        result.refresh_index,
                        timeout=REFRESH_WAIT_S,
                    )
                    return result, snap
                if result.alloc_index:
                    # best-effort catch-up to our own commit; a
                    # lagging apply only costs conflict-fence
                    # fallbacks, never correctness (the leader's
                    # evaluate_plan is the serialization point)
                    self.store.wait_for_index(
                        result.alloc_index,
                        timeout=REFRESH_WAIT_S,
                    )
                return result, None
            finally:
                # commit-plane wait accounting (Worker.plan_wait_s):
                # the remote round trip + local-apply catch-up is
                # serialized-commit latency, not planning work
                dt = _time.monotonic() - t0
                self.plan_wait_s += dt
                if plan.eval_id:
                    TRACE.add_span(
                        plan.eval_id, "fanout.plan_submit", t0, dt
                    )

    return FanoutBatchWorker(view, seed=seed)


class FanoutManager:
    """Owns the fan-out worker fleet on one ClusterServer: a monitor
    thread watches the raft role and runs ``NOMAD_TPU_FANOUT_WORKERS``
    follower-mode batch workers exactly while this server is a
    follower with a known leader.  Taking leadership (or stopping)
    tears them down — the leader's own workers take over, and the
    follower view's ``_leader_established`` goes False so in-flight
    chains abort through the leadership fence."""

    def __init__(self, server, seed=None) -> None:
        self.server = server
        self.seed = seed
        self.enabled = fanout_enabled()
        self.view: Optional[FollowerView] = None
        self.workers: List[object] = []
        self._active = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def active(self) -> bool:
        return self._active

    def start(self) -> None:
        if not self.enabled or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._monitor, name="fanout-monitor", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=5.0)
        self._thread = None
        self._stop_workers(dispose=True)

    # -- monitor loop --------------------------------------------------

    def _monitor(self) -> None:
        while not self._stop.is_set():
            try:
                self._reconcile()
            except Exception:  # noqa: BLE001 — the monitor must
                # survive any single pass; a dead monitor would
                # silently freeze the fan-out fleet in its last shape
                LOG.exception("fanout reconcile failed")
            self._stop.wait(0.05)
        self._stop_workers()

    def _reconcile(self) -> None:
        srv = self.server
        if not srv._running or srv.is_leader():
            self._stop_workers()
            return
        if srv.raft.leader_hint() is None:
            # leaderless interregnum: running workers idle on failed
            # dequeues (cheap) and resume the moment a leader exists;
            # none are STARTED until one is known
            return
        self._ensure_workers()

    def _ensure_workers(self) -> None:
        with self._lock:
            if self._active and all(
                w._thread is not None and w._thread.is_alive()
                for w in self.workers
            ):
                return
            if self.view is None:
                self.view = FollowerView(self.server, self)
            self._active = True
            if not self.workers:
                self.workers = [
                    _make_fanout_worker(self.view, seed=self.seed)
                    for _ in range(fanout_workers())
                ]
            for worker in self.workers:
                if worker._thread is None or not (
                    worker._thread.is_alive()
                ):
                    worker.start()
            metrics = getattr(self.server, "metrics", None)
            if metrics is not None:
                metrics.set_gauge(
                    "fanout.workers", float(len(self.workers))
                )

    def _stop_workers(self, dispose: bool = False) -> None:
        """Tear the fleet down.  ``dispose=False`` (a leadership
        change) PARKS the workers rather than discarding them: their
        device mirrors — and, on a pod head, the mesh peers' mirror
        shards, which a discarded worker could never rebuild (the old
        pod service still owns the port) — stay resident, so
        re-establishing the fleet catches up in O(dirty rows) deltas
        instead of a full-world resync.  A parked worker's mirrors
        are marked dirty exactly like ``_on_device_transition``: an
        abandoned in-flight launch may still be reading them, so the
        catch-up sync must re-upload rather than donate the buffers
        out from under it — without this, a re-established fleet
        plans against a mirror whose buffers a straggler consumed.
        ``dispose=True`` (manager shutdown) additionally releases the
        workers and their pod service."""
        with self._lock:
            if not self._active and not self.workers:
                return
            self._active = False
            if dispose:
                workers, self.workers = self.workers, []
            else:
                workers = list(self.workers)
            view = self.view
        for worker in workers:
            if dispose and hasattr(worker, "dispose"):
                worker.dispose()
            else:
                worker.stop()
            mark = getattr(worker, "_mark_mirror_dirty", None)
            if mark is not None:
                mark()
        # buffered (undelivered) leases must not sit out the nack
        # timeout: hand them straight back for redelivery
        if view is not None:
            for ev, token in view.broker.outstanding_buffered():
                try:
                    view.broker.nack(ev.id, token)
                except ValueError:
                    pass
        metrics = getattr(self.server, "metrics", None)
        if metrics is not None:
            metrics.set_gauge("fanout.workers", 0.0)
