"""The control plane in one process (reference nomad/server.go +
nomad/leader.go).

Wires the state store, eval broker, blocked-evals tracker, plan queue,
the serialized plan applier, N scheduling workers and the node heartbeat
monitor, and exposes the write-path operations the RPC endpoints perform
in the reference (job register -> eval create, node register/heartbeat ->
node evals, etc.).

Consensus/federation scope for this stage: the reference replicates this
state machine with Raft and gossips membership with Serf
(nomad/server.go:105-186); here a single process owns the store and the
leader services are always enabled.  The store's index plumbing,
snapshot-fencing and the broker/applier protocols are the Raft-facing
surfaces and keep their reference semantics so a replicated log can slot
in underneath.
"""
from __future__ import annotations

import itertools
import logging
import threading
import time
from typing import Dict, List, Optional

LOG = logging.getLogger("nomad_tpu.server")

from .. import collector
from ..state.store import StateStore
from ..structs import (
    Allocation,
    ALLOC_CLIENT_STATUS_FAILED,
    ALLOC_DESIRED_STOP,
    Evaluation,
    EVAL_STATUS_BLOCKED,
    EVAL_STATUS_PENDING,
    EVAL_TRIGGER_JOB_DEREGISTER,
    EVAL_TRIGGER_JOB_REGISTER,
    EVAL_TRIGGER_NODE_UPDATE,
    id_counts,
    Job,
    JOB_TYPE_CORE,
    JOB_TYPE_SERVICE,
    Node,
    NODE_STATUS_DOWN,
    NODE_STATUS_READY,
)
from .blocked_evals import BlockedEvals
from .deployment_watcher import DeploymentWatcher
from .drainer import Drainer
from .volume_watcher import VolumeWatcher
from .eval_broker import EvalBroker
from .periodic import PeriodicDispatcher
from .plan_apply import PlanApplier
from .plan_queue import PlanQueue
from .worker import Worker

DEFAULT_HEARTBEAT_TTL = 30.0

# leadership failover telemetry, zero-registered at construction (the
# `leadership-metrics` nomadlint rule enforces registry membership for
# every emission across server.py / batch_worker.py / cluster.py)
LEADERSHIP_COUNTERS = (
    "leadership.establishes",
    "leadership.revokes",
    "leadership.unacked_on_revoke",
    "leadership.chain_aborts",
    "leadership.plan_rejected",
    "leadership.stale_wave_fenced",
    "raft.forward_retries",
)
LEADERSHIP_GAUGES = ("leadership.generation", "leadership.is_leader")


class _PlanRecorder:
    """Records scheduler output without committing (dry-run planner)."""

    def __init__(self, store: StateStore) -> None:
        self.store = store
        self.plans = []
        self.evals = []

    def submit_plan(self, plan):
        from ..structs import PlanResult

        self.plans.append(plan)
        # report everything as committed so the scheduler completes
        result = PlanResult(
            node_update=plan.node_update,
            node_allocation=plan.node_allocation,
            node_preemptions=plan.node_preemptions,
            deployment=plan.deployment,
            deployment_updates=plan.deployment_updates,
            alloc_index=self.store.latest_index(),
        )
        return result, None

    def update_eval(self, ev):
        self.evals.append(ev)

    def create_eval(self, ev):
        self.evals.append(ev)

    def reblock_eval(self, ev):
        self.evals.append(ev)


class Keyring:
    """Gossip encryption keyring (reference serf KeyManager backing
    `operator keyring`): a set of installed base64 keys with one
    primary.  Transport encryption itself rides mTLS in this build
    (raft/tcp.py), so the keyring manages identities/rotation state.

    Scope deviation: ops apply to the ADDRESSED agent only — the
    reference broadcasts key changes through serf; here each server's
    keyring is local state, so rotation tooling must address every
    server (mTLS certs, not these keys, are what gates transport)."""

    def __init__(self) -> None:
        import threading

        self._lock = threading.Lock()
        self._keys: list = []
        self._primary: str = ""

    @staticmethod
    def _validate(key: str) -> str:
        import base64 as _b64

        try:
            raw = _b64.b64decode(key, validate=True)
        except Exception:
            raise ValueError("key must be base64")
        if len(raw) not in (16, 24, 32):
            raise ValueError("key must decode to 16, 24 or 32 bytes")
        return key

    def install(self, key: str) -> None:
        key = self._validate(key)
        with self._lock:
            if key not in self._keys:
                self._keys.append(key)
            if not self._primary:
                self._primary = key

    def use(self, key: str) -> None:
        with self._lock:
            if key not in self._keys:
                raise ValueError("key is not installed")
            self._primary = key

    def remove(self, key: str) -> None:
        with self._lock:
            if key == self._primary:
                raise ValueError("cannot remove the primary key")
            if key not in self._keys:
                raise ValueError("key is not installed")
            self._keys.remove(key)

    def list(self) -> dict:
        with self._lock:
            return {
                "Keys": {k: 1 for k in self._keys},
                "PrimaryKeys": (
                    {self._primary: 1} if self._primary else {}
                ),
            }


class Server:
    def __init__(
        self,
        num_schedulers: int = 1,
        heartbeat_ttl: float = DEFAULT_HEARTBEAT_TTL,
        seed: Optional[int] = None,
        nack_timeout: float = 60.0,
        acl_enabled: bool = False,
        # the batched TPU pipeline is the default scheduling path; it
        # falls back per eval to the exact sequential scheduler for
        # shapes the kernel doesn't model (networks/devices/multi-TG/
        # sticky), with prescore-rate + fallback counters in /v1/metrics
        batch_pipeline: bool = True,
        store: Optional[StateStore] = None,
        acls=None,
        device_config=None,
    ) -> None:
        from ..acl import ACLStore
        from ..telemetry import Metrics

        # store/acls are injectable so a replicated cluster can hand in
        # raft-backed facades (server/cluster.py); default is the
        # single-process direct store
        self.store = store if store is not None else StateStore()
        self.acls = acls if acls is not None else ACLStore(
            enabled=acl_enabled
        )
        self.metrics = Metrics()
        # the store counts which side each alloc write's usage took,
        # and carries the registry to the plan's per-node fit
        self.store.attach_metrics(self.metrics)
        # the scheduler's own fit check counts its sides there too:
        # 0 must read "no option ranked", not "not exported"
        from ..sched.rank import FIT_COUNTERS as RANK_FIT_COUNTERS

        self.metrics.preregister(counters=RANK_FIT_COUNTERS)
        # the process's id pool keeps its two counts (ID_COUNTERS) as
        # plain integers: structs.new_id takes no lock and makes no
        # call a draw.  The registry reads them whenever it is read,
        # from construction on
        self.metrics.attach_live_counters(id_counts)
        # the same for the collector policy a started server holds
        # (nomad_tpu/collector.py): freezes and idle reclaims
        self.metrics.attach_live_counters(collector.counts)
        self._holds_collector = False
        # placement explainability: zero-register the placement.*
        # counter/gauge families so dashboards see the whole reason
        # vocabulary from process start (absence-of-series must mean
        # absence-of-filtering, not "no eval explained yet")
        from ..explain import preregister as _preregister_placement

        _preregister_placement(self.metrics)
        # accelerator supervisor: owns device liveness (health probes,
        # launch watchdogs, hot CPU failover) for every worker.  Built
        # BEFORE the workers so they can subscribe to backend
        # transitions; idle (no thread) where JAX resolved the CPU
        # unless forced via NOMAD_TPU_SUPERVISOR=1 or an armed
        # NOMAD_TPU_FAULT.  The batch pipeline's backend is resolved
        # HERE, once (PJRT init is paid now, not inside the first
        # flush): what JAX initialised decides supervision, donation
        # and the /v1/device payload — never how JAX_PLATFORMS is
        # spelt.  A sequential-oracle server never touches JAX.
        from ..device import DeviceSupervisor

        backend = None
        if batch_pipeline:
            from ..backend import resolve_backend

            backend = resolve_backend()
            LOG.info(
                "batch pipeline backend: platform=%s device_kind=%s "
                "devices=%d",
                backend.platform, backend.device_kind,
                backend.device_count,
            )
        self.device_supervisor = DeviceSupervisor(
            metrics=self.metrics, config=device_config,
            backend=backend,
        )
        self.broker = EvalBroker(nack_timeout=nack_timeout)
        # lost-eval accounting: the broker is constructed without a
        # telemetry handle, so wire ours in and zero-register its
        # family — broker.delivery_failures is the zero-lost-evals
        # SLO's burn signal, and absence-of-series must mean "nothing
        # ever lost", not "not exported"
        from .eval_broker import BROKER_COUNTERS

        self.broker.metrics = self.metrics
        self.metrics.preregister(counters=BROKER_COUNTERS)
        # the flight recorder's fold (an acked eval's trace, folded by
        # layer where it closes) lands on this server's telemetry
        # through the broker's ack: zero-register the trace.* family
        # (absence of samples must mean "no eval acked yet" or
        # NOMAD_TPU_TRACE=0, not "not exported")
        from ..trace import FOLD_COUNTERS, FOLD_SAMPLES

        self.metrics.preregister(
            counters=FOLD_COUNTERS, samples=FOLD_SAMPLES
        )
        self.blocked = BlockedEvals(self.broker)
        self.plan_queue = PlanQueue()
        self.applier = PlanApplier(
            self.store, self.plan_queue, self.blocked, self.metrics,
            # in-flight plans of a deposed leadership respond
            # NotLeaderError (the worker converts it to
            # nack-for-redelivery) instead of committing against state
            # a new leader now owns
            leader_check=lambda: self._leader_established,
        )
        # leadership failover observability: zero-registered so
        # absence-of-series means "no leadership ever changed", never
        # "not exported" (the same contract as device.* incidents)
        self.metrics.preregister(
            counters=LEADERSHIP_COUNTERS, gauges=LEADERSHIP_GAUGES
        )
        # ingress backpressure: overload is a first-class server state
        # (NORMAL -> SHEDDING -> EMERGENCY mode ladder driven by
        # broker depth / oldest-pending-age / flight-recorder p99)
        # with priority-classed shedding at the HTTP ingress.  The
        # overload.* family is zero-registered here so dashboards can
        # tell "never overloaded" from "not exported".
        from .overload import (
            OVERLOAD_COUNTERS,
            OVERLOAD_GAUGES,
            OverloadController,
        )

        self.overload = OverloadController(self)
        self.metrics.preregister(
            counters=OVERLOAD_COUNTERS, gauges=OVERLOAD_GAUGES
        )
        # follower scheduling fan-out: zero-register the fanout.*
        # family (absence-of-series must mean "fan-out never engaged"
        # — single server, or NOMAD_TPU_FANOUT off — not "not
        # exported").  The registries live in server/fanout.py; the
        # manager itself exists only on ClusterServer.
        from .fanout import FANOUT_COUNTERS, FANOUT_GAUGES

        self.metrics.preregister(
            counters=FANOUT_COUNTERS, gauges=FANOUT_GAUGES
        )
        # multi-region federation: zero-register the federation.*
        # family (absence-of-series must mean "single region, nothing
        # ever crossed the WAN", not "not exported").  The registries
        # live in server/federation.py; the router itself exists only
        # on ClusterServer.
        from .federation import FEDERATION_COUNTERS, FEDERATION_GAUGES

        self.metrics.preregister(
            counters=FEDERATION_COUNTERS, gauges=FEDERATION_GAUGES
        )
        # cluster-scope observability: zero-register the obs.* /
        # cluster.* family (absence-of-series must mean "no segment
        # ever stitched / no fan-in ever asked", not "not exported")
        # and stand up the metric time-series history ring — its
        # snapshot thread starts with the server lifecycle
        from ..telemetry import (
            CLUSTER_OBS_COUNTERS,
            CLUSTER_OBS_GAUGES,
            MetricsHistory,
        )

        self.metrics.preregister(
            counters=CLUSTER_OBS_COUNTERS, gauges=CLUSTER_OBS_GAUGES
        )
        self.metrics_history = MetricsHistory(self.metrics)
        # control-loop flight data: the SLO engine grades declared
        # objectives over the history ring just stood up, and the
        # process-wide decision ledger records why every adaptive
        # site chose what it chose.  Both families are
        # zero-registered (absence-of-series must mean "never
        # evaluated" / "site never fired", not "not exported").
        from ..decisions import (
            DECISION_COUNTERS,
            DECISION_GAUGES,
            DECISIONS,
        )
        from ..slo import SLO_COUNTERS, SLO_GAUGES, SLOEngine

        self.metrics.preregister(
            counters=DECISION_COUNTERS, gauges=DECISION_GAUGES
        )
        self.metrics.preregister(
            counters=SLO_COUNTERS, gauges=SLO_GAUGES
        )
        self.decisions = DECISIONS
        self.slo = SLOEngine(self.metrics, self.metrics_history)
        # policy-weighted scoring: zero-register the policy.* family
        # (absence-of-series must mean "no policy-weighted select ever
        # ran" — no job carries a PolicySpec, or NOMAD_TPU_POLICY=0 —
        # not "not exported").  Registered outside the batch_pipeline
        # gate: weighted tensor assembly runs in BOTH pipeline modes.
        from ..sched.policy import POLICY_COUNTERS, POLICY_GAUGES

        self.metrics.preregister(
            counters=POLICY_COUNTERS, gauges=POLICY_GAUGES
        )
        if batch_pipeline:
            from .batch_worker import BatchWorker

            self.workers: List[Worker] = [
                BatchWorker(self, seed=seed)
                for _ in range(num_schedulers)
            ]
        else:
            self.workers = [
                Worker(self, seed=seed) for _ in range(num_schedulers)
            ]
        # pipeline-mode markers on /v1/metrics from construction time,
        # so an operator can tell a batch-pipeline server (and whether
        # its optimistic parallel replay is enabled) before any
        # traffic populates the replay.* counters
        self.metrics.set_gauge(
            "server.batch_pipeline", 1.0 if batch_pipeline else 0.0
        )
        # eval-flight-recorder mode marker (NOMAD_TPU_TRACE=0 opts
        # out), so an operator can tell why /v1/traces is empty
        from ..trace import TRACE as _trace

        self.metrics.set_gauge(
            "server.trace_enabled", 1.0 if _trace.enabled else 0.0
        )
        if batch_pipeline:
            self.metrics.set_gauge(
                "batch_worker.parallel_replay_enabled",
                1.0 if any(
                    getattr(w, "parallel_replay", False)
                    for w in self.workers
                ) else 0.0,
            )
            # continuous micro-batching: zero-register the admission.*
            # counter family (absence-of-series must mean "admission
            # never engaged", not "not exported") and expose the mode
            # flag (NOMAD_TPU_ADMIT=0 restores flush-boundary gulps)
            from .batch_worker import (
                ADMISSION_COUNTERS,
                PAIR_COUNTER,
                SHIELD_COUNTERS,
                WALK_COUNTERS,
            )

            self.metrics.preregister(counters=ADMISSION_COUNTERS)
            # cold-compile shield: 0 must read "no cold shape met"
            self.metrics.preregister(counters=SHIELD_COUNTERS)
            # the limit walk: 0 must read "no prescored pick fetched"
            self.metrics.preregister(
                counters=WALK_COUNTERS + (PAIR_COUNTER,)
            )
            # sharded hot path: zero-register the mesh.* family the
            # same way (absence-of-series must mean "mesh never
            # engaged" — NOMAD_TPU_MESH off or a single-device host —
            # not "not exported")
            from .batch_worker import MESH_COUNTERS, MESH_GAUGES

            self.metrics.preregister(
                counters=MESH_COUNTERS, gauges=MESH_GAUGES
            )
            # global storm solver: zero-register the storm.* family
            # (absence-of-series must mean "no storm ever coalesced"
            # — NOMAD_TPU_STORM off or backlog under the trigger —
            # not "not exported") and expose the mode flag
            from .batch_worker import STORM_COUNTERS, STORM_GAUGES

            self.metrics.preregister(
                counters=STORM_COUNTERS, gauges=STORM_GAUGES
            )
            self.metrics.set_gauge(
                "batch_worker.storm_enabled",
                1.0 if any(
                    getattr(w, "storm_enabled", False)
                    for w in self.workers
                ) else 0.0,
            )
            self.metrics.set_gauge(
                "batch_worker.admit_enabled",
                1.0 if any(
                    getattr(w, "admit_enabled", False)
                    for w in self.workers
                ) else 0.0,
            )
        self.deployment_watcher = DeploymentWatcher(self)
        self.drainer = Drainer(self)
        self.periodic = PeriodicDispatcher(self)
        self.volume_watcher = VolumeWatcher(self)
        from .services import ServiceCatalog

        self.catalog = ServiceCatalog(self)
        # raft-index <-> wall-clock witness on every state mutation
        # (reference fsm.go Apply -> timetable.Witness)
        # live log tail for /v1/agent/monitor (reference
        # command/agent/monitor); captures the nomad_tpu logger tree
        from ..monitor import LogMonitor

        self.log_monitor = LogMonitor().install("nomad_tpu")
        # gossip encryption keyring (reference serf keyring backing
        # `operator keyring` / `keyring`: install/use/remove/list)
        self.keyring = Keyring()
        from .timetable import TimeTable

        self.timetable = TimeTable()
        # ReplicatedStore forwards add_watcher to its local store
        self.store.add_watcher(
            lambda _table, index: self.timetable.witness(index)
        )
        self.heartbeat_ttl = heartbeat_ttl
        # node id -> monotonic expiry deadline.  ONE sweeper thread
        # serves every TTL — a threading.Timer per node is an OS thread
        # per node, which at 10k nodes means 10k live threads (the
        # reference's per-node timers are Go runtime timers, not
        # threads; the Python translation must not be thread-per-node)
        self._heartbeat_deadlines: Dict[str, float] = {}
        # mass node-death gather: node id -> monotonic instant its TTL
        # expiry was detected.  A sweep that detects a correlated wave
        # (>= _wave_min expiries) holds the down transition briefly
        # (up to _wave_gather_s, settling one sweep after the last new
        # expiry) so a rack death whose members' heartbeat phases
        # straddle sweep boundaries still commits as ONE batched
        # transition + ONE storm-family replan wave.  A heartbeat
        # arriving mid-gather pulls its node back out (zero false
        # node-downs).  Small waves (< _wave_min) settle just ONE
        # sweep — a single-node death pays one sweep interval of
        # extra detection latency, and a rack death's leading edge
        # merges into the mass wave behind it.
        self._down_wave: Dict[str, float] = {}
        self._wave_counter = itertools.count(1)
        import os as _os

        try:
            self._wave_min = max(
                1,
                int(
                    _os.environ.get("NOMAD_TPU_OVERLOAD_WAVE_MIN", "8")
                ),
            )
        except ValueError:
            self._wave_min = 8
        # gather budget: "auto" (default) derives it from the TTL —
        # a rack death's expiries spread over roughly one heartbeat
        # period (clients beat at a fraction of the TTL), so the
        # budget must exceed the 2s quiet-stream settle or the
        # settle could never engage and every >2s-spread death
        # would fragment
        raw_gather = _os.environ.get(
            "NOMAD_TPU_OVERLOAD_WAVE_GATHER_S", "auto"
        )
        try:
            self._wave_gather_s = max(0.0, float(raw_gather))
        except ValueError:
            self._wave_gather_s = min(
                10.0, max(2.5, heartbeat_ttl / 3.0)
            )
        # node id -> persistent client connection for log/fs
        # proxying (populated from HTTP handler threads)
        self._clients: Dict[str, object] = {}
        self._heartbeat_sweeper: Optional[threading.Thread] = None
        self._sweeper_lock = threading.Lock()
        self._running = False
        self._leader_established = False
        # leadership generation: bumped on every establish (a cluster
        # server passes its raft term, so generations are monotone
        # ACROSS servers).  The batched hot path captures it at
        # wave/chain/storm start and fences commits on it exactly like
        # _backend_epoch fences device buffers — a wave speculated
        # under a deposed leadership can never commit.
        self._leadership_gen = 0
        self._leader_lock = threading.Lock()
        # happens-before sanitizer (NOMAD_TPU_TSAN=1)
        from ..tsan import maybe_instrument

        maybe_instrument(self, "Server")

    # -- lifecycle (reference leader.go:222 establishLeadership) -------

    def start(self) -> None:
        """Single-process mode: this server is always the leader."""
        self._running = True
        # while any server serves, the process's cycle collector
        # freezes what outlives a full collection
        if not self._holds_collector:
            self._holds_collector = True
            collector.hold()
        # history snapshots run for the whole server lifetime, not
        # just leadership — a follower's metrics are history too
        self.metrics_history.start()
        self.establish_leadership()

    def stop(self) -> None:
        self._running = False
        self.revoke_leadership()
        if self._holds_collector:
            self._holds_collector = False
            collector.release()
        self.metrics_history.stop()
        self._heartbeat_deadlines.clear()
        # an overload excursion that never walked back to NORMAL must
        # not leave its incident trace dangling in flight
        self.overload.close_incident()
        # detach the monitor handler or stopped servers pile up on the
        # shared logger and keep buffering every record
        self.log_monitor.uninstall("nomad_tpu")

    def establish_leadership(self, gen: Optional[int] = None) -> None:
        """Enable the leader-only services (reference leader.go:222):
        eval broker, blocked evals, plan queue/applier, scheduling
        workers, deployment watcher, drainer, periodic dispatcher,
        heartbeat timers; then restore evals from state.  ``gen`` is
        the new leadership generation (a cluster server passes its
        raft term); single-process servers self-increment."""
        with self._leader_lock:
            if self._leader_established:
                return
            self._leadership_gen = (
                gen if gen is not None else self._leadership_gen + 1
            )
            # flipped BEFORE any service starts (the mirror of revoke
            # flipping it false first): the applier's leader_check and
            # the workers' leadership fences read this latch, and a
            # worker dequeuing in the establish window must not fence
            # its own brand-new leadership's evals into nacks
            self._leader_established = True
            self.metrics.incr("leadership.establishes")
            self.metrics.set_gauge(
                "leadership.generation", float(self._leadership_gen)
            )
            self.metrics.set_gauge("leadership.is_leader", 1.0)
            self.broker.set_enabled(True)
            self.blocked.set_enabled(True)
            self.plan_queue.set_enabled(True)
            self.applier.start()
            # device supervision runs while this server schedules (a
            # no-op on CPU-only deployments: no probe thread starts)
            self.device_supervisor.start()
            for worker in self.workers:
                worker.start()
            # opt-in: pre-compile the pipelined prescore launch shapes
            # off the scheduling path (production deployments set
            # NOMAD_TPU_WARM_ON_START=1; test servers start hundreds
            # of times and must not pay the XLA compiles).  Without it
            # the cold-compile shield routes the first batches to the
            # exact sequential path until the background compile lands.
            # The warmup waits for the node join wave to settle first:
            # compiled shapes embed the node arena capacity, so warming
            # the initial near-empty table would compile executables no
            # later launch matches
            import os as _os

            if _os.environ.get("NOMAD_TPU_WARM_ON_START") == "1":
                for worker in self.workers:
                    warm = getattr(worker, "warm_shapes", None)
                    if warm is not None:
                        threading.Thread(
                            target=self._warm_when_topology_settles,
                            args=(warm,),
                            name="prescore-warmup",
                            daemon=True,
                        ).start()
                        # the same warmup validates a RECOVERING device
                        # before the supervisor flips the pipeline back
                        self.device_supervisor.add_warm_hook(warm)
            self.deployment_watcher.start()
            self.drainer.start()
            self.periodic.start()
            self.volume_watcher.start()
            # rebuild the service catalog once from restored state; all
            # steady-state maintenance is incremental per alloc delta
            self.catalog.sync()
            # re-arm heartbeat TTLs for every known node (reference
            # heartbeat.go initializeHeartbeatTimers on leadership)
            for node in self.store.iter_nodes():
                if node.status != NODE_STATUS_DOWN:
                    self._reset_heartbeat(node.id)
            # even with zero known nodes, arm TTL enforcement now — a
            # sweeper that died under the previous leadership must
            # never stay dead into this one
            self._ensure_sweeper()
            self.restore_evals()

    def _warm_when_topology_settles(
        self, warm, poll_s: float = 5.0, timeout_s: float = 300.0
    ) -> None:
        """Run a worker's warm_shapes once the node table has at least
        one row and its topology generation held still for one poll
        interval (or the timeout passes).  Compiled launch shapes embed
        the arena capacity, so warming before clients register would
        burn the compiles on a capacity no production launch uses."""
        import time as _time

        deadline = _time.monotonic() + timeout_s
        last = None
        while self._running and _time.monotonic() < deadline:
            # re-read the table each poll: a snapshot restore replaces
            # store.node_table, and a stale binding would see a frozen
            # generation and fire mid-join-wave
            table = self.store.node_table
            gen = (table.epoch, table.topo_generation)
            if table.n_rows > 0 and gen == last:
                break
            last = gen
            _time.sleep(poll_s)
        if not self._running:
            return
        try:
            warm()
        except Exception:  # noqa: BLE001 — warmup is best-effort
            LOG.exception("prescore warmup failed")

    def revoke_leadership(self) -> None:
        """Disable leader-only services (reference leader.go
        revokeLeadership on leadership loss).

        Order matters for the batched hot path: ``_leader_established``
        flips FIRST, so every in-flight wave/chain/storm commit hits
        the leadership fence (and the plan applier's leader check)
        before any queue is torn down — an open chunk chain is dropped
        through its abandon path, a mid-settle storm gulp discards its
        solve before decompose, and the worker nacks every lease it
        still holds.  The broker flush then unacks every OUTSTANDING
        token (drain_family shadow-heap members included); nothing is
        committed, and the next leader's restore_evals re-enqueues all
        of it from replicated state."""
        with self._leader_lock:
            if not self._leader_established:
                return
            self._leader_established = False
            self.metrics.incr("leadership.revokes")
            self.metrics.set_gauge("leadership.is_leader", 0.0)
            self.device_supervisor.stop()
            self.periodic.stop()
            self.deployment_watcher.stop()
            self.drainer.stop()
            self.volume_watcher.stop()
            for worker in self.workers:
                worker.stop()
            self.applier.stop()
            self._heartbeat_deadlines.clear()
            self._down_wave.clear()
            self.plan_queue.set_enabled(False)
            self.blocked.set_enabled(False)
            # every token still outstanding at this point — normal
            # dequeues, drain_family shadow-heap members, mid-settle
            # storm gulps, admission-queue leases — is unacked by the
            # disable flush; the count is the failover's "work in
            # flight" exposure on /v1/metrics
            outstanding = self.broker.unacked_count()
            if outstanding:
                self.metrics.incr(
                    "leadership.unacked_on_revoke", float(outstanding)
                )
            self.broker.set_enabled(False)

    def restore_evals(self) -> None:
        """Re-enqueue non-terminal evals from state after (re)start
        (reference leader.go:352 restoreEvals)."""
        for ev in list(self.store.evals.values()):
            if ev.should_enqueue():
                self.broker.enqueue(ev)
            elif ev.should_block():
                self.blocked.block(ev)

    # -- eval routing (reference fsm.go:715) ----------------------------

    def on_eval_update(self, ev: Evaluation) -> None:
        if ev.should_enqueue():
            self.broker.enqueue(ev)
        elif ev.should_block():
            self.blocked.block(ev)

    def route_eval(self, eval_id: str) -> None:
        """Route a persisted eval into the broker/blocked tracker by id
        (the forwarding target for evals created away from the
        leader)."""
        ev = self.store.eval_by_id(eval_id)
        if ev is not None:
            self.on_eval_update(ev)

    # -- job API (reference nomad/job_endpoint.go Register:349) ---------

    def register_job(self, job: Job) -> Evaluation:
        self._validate_job(job)
        self._inject_connect_sidecars(job)
        self._interpolate_multiregion(job)
        self.store.upsert_job(job)
        if job.is_periodic() or job.is_parameterized():
            # launched by the periodic dispatcher / dispatch call instead
            return None
        ev = Evaluation(
            namespace=job.namespace,
            priority=job.priority,
            type=job.type,
            triggered_by=EVAL_TRIGGER_JOB_REGISTER,
            job_id=job.id,
            job_modify_index=job.modify_index,
            status=EVAL_STATUS_PENDING,
        )
        self.store.upsert_evals([ev])
        self.on_eval_update(ev)
        return ev

    def _inject_connect_sidecars(self, job: Job) -> None:
        """Connect admission hook (reference job_endpoint_hooks.go
        jobImplicitConstraints + the connect hook's sidecar injection:
        each service with connect.sidecar_service gets a
        'connect-proxy-<service>' task; upstream addresses surface to
        the group's tasks as NOMAD_UPSTREAM_ADDR_<dest>, the
        reference's env contract).  Our proxy is the in-tree L4
        forwarder (client/connect.py) instead of Envoy."""
        import sys as _sys

        from ..structs import Lifecycle, Resources, Task

        for tg in job.task_groups:
            upstreams = []  # (dest, local_bind_port), deduped
            seen_up = set()
            sidecars = []  # service names needing a proxy
            for task in tg.tasks:
                for svc in getattr(task, "services", None) or []:
                    cn = svc.connect
                    if cn is None or cn.native:
                        continue
                    if cn.sidecar_service:
                        sidecars.append(svc.name)
                    for up in cn.upstreams:
                        if up.local_bind_port <= 0:
                            raise ValueError(
                                f"connect upstream "
                                f"{up.destination_name!r} requires a "
                                "positive local_bind_port"
                            )
                        key = (
                            up.destination_name, up.local_bind_port
                        )
                        if key in seen_up:
                            continue
                        seen_up.add(key)
                        upstreams.append(key)
            if not sidecars and not upstreams:
                continue
            existing = {t.name for t in tg.tasks}
            proxy_name = (
                f"connect-proxy-{sidecars[0]}"
                if sidecars
                else "connect-proxy"
            )
            # expose the upstream binds to every app task (reference
            # taskenv: NOMAD_UPSTREAM_ADDR_<dest>=127.0.0.1:<port>)
            from ..client.connect import env_key

            for task in tg.tasks:
                for dest, port in upstreams:
                    task.env.setdefault(
                        f"NOMAD_UPSTREAM_ADDR_{env_key(dest)}",
                        f"127.0.0.1:{port}",
                    )
            if proxy_name in existing:
                continue  # idempotent across re-registers
            argv = []
            for dest, port in upstreams:
                argv += ["--upstream", f"{dest}:{port}"]
            if not argv and sidecars:
                # inbound-only sidecar: nothing to bind in the lite
                # proxy; skip injecting a no-op task
                continue
            tg.tasks.append(
                Task(
                    name=proxy_name,
                    # exec (executor-backed): the proxy survives agent
                    # restarts via reattach records instead of
                    # orphaning on SIGKILL; chroot off — the proxy
                    # imports this framework from the client's own
                    # package path, which a sandbox wouldn't see
                    driver="exec",
                    config={
                        "command": _sys.executable,
                        "args": ["-m", "nomad_tpu.client.connect"]
                        + argv,
                        "chroot": False,
                        "connect_upstreams": [
                            [dest, port] for dest, port in upstreams
                        ],
                    },
                    resources=Resources(cpu=100, memory_mb=64),
                    lifecycle=Lifecycle(hook="prestart", sidecar=True),
                )
            )

    def _interpolate_multiregion(self, job: Job) -> None:
        """Specialize a multiregion job for the region it landed in
        (reference job_endpoint_hooks.go jobImpliedConstraints +
        multiregion hook: the local region's count/datacenters/meta
        override the job-wide defaults; cross-region deployment
        coordination itself is the enterprise no-op,
        deploymentwatcher/multiregion_oss.go)."""
        if job.multiregion is None:
            return
        region = job.multiregion.region(
            getattr(self, "region", job.region) or job.region
        )
        if region is None:
            return
        job.region = region.name
        if region.datacenters:
            job.datacenters = list(region.datacenters)
        if region.meta:
            job.meta = {**job.meta, **region.meta}
        if region.count:
            # region count takes precedence over the group count
            # (reference multiregion docs for the region stanza)
            for tg in job.task_groups:
                tg.count = region.count

    def revert_job(
        self,
        namespace: str,
        job_id: str,
        job_version: int,
        enforce_prior_version: Optional[int] = None,
    ) -> Evaluation:
        """Re-register a historical version as the newest one
        (reference job_endpoint.go Job.Revert)."""
        import copy as _copy

        current = self.store.job_by_id(namespace, job_id)
        if current is None:
            raise KeyError(job_id)
        if enforce_prior_version is not None and (
            current.version != enforce_prior_version
        ):
            raise ValueError(
                f"current version is {current.version}, not "
                f"{enforce_prior_version}"
            )
        if job_version == current.version:
            raise ValueError(
                "cannot revert to the current version"
            )
        target = self.store.job_by_version(
            namespace, job_id, job_version
        )
        if target is None:
            raise KeyError(
                f"job {job_id!r} has no version {job_version}"
            )
        # deep copy: never mutate the store-resident history entry
        # (register-time interpolation writes into task groups)
        reverted = _copy.deepcopy(target)
        reverted.stop = False
        return self.register_job(reverted)

    def set_job_stability(
        self, namespace: str, job_id: str, version: int, stable: bool
    ) -> None:
        """(reference job_endpoint.go Job.Stable)"""
        self.store.set_job_stability(namespace, job_id, version, stable)

    def job_summary(self, namespace: str, job_id: str) -> Dict:
        """Per-task-group alloc rollup (reference structs.go JobSummary,
        maintained incrementally in state_store.go; derived on read
        here, same shape)."""
        job = self.store.job_by_id(namespace, job_id)
        if job is None:
            raise KeyError(job_id)
        groups: Dict[str, Dict[str, int]] = {
            tg.name: {
                "Queued": 0, "Complete": 0, "Failed": 0,
                "Running": 0, "Starting": 0, "Lost": 0,
            }
            for tg in job.task_groups
        }
        for a in self.store.allocs_by_job(namespace, job_id):
            g = groups.setdefault(
                a.task_group,
                {
                    "Queued": 0, "Complete": 0, "Failed": 0,
                    "Running": 0, "Starting": 0, "Lost": 0,
                },
            )
            cs = a.client_status
            if cs == "running":
                g["Running"] += 1
            elif cs == "complete":
                g["Complete"] += 1
            elif cs == "failed":
                g["Failed"] += 1
            elif cs == "lost":
                g["Lost"] += 1
            elif a.desired_status == "run":
                g["Starting"] += 1
        # queued = asks the blocked machinery is still holding
        for ev in self.store.evals_by_job(namespace, job_id):
            for tg_name, n in (ev.queued_allocations or {}).items():
                if tg_name in groups and ev.status == "blocked":
                    groups[tg_name]["Queued"] = max(
                        groups[tg_name]["Queued"], n
                    )
        return {
            "JobID": job_id,
            "Namespace": namespace,
            "Summary": groups,
            "Children": {
                "Pending": 0,
                "Running": sum(
                    1
                    for j in self.store.iter_jobs()
                    if j.parent_id == job_id and not j.stopped()
                ),
                "Dead": sum(
                    1
                    for j in self.store.iter_jobs()
                    if j.parent_id == job_id and j.stopped()
                ),
            },
        }

    def stop_alloc(self, alloc_id: str) -> Optional[Evaluation]:
        """User-initiated alloc stop: desired=stop + reschedule eval
        (reference alloc_endpoint.go Alloc.Stop)."""
        from dataclasses import replace as _replace

        from ..structs import ALLOC_DESIRED_STOP, EVAL_TRIGGER_ALLOC_STOP

        alloc = self.store.alloc_by_id(alloc_id)
        if alloc is None:
            raise KeyError(alloc_id)
        stopped = _replace(alloc)
        stopped.desired_status = ALLOC_DESIRED_STOP
        self.store.upsert_allocs([stopped])
        ev = Evaluation(
            namespace=alloc.namespace,
            priority=alloc.job.priority if alloc.job else 50,
            type=alloc.job.type if alloc.job else "service",
            triggered_by=EVAL_TRIGGER_ALLOC_STOP,
            job_id=alloc.job_id,
            status=EVAL_STATUS_PENDING,
        )
        self.store.upsert_evals([ev])
        self.on_eval_update(ev)
        return ev

    def restart_alloc(self, alloc_id: str, task: str = "") -> None:
        """Proxy a restart to the owning client (reference
        client_alloc_endpoint.go Allocations.Restart)."""
        self._client_for_alloc(alloc_id).restart_alloc(alloc_id, task)

    def signal_alloc(
        self, alloc_id: str, signal: str = "SIGTERM", task: str = ""
    ) -> None:
        """(reference client_alloc_endpoint.go Allocations.Signal)"""
        self._client_for_alloc(alloc_id).signal_alloc(
            alloc_id, signal, task
        )

    def _client_for_alloc(self, alloc_id: str):
        alloc = self.store.alloc_by_id(alloc_id)
        if alloc is None:
            raise KeyError(alloc_id)
        client = self._clients.get(alloc.node_id)
        if client is None:
            raise KeyError(f"no client connection for {alloc.node_id}")
        return client

    def exec_alloc(
        self,
        alloc_id: str,
        task: str,
        argv,
        timeout: float = 30.0,
    ):
        """(reference command/alloc_exec.go streaming exec, proxied
        server -> client; one-shot request/response here)"""
        return self._client_for_alloc(alloc_id).exec_alloc(
            alloc_id, task, list(argv), timeout
        )

    def exec_alloc_stream(self, alloc_id: str, task: str, argv):
        """Interactive exec handle, proxied to the owning client
        (reference nomad/rpc.go handleStreamingConn topology)."""
        return self._client_for_alloc(alloc_id).exec_alloc_stream(
            alloc_id, task, list(argv)
        )

    def tail_task_log(
        self, alloc_id: str, task: str, kind: str, cursor
    ):
        return self._client_for_alloc(alloc_id).tail_task_log(
            alloc_id, task, kind, cursor
        )

    def list_alloc_files(self, alloc_id: str, rel: str = ""):
        return self._client_for_alloc(alloc_id).list_alloc_files(
            alloc_id, rel
        )

    def read_alloc_file(self, alloc_id: str, rel: str):
        """Returns (data, truncated) from the owning client."""
        return self._client_for_alloc(alloc_id).read_alloc_file(
            alloc_id, rel
        )

    def purge_node(self, node_id: str) -> List[Evaluation]:
        """Remove a node from state entirely (reference
        node_endpoint.go Node.Deregister, PUT /v1/node/:id/purge);
        evals fan out for every job that had allocs there."""
        node = self.store.node_by_id(node_id)
        if node is None:
            raise KeyError(node_id)
        self._heartbeat_deadlines.pop(node_id, None)
        self._down_wave.pop(node_id, None)
        # delete first so the fanned-out evals schedule against a
        # state where the node is already gone
        self.store.delete_node(node_id)
        return self._create_node_evals(node_id)

    def deregister_job(
        self, namespace: str, job_id: str, purge: bool = False
    ) -> Optional[Evaluation]:
        job = self.store.job_by_id(namespace, job_id)
        if job is None:
            return None
        if purge:
            self.store.delete_job(namespace, job_id)
        else:
            job.stop = True
            self.store.upsert_job(job)
        self.blocked.untrack(namespace, job_id)
        ev = Evaluation(
            namespace=namespace,
            priority=job.priority,
            type=job.type,
            triggered_by=EVAL_TRIGGER_JOB_DEREGISTER,
            job_id=job_id,
            status=EVAL_STATUS_PENDING,
        )
        self.store.upsert_evals([ev])
        self.on_eval_update(ev)
        return ev

    def scale_job(
        self,
        namespace: str,
        job_id: str,
        group: str,
        count=None,
        message: str = "",
        error: bool = False,
        meta=None,
        policy_override: bool = False,
    ):
        """Scale one task group's count and record a scaling event
        (reference nomad/job_endpoint.go Job.Scale).  ``count=None``
        records the event without changing the job — the autoscaler's
        status-report path."""
        import copy

        from ..structs import ScalingEvent

        job = self.store.job_by_id(namespace, job_id)
        if job is None:
            raise KeyError(f"job {job_id!r} not found")
        # never mutate the store-resident object: it is also the
        # newest entry in the version history
        job = copy.deepcopy(job)
        tg = job.lookup_task_group(group)
        if tg is None:
            raise ValueError(f"unknown task group {group!r}")
        ev = None
        previous = tg.count
        if count is not None:
            count = int(count)
            pol = self.store.scaling_policy_by_target(
                namespace, job_id, group
            )
            if pol is not None and not policy_override:
                if count < pol.min:
                    raise ValueError(
                        f"group count {count} below scaling policy "
                        f"minimum {pol.min}"
                    )
                if pol.max and count > pol.max:
                    raise ValueError(
                        f"group count {count} above scaling policy "
                        f"maximum {pol.max}"
                    )
            tg.count = count
            ev = self.register_job(job)
        event = ScalingEvent(
            count=count,
            previous_count=previous,
            message=message,
            error=error,
            eval_id=ev.id if ev else None,
            meta=dict(meta or {}),
        )
        self.store.upsert_scaling_event(namespace, job_id, group, event)
        return ev, event

    def validate_job(self, job: Job) -> None:
        """Public validation surface (reference Job.Validate RPC
        backing /v1/validate/job)."""
        self._validate_job(job)

    def _validate_job(self, job: Job) -> None:
        if not job.id:
            raise ValueError("missing job ID")
        if not job.task_groups:
            raise ValueError("job requires at least one task group")
        names = set()
        for tg in job.task_groups:
            if tg.name in names:
                raise ValueError(f"duplicate task group {tg.name!r}")
            names.add(tg.name)
            if tg.count < 0:
                raise ValueError("task group count must be >= 0")
            if not tg.tasks and job.type != JOB_TYPE_CORE:
                raise ValueError(
                    f"task group {tg.name!r} requires at least one task"
                )
        if job.type not in ("service", "batch", "system"):
            raise ValueError(f"invalid job type {job.type!r}")
        if (
            job.namespace != "default"
            and self.store.namespace_by_name(job.namespace) is None
        ):
            raise ValueError(
                f"namespace {job.namespace!r} does not exist"
            )

    # -- node API (reference nomad/node_endpoint.go) --------------------

    def register_node(self, node: Node) -> None:
        first_seen = self.store.node_by_id(node.id) is None
        if node.status == "initializing":
            node.status = NODE_STATUS_READY
        self.store.upsert_node(node)
        self._emit_node_event(
            node.id,
            "Node registered" if first_seen else "Node re-registered",
        )
        self._reset_heartbeat(node.id)
        self.blocked.unblock(
            node.computed_class, self.store.latest_index()
        )
        self._create_node_evals(node.id)

    def heartbeat(self, node_id: str) -> None:
        """(reference nomad/heartbeat.go resetHeartbeatTimer)"""
        node = self.store.node_by_id(node_id)
        if node is None:
            raise KeyError(node_id)
        if node.status == NODE_STATUS_DOWN:
            self.update_node_status(node_id, NODE_STATUS_READY)
        self._reset_heartbeat(node_id)

    def _reset_heartbeat(self, node_id: str) -> None:
        # TTL deadlines are a leader-only service (reference
        # heartbeat.go runs on the leader; followers forward
        # Node.UpdateStatus)
        if not (self._running and self._leader_established):
            self._heartbeat_deadlines.pop(node_id, None)
            self._down_wave.pop(node_id, None)
            return
        self._heartbeat_deadlines[node_id] = (
            time.monotonic() + self.heartbeat_ttl
        )
        # a node heartbeating while its expiry sits in a gathering
        # down-wave was never dead: pull it back out before the wave
        # commits (zero false node-downs under mass-death gather)
        self._down_wave.pop(node_id, None)
        self._ensure_sweeper()

    def _ensure_sweeper(self) -> None:
        """(Re)spawn the heartbeat sweeper if it is missing or died.
        Called from every heartbeat reset AND from leadership
        establish — a crashed sweeper must never silently stop TTL
        enforcement for as long as traffic flows."""
        if not (self._running and self._leader_established):
            return
        with self._sweeper_lock:
            if self._heartbeat_sweeper is None or not (
                self._heartbeat_sweeper.is_alive()
            ):
                self._heartbeat_sweeper = threading.Thread(
                    target=self._sweep_heartbeats,
                    name="heartbeat-sweeper",
                    daemon=True,
                )
                self._heartbeat_sweeper.start()

    def _sweep_heartbeats(self) -> None:
        while self._running:
            interval = max(
                0.02, min(0.5, self.heartbeat_ttl / 5.0)
            )
            time.sleep(interval)
            if not self._leader_established:
                self._down_wave.clear()
                continue
            try:
                self._sweep_once(interval)
            except Exception:  # noqa: BLE001 — TTL enforcement must
                # survive any single sweep's failure; a dead sweeper
                # silently stops node-death detection cluster-wide
                LOG.exception("heartbeat sweep failed")

    def _sweep_once(self, interval: float) -> None:
        """One sweep: collect every TTL expiry, fold it into the
        pending down-wave, and commit the wave as ONE batched
        transition when it has settled (or immediately when it is
        below the mass-death gather threshold)."""
        now = time.monotonic()
        expired = [
            node_id
            for node_id, deadline in list(
                self._heartbeat_deadlines.items()
            )
            if deadline <= now
        ]
        for node_id in expired:
            current = self._heartbeat_deadlines.get(node_id)
            if current is None or current > now:
                continue  # heartbeated (refreshed) since the scan
            self._heartbeat_deadlines.pop(node_id, None)
            self._down_wave[node_id] = now
        if not self._down_wave:
            return
        stamps = list(self._down_wave.values())
        wave_started = min(stamps)
        last_new = max(stamps)
        if len(self._down_wave) >= self._wave_min:
            # correlated failure: settle until the expiry stream has
            # been quiet for two full seconds (heartbeat phases
            # spread a rack death across sweeps, and scheduler work
            # under overload stalls sweeps mid-stream — a short
            # settle fragments the wave, and a fragment whose jobs
            # overlap the first wave's outstanding evals trickles
            # through the per-job pending heaps into extra storm
            # solves), capped by the gather budget.
            settle_s = max(interval, min(2.0, self._wave_gather_s))
        else:
            # below the mass threshold: hold ONE extra sweep.  A
            # rack death's leading edge (the first sweep sees only a
            # couple of nodes, which may host dozens of jobs) must
            # merge into the mass wave behind it instead of
            # committing — and storming — on its own; a genuinely
            # single node death pays one sweep interval of extra
            # detection latency.
            settle_s = interval
        if (
            now - last_new < settle_s
            and now - wave_started < self._wave_gather_s
        ):
            return
        wave = list(self._down_wave.keys())
        self._down_wave.clear()
        self._heartbeats_expired(wave)

    def _heartbeats_expired(self, node_ids: List[str]) -> None:
        """Missed TTLs: the whole wave goes down in ONE batched state
        transition (one FSM apply — a 500-node rack death is one
        replicated command, not 500 serialized writes under the store
        lock), and its replan evals are enqueued as ONE storm family
        so the batch worker coalesces the replanning into a global
        assignment solve instead of per-eval chunk-chain walks
        (reference heartbeat.go:135 invalidateHeartbeat, batched)."""
        from ..trace import TRACE

        node_ids = [
            node_id
            for node_id in node_ids
            # a member whose deadline was RE-ARMED between the wave
            # snapshot and this commit heartbeated through the race
            # window — it was never dead, drop it (the last line of
            # the zero-false-node-downs defense; the mid-gather pop
            # in _reset_heartbeat covers the gather window, this
            # covers the snapshot->commit window)
            if node_id not in self._heartbeat_deadlines
            and (node := self.store.node_by_id(node_id)) is not None
            and node.status != NODE_STATUS_DOWN
        ]
        if not node_ids:
            return
        self.store.update_node_statuses(
            node_ids,
            NODE_STATUS_DOWN,
            message="Node heartbeat missed",
        )
        # one family hint per wave: replan evals across MANY unrelated
        # jobs still coalesce into one storm drain (job_family honors
        # the hint); single-node waves carry it too — harmless below
        # the storm trigger threshold
        wave_n = next(self._wave_counter)
        hint = f"node-down:w{wave_n}"
        evals = self._create_node_evals_batch(
            node_ids, family_hint=hint
        )
        self.metrics.incr("overload.node_down_waves")
        self.metrics.set_gauge(
            "overload.last_wave_nodes", float(len(node_ids))
        )
        # flight-recorder incident: one trace per down-wave, the
        # operator's handle for "which nodes, how many evals, which
        # storm family" after a mass death
        incident = f"node_down_wave:{wave_n}"
        TRACE.begin(
            incident,
            root_span="server.node_down_wave",
            nodes=len(node_ids),
            evals=len(evals),
            family=hint,
            sample_nodes=node_ids[:8],
        )
        TRACE.finish(incident, "recorded")

    def _emit_node_event(
        self, node_id: str, message: str, subsystem: str = "Cluster"
    ) -> None:
        """(reference node_endpoint.go emitting NodeEvents via
        UpsertNodeEventsType raft entries)"""
        from ..structs import NodeEvent

        try:
            self.store.upsert_node_events(
                node_id,
                [NodeEvent(message=message, subsystem=subsystem)],
            )
        except KeyError:
            pass

    def update_node_status(self, node_id: str, status: str) -> None:
        prev = self.store.node_by_id(node_id)
        prev_status = prev.status if prev is not None else ""
        self.store.update_node_status(node_id, status)
        if status != prev_status:
            self._emit_node_event(
                node_id,
                (
                    "Node heartbeat missed"
                    if status == NODE_STATUS_DOWN
                    else f"Node status changed to {status}"
                ),
            )
        node = self.store.node_by_id(node_id)
        if status == NODE_STATUS_READY:
            self._reset_heartbeat(node_id)
            self.blocked.unblock(
                node.computed_class, self.store.latest_index()
            )
        self._create_node_evals(node_id)

    def update_node_drain(
        self, node_id: str, drain: bool, strategy=None
    ) -> None:
        self.store.update_node_drain(node_id, drain, strategy)
        self._emit_node_event(
            node_id,
            "Node drain strategy set" if drain else "Node drain complete",
            subsystem="Drain",
        )
        self._create_node_evals(node_id)

    def update_node_eligibility(
        self, node_id: str, eligibility: str
    ) -> None:
        self.store.update_node_eligibility(node_id, eligibility)
        self._emit_node_event(
            node_id, f"Node marked {eligibility}", subsystem="Cluster"
        )
        node = self.store.node_by_id(node_id)
        if eligibility == "eligible":
            self.blocked.unblock(
                node.computed_class, self.store.latest_index()
            )

    def _create_node_evals(self, node_id: str) -> List[Evaluation]:
        """One eval per job with allocs on the node, plus system jobs
        (reference node_endpoint.go:1316 createNodeEvals)."""
        return self._create_node_evals_batch([node_id])

    def _create_node_evals_batch(
        self, node_ids: List[str], family_hint: str = ""
    ) -> List[Evaluation]:
        """The wave form of ``_create_node_evals``: ONE eval per
        affected (namespace, job) across the whole node wave — a
        500-node death whose allocs span 120 jobs creates 120 evals,
        not 500 x per-node fan-outs — persisted in one upsert and
        stamped with the wave's ``family_hint`` so the broker's
        storm detector sees them as one family."""
        evals = []
        seen_jobs = set()
        for node_id in node_ids:
            for alloc in self.store.allocs_by_node(node_id):
                key = (alloc.namespace, alloc.job_id)
                if key in seen_jobs:
                    continue
                seen_jobs.add(key)
                job = self.store.job_by_id(*key)
                sched_type = (
                    job.type if job is not None else JOB_TYPE_SERVICE
                )
                ev = Evaluation(
                    namespace=alloc.namespace,
                    priority=job.priority if job else 50,
                    type=sched_type,
                    triggered_by=EVAL_TRIGGER_NODE_UPDATE,
                    job_id=alloc.job_id,
                    node_id=node_id,
                    family_hint=family_hint,
                    status=EVAL_STATUS_PENDING,
                )
                evals.append(ev)
        # system jobs: ONE pass for the whole wave (seen_jobs dedups
        # to one eval per job anyway — scanning iter_jobs once per
        # node made a 500-node death O(nodes x jobs) store calls in
        # the sweeper's critical replan path); a job fires off the
        # first wave node matching its datacenters
        wave_nodes = [
            (node_id, node)
            for node_id in node_ids
            if (node := self.store.node_by_id(node_id)) is not None
        ]
        for job in self.store.iter_jobs():
            if job.type != "system" or job.stopped():
                continue
            key = (job.namespace, job.id)
            if key in seen_jobs:
                continue
            trigger = next(
                (
                    node_id
                    for node_id, node in wave_nodes
                    if not job.datacenters
                    or node.datacenter in job.datacenters
                ),
                None,
            )
            if trigger is None:
                continue
            seen_jobs.add(key)
            evals.append(
                Evaluation(
                    namespace=job.namespace,
                    priority=job.priority,
                    type="system",
                    triggered_by=EVAL_TRIGGER_NODE_UPDATE,
                    job_id=job.id,
                    node_id=trigger,
                    family_hint=family_hint,
                    status=EVAL_STATUS_PENDING,
                )
            )
        if evals:
            self.store.upsert_evals(evals)
            if family_hint:
                # the whole wave lands in ONE broker lock acquisition:
                # per-eval enqueues trickle the family in, and a GIL
                # hiccup mid-loop lets the storm detector's settle
                # beat cut the stream — fragmenting a 500-node death
                # into several solves
                self.broker.enqueue_all(
                    [ev for ev in evals if ev.should_enqueue()]
                )
                for ev in evals:
                    if not ev.should_enqueue():
                        self.on_eval_update(ev)
            else:
                for ev in evals:
                    self.on_eval_update(ev)
        return evals

    # -- client-side alloc updates (reference node_endpoint.go:1065) ----

    # -- job plan: dry-run an eval without committing
    # (reference nomad/job_endpoint.go Plan + scheduler/annotate.go) ----

    def plan_job(self, job: Job, diff: bool = True) -> Dict:
        """Run the scheduler against a snapshot with plan submission
        rejected, returning the would-be changes per task group."""
        from ..sched.generic_sched import BatchScheduler, ServiceScheduler
        from ..sched.system_sched import SystemScheduler
        from ..sched.testing import Harness
        from ..structs import EVAL_TRIGGER_JOB_REGISTER

        self._validate_job(job)
        # same admission hooks as register: the dry-run must predict
        # the job as it would actually be stored (connect sidecars
        # included), or `nomad plan` under-reports the placements
        self._inject_connect_sidecars(job)
        self._interpolate_multiregion(job)
        # run against a snapshot with the new job overlaid — the store
        # itself is never touched, so a replicated store can't diverge
        prev = self.store.job_by_id(job.namespace, job.id)
        recorder = _PlanRecorder(self.store)
        ev = Evaluation(
            namespace=job.namespace,
            priority=job.priority,
            type=job.type,
            triggered_by=EVAL_TRIGGER_JOB_REGISTER,
            job_id=job.id,
            annotate_plan=True,
            status=EVAL_STATUS_PENDING,
        )
        factory = {
            "service": ServiceScheduler,
            "batch": BatchScheduler,
            "system": SystemScheduler,
        }[job.type]
        if job.version == 0 and prev is not None:
            job.version = prev.version + 1
        snap = self.store.snapshot()
        snap.override_job(job)
        scheduler = factory(snap, recorder, seed=0)
        scheduler.process(ev)
        annotations = {}
        if recorder.plans and recorder.plans[-1].annotations:
            raw = recorder.plans[-1].annotations.get(
                "desired_tg_updates", {}
            )
            annotations = {
                tg: {
                    "Place": du.place,
                    "Stop": du.stop,
                    "Migrate": du.migrate,
                    "InPlaceUpdate": du.in_place_update,
                    "DestructiveUpdate": du.destructive_update,
                    "Canary": du.canary,
                    "Ignore": du.ignore,
                }
                for tg, du in raw.items()
            }
        from ..explain import alloc_metric_to_api

        failed = {}
        for e in recorder.evals:
            for tg, metric in (e.failed_tg_allocs or {}).items():
                # full Nomad API AllocMetric shape (ScoreMetaData is
                # top-K trimmed on this read)
                failed[tg] = alloc_metric_to_api(metric)
        return {
            "Annotations": annotations,
            "FailedTGAllocs": failed,
            "Diff": self._job_diff(prev, job) if diff else None,
        }

    @staticmethod
    def _job_diff(old: Optional[Job], new: Job) -> Dict:
        """Field-level diff summary (reference nomad/structs/diff.go,
        condensed to the fields the plan UX shows)."""
        if old is None:
            return {"Type": "Added"}
        changes = {}
        for attr in ("type", "priority", "datacenters"):
            a, b = getattr(old, attr), getattr(new, attr)
            if a != b:
                changes[attr] = {"Old": a, "New": b}
        old_groups = {tg.name: tg for tg in old.task_groups}
        new_groups = {tg.name: tg for tg in new.task_groups}
        group_changes = {}
        for name in old_groups.keys() | new_groups.keys():
            og, ng = old_groups.get(name), new_groups.get(name)
            if og is None:
                group_changes[name] = {"Type": "Added"}
            elif ng is None:
                group_changes[name] = {"Type": "Deleted"}
            elif og != ng:
                entry = {"Type": "Edited"}
                if og.count != ng.count:
                    entry["Count"] = {"Old": og.count, "New": ng.count}
                group_changes[name] = entry
        if group_changes:
            changes["TaskGroups"] = group_changes
        return {"Type": "Edited" if changes else "None", **changes}

    # -- parameterized jobs (reference nomad/job_endpoint.go Dispatch) --

    def dispatch_job(
        self,
        namespace: str,
        job_id: str,
        meta: Optional[Dict[str, str]] = None,
        payload: Optional[bytes] = None,
    ) -> Job:
        from dataclasses import replace as _replace

        parent = self.store.job_by_id(namespace, job_id)
        if parent is None:
            raise KeyError(job_id)
        if not parent.is_parameterized():
            raise ValueError(f"job {job_id!r} is not parameterized")
        spec = parent.parameterized or {}
        required = set(spec.get("meta_required", ()))
        optional = set(spec.get("meta_optional", ()))
        meta = dict(meta or {})
        missing = required - set(meta)
        if missing:
            raise ValueError(f"missing required meta: {sorted(missing)}")
        unexpected = set(meta) - required - optional
        if unexpected:
            raise ValueError(
                f"unpermitted meta keys: {sorted(unexpected)}"
            )
        if payload and spec.get("payload") == "forbidden":
            raise ValueError("payload is forbidden for this job")
        if not payload and spec.get("payload") == "required":
            raise ValueError("payload is required for this job")

        from ..structs import new_id

        child = _replace(parent)
        child.id = f"{parent.id}/dispatch-{new_id()[:8]}"
        child.name = child.id
        child.parent_id = parent.id
        child.parameterized = None
        child.meta = {**parent.meta, **meta}
        child.payload = bytes(payload or b"")
        self.register_job(child)
        return child

    # -- client registry for log/fs proxying (reference
    # nomad/client_rpc.go persistent connections) -----------------------

    def register_client(self, node_id: str, client) -> None:
        self._clients[node_id] = client

    def read_task_log(
        self, alloc_id: str, task: str, kind: str = "stdout",
        max_bytes: int = 64 * 1024,
    ) -> bytes:
        """(reference client fs/logs endpoints via server proxy)"""
        alloc = self.store.alloc_by_id(alloc_id)
        if alloc is None:
            raise KeyError(alloc_id)
        client = self._clients.get(alloc.node_id)
        if client is None:
            raise KeyError(f"no client connection for {alloc.node_id}")
        if hasattr(client, "read_task_log"):
            # remote client proxy: the files live on ITS disk
            return client.read_task_log(
                alloc_id, task, kind, max_bytes
            )
        import os

        # rotated logs first (client/logmon layout under alloc/logs/),
        # then the flat legacy path
        from ..client.logmon import read_task_log as _read_rotated

        log_dir = os.path.join(
            client.data_dir, "allocs", alloc_id, "alloc", "logs"
        )
        data = _read_rotated(log_dir, task, kind, max_bytes)
        if data:
            return data
        path = os.path.join(
            client.data_dir, "allocs", alloc_id, f"{task}.{kind}"
        )
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - max_bytes))
                return f.read()
        except OSError:
            return b""

    def update_allocs_from_client(self, updates: List[Allocation]) -> None:
        """Client pushes alloc status changes; terminal transitions free
        capacity and may trigger reschedule evals."""
        self.store.upsert_allocs(updates)
        evals = []
        seen = set()
        for alloc in updates:
            if not alloc.terminal_status():
                continue
            node = self.store.node_by_id(alloc.node_id)
            if node is not None:
                self.blocked.unblock(
                    node.computed_class, self.store.latest_index()
                )
            key = (alloc.namespace, alloc.job_id)
            if key in seen:
                continue
            job = self.store.job_by_id(*key)
            if job is None or job.stopped():
                continue
            if alloc.client_status == ALLOC_CLIENT_STATUS_FAILED:
                seen.add(key)
                evals.append(
                    Evaluation(
                        namespace=alloc.namespace,
                        priority=job.priority,
                        type=job.type,
                        triggered_by="alloc-failure",
                        job_id=alloc.job_id,
                        status=EVAL_STATUS_PENDING,
                    )
                )
        if evals:
            self.store.upsert_evals(evals)
            for ev in evals:
                self.on_eval_update(ev)

    # -- GC (reference nomad/core_sched.go; system gc endpoint) ----------

    def force_gc(self) -> None:
        from ..sched.core_sched import CORE_JOB_FORCE_GC
        from ..structs import JOB_TYPE_CORE

        ev = Evaluation(
            priority=100,
            type=JOB_TYPE_CORE,
            triggered_by="scheduled",
            job_id=CORE_JOB_FORCE_GC,
            status=EVAL_STATUS_PENDING,
        )
        self.store.upsert_evals([ev])
        self.on_eval_update(ev)

    # -- cluster observability (one server's share of a fan-in) ----------

    def _obs_local(self, what: str, params: dict) -> dict:
        """Serve this server's share of a cluster observability query
        (the `obs_query` RPC target, and the local half of every
        /v1/cluster/* merge).  Read-only and NOT leader-gated: every
        server's trace ring / metrics / history is its own."""
        from ..trace import TRACE

        if what == "traces":
            slow_ms = params.get("slow_ms")
            limit = int(params.get("limit", 64))
            return {
                "traces": TRACE.recent(
                    slow_ms=float(slow_ms)
                    if slow_ms is not None
                    else None,
                    outcome=params.get("outcome"),
                    limit=max(1, min(limit, 1024)),
                    full=bool(params.get("full")),
                )
            }
        if what == "trace":
            return {"trace": TRACE.get(params.get("ref", ""))}
        if what == "metrics":
            return {"metrics": self.metrics.dump()}
        if what == "metrics_history":
            return {"history": self.metrics_history.to_dict()}
        if what == "explain":
            from ..explain import EXPLAIN

            return {"explain": EXPLAIN.get(params.get("eval_id", ""))}
        if what == "slo":
            return {"slo": self.slo.status()}
        if what == "decisions":
            limit = int(params.get("limit", 64))
            return {
                "decisions": self.decisions.to_dict(
                    site=params.get("site"),
                    outcome=params.get("outcome"),
                    trace=params.get("trace"),
                    limit=max(1, min(limit, 1024)),
                )
            }
        raise ValueError(f"unknown obs query {what!r}")

    # -- helpers ---------------------------------------------------------

    def drain_to_idle(self, timeout: float = 10.0) -> bool:
        """Wait until no evals are in flight (test/bench helper)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if (
                self.broker.ready_count() == 0
                and self.broker.stats["total_unacked"] == 0
                and self.plan_queue.stats["depth"] == 0
            ):
                return True
            time.sleep(0.01)
        return False
