"""Replicated multi-server control plane (reference nomad/server.go +
nomad/leader.go + nomad/rpc.go forwarding).

Each ClusterServer owns a local StateStore applied to exclusively by the
raft FSM; the Server machinery on top sees a ReplicatedStore whose write
methods propose FSM commands through the raft log (reference
nomad/rpc.go:742 raftApply) and whose reads hit local state.  Leadership
changes from raft drive establishLeadership/revokeLeadership exactly as
the reference's monitorLeadership loop does (leader.go:54,222): the eval
broker, plan applier, scheduling workers, deployment watcher, drainer,
periodic dispatcher and heartbeat timers run only on the leader.

Writes issued on a follower forward to the leader transparently at the
store-write level (reference rpc.go:509 forward), so the HTTP/API layer
works unchanged on any server.
"""
from __future__ import annotations

import logging
import os
import pickle
import time
from typing import List, Optional

from ..acl import ACLStore, Token
from ..raft import InmemTransport, NotLeaderError, RaftNode
from ..raft.transport import TransportError
from ..state.store import StateStore
from ..structs import new_id
from ..trace import TRACE
from .fsm import ServerFSM, StaleLeadershipError, encode_command
from .membership import Gossip
from .server import Server

LOG = logging.getLogger(__name__)

_RAFT_METHODS = {"request_vote", "append_entries", "install_snapshot"}


def _forward_retries() -> int:
    """Bounded leader-forward retry budget (attempts AFTER the first);
    each retry rediscovers the leader, so a command survives the
    leadership moving mid-forward instead of being lost."""
    try:
        return max(0, int(os.environ.get("NOMAD_TPU_FORWARD_RETRIES", "4")))
    except ValueError:
        return 4


# initial leader-forward retry backoff; doubles per attempt (capped at
# 1s) so a leaderless interregnum is waited out, not hammered
FORWARD_BACKOFF_S = 0.05


def obs_fanin_timeout_s() -> float:
    """Whole-query budget for a /v1/cluster/* fan-in: peers not
    answered (or not even asked) inside it are marked `unreachable`
    in the merged result rather than failing the query."""
    try:
        return max(
            0.0,
            float(
                os.environ.get("NOMAD_TPU_OBS_FANIN_TIMEOUT_S", "2.0")
            ),
        )
    except ValueError:
        return 2.0


class ReplicatedStore:
    """StateStore facade: reads are local, writes go through raft.

    Mirrors the split in the reference where endpoint reads use the
    local memdb and writes call raftApply (e.g. node_endpoint.go,
    job_endpoint.go).
    """

    def __init__(
        self, local: StateStore, raft_apply, leader_gen=None
    ) -> None:
        self.local = local
        self._raft_apply = raft_apply
        # callable returning the proposer's current leadership
        # generation; stamped onto plan-result commands so the FSM's
        # replicated fence can reject a deposed leader's wave
        self._leader_gen = leader_gen

    def __getattr__(self, name):
        return getattr(self.local, name)

    # -- replicated write surface (FSM command per method) -------------

    def upsert_node(self, node):
        return self._raft_apply("upsert_node", (node,))

    def delete_node(self, node_id):
        return self._raft_apply("delete_node", (node_id,))

    def update_node_status(self, node_id, status, now=None):
        # timestamps are fixed by the proposer so every replica's FSM
        # applies the identical value
        return self._raft_apply(
            "update_node_status",
            (node_id, status, time.time() if now is None else now),
        )

    def update_node_statuses(
        self, node_ids, status, now=None, message=""
    ):
        # one FSM command for the whole down-node wave: a mass
        # node-death replicates as ONE log entry applied atomically
        # on every replica, not hundreds of raft round trips
        return self._raft_apply(
            "update_node_statuses",
            (
                list(node_ids),
                status,
                time.time() if now is None else now,
                message,
            ),
        )

    def update_node_eligibility(self, node_id, eligibility):
        return self._raft_apply(
            "update_node_eligibility", (node_id, eligibility)
        )

    def upsert_node_events(self, node_id, events):
        return self._raft_apply("upsert_node_events", (node_id, events))

    def update_node_drain(self, node_id, drain, strategy=None):
        return self._raft_apply(
            "update_node_drain", (node_id, drain, strategy)
        )

    def set_job_stability(self, namespace, job_id, version, stable):
        return self._raft_apply(
            "set_job_stability", (namespace, job_id, version, stable)
        )

    def upsert_job(self, job, keep_versions: int = 6):
        return self._raft_apply("upsert_job", (job, keep_versions))

    def delete_job(self, namespace, job_id):
        return self._raft_apply("delete_job", (namespace, job_id))

    def upsert_evals(self, evals, now=None):
        return self._raft_apply(
            "upsert_evals", (evals, time.time() if now is None else now)
        )

    def delete_eval(self, eval_id):
        return self._raft_apply("delete_eval", (eval_id,))

    def upsert_allocs(self, allocs):
        return self._raft_apply("upsert_allocs", (allocs,))

    def upsert_deployment(self, deployment):
        return self._raft_apply("upsert_deployment", (deployment,))

    def upsert_scaling_event(self, namespace, job_id, group, event):
        return self._raft_apply(
            "upsert_scaling_event", (namespace, job_id, group, event)
        )

    def upsert_csi_volume(self, volume):
        return self._raft_apply("upsert_csi_volume", (volume,))

    def upsert_namespace(self, ns):
        return self._raft_apply("upsert_namespace", (ns,))

    def reconcile_job_summaries(self):
        return self._raft_apply("reconcile_job_summaries", ())

    def delete_namespace(self, name):
        return self._raft_apply("delete_namespace", (name,))

    def deregister_csi_volume(self, namespace, volume_id, force=False):
        return self._raft_apply(
            "deregister_csi_volume", (namespace, volume_id, force)
        )

    def release_csi_claims_for_alloc(self, alloc_id):
        return self._raft_apply(
            "release_csi_claims_for_alloc", (alloc_id,)
        )

    def set_autopilot_config(self, config):
        return self._raft_apply("set_autopilot_config", (config,))

    def set_scheduler_config(self, config):
        return self._raft_apply("set_scheduler_config", (config,))

    def upsert_plan_results(self, result, eval_id, leader_gen=None):
        # stops/preemptions replicate as AllocationDiffs; every
        # replica's FSM denormalizes against its own state (reference
        # plan_apply.go:324 normalizePlan).  The command carries a
        # leadership generation: if a newer leader's barrier lands
        # first, every replica's FSM rejects this plan under the
        # apply (StaleLeadershipError) — the fence a deposed leader's
        # host-side checks alone could race past.  ``leader_gen`` is
        # the generation the PRODUCING wave captured when it started
        # (stamped on the Plan); falling back to the current
        # generation only for plans that carry no stamp — a straggler
        # wave must never be re-stamped with a newer generation it
        # did not run under.
        from .fsm import normalize_plan_result

        if leader_gen is None and self._leader_gen is not None:
            leader_gen = self._leader_gen()
        return self._raft_apply(
            "upsert_plan_results",
            (normalize_plan_result(result), eval_id, leader_gen),
        )


class ReplicatedACLStore:
    """ACL writes through raft; resolution against local state
    (reference: ACL tables live in the same raft FSM, fsm.go
    ACLPolicyUpsert/ACLTokenUpsert)."""

    def __init__(self, local: ACLStore, raft_apply) -> None:
        self.local = local
        self._raft_apply = raft_apply

    def __getattr__(self, name):
        return getattr(self.local, name)

    def bootstrap(self) -> Token:
        # generate on the caller, replicate the concrete token (token
        # IDs are random; the FSM must stay deterministic)
        token = Token(name="Bootstrap Token", type="management")
        return self._raft_apply("acl_bootstrap", (token,))

    def upsert_policy(self, policy):
        return self._raft_apply("acl_upsert_policy", (policy,))

    def delete_policy(self, name):
        return self._raft_apply("acl_delete_policy", (name,))

    def create_token(self, token):
        return self._raft_apply("acl_create_token", (token,))

    def delete_token(self, accessor_id):
        return self._raft_apply("acl_delete_token", (accessor_id,))


class ClusterServer(Server):
    """A Server participating in a raft-replicated cluster."""

    def __init__(
        self,
        addr: str,
        peers: List[str],
        transport: Optional[InmemTransport] = None,
        region: str = "global",
        election_timeout: float = 0.15,
        heartbeat_interval: float = 0.04,
        snapshot_threshold: int = 2048,
        acl_enabled: bool = False,
        **kwargs,
    ) -> None:
        self.addr = addr
        self.region = region
        self.transport = transport or InmemTransport()
        local_store = StateStore()
        local_acls = ACLStore(enabled=acl_enabled)
        self.fsm = ServerFSM(local_store, local_acls)
        self.raft = RaftNode(
            addr,
            peers,
            self.transport,
            self.fsm,
            election_timeout=election_timeout,
            heartbeat_interval=heartbeat_interval,
            snapshot_threshold=snapshot_threshold,
            on_leadership=self._on_leadership,
        )
        # the server machinery sees the replicated facades
        super().__init__(
            store=ReplicatedStore(
                local_store,
                self._raft_apply,
                leader_gen=lambda: self._leadership_gen,
            ),
            acls=ReplicatedACLStore(local_acls, self._raft_apply),
            acl_enabled=acl_enabled,
            **kwargs,
        )
        # gossip membership across servers and regions (reference
        # nomad/serf.go; WAN pool gives region federation its routes)
        self.gossip = Gossip(
            addr,
            addr,
            self.transport,
            region=region,
            on_event=self._on_member_event,
        )
        # take over the transport slot: raft RPCs pass through, plus a
        # leader-forwarding channel (reference nomad/rpc.go: one port,
        # multiplexed raft + RPC + serf)
        self.transport.register(addr, self._handle_cluster_rpc)
        # dead-server cleanup (reference nomad/autopilot.go)
        from .autopilot import Autopilot

        self.autopilot = Autopilot(self)
        # follower scheduling fan-out (NOMAD_TPU_FANOUT=1): while this
        # server is a follower, a monitor runs batch workers that
        # lease evals from the leader's broker over the transport,
        # plan on LOCAL replicated state + local device, and submit
        # plans into the leader's serialized plan queue
        from .fanout import FanoutManager

        self.fanout = FanoutManager(self, seed=kwargs.get("seed"))
        # the geo plane: per-server router resolving home regions,
        # forwarding region_call RPCs with bounded retry, fanning
        # Multiregion jobs out, and snapshotting gossip into the
        # region health table behind the shed-redirect hint
        from .federation import FederationRouter

        self.federation = FederationRouter(self)

    # -- raft plumbing --------------------------------------------------

    def _raft_apply(self, kind: str, args: tuple, cmd_id: str = None):
        """Propose a command; on a follower, forward to the leader with
        bounded retry (reference rpc.go:509 forward + rpc.go:742
        raftApply).  Leadership moving mid-forward used to LOSE the
        command (one shot at one hint); now each attempt rediscovers
        the leader and backs off, and the client-supplied cmd_id makes
        the retry idempotent — if the first forward actually committed
        before its ack was lost, the FSM dedup returns that apply's
        result instead of mutating twice.  Callers with their own
        idempotency scope (cross-region fan-out) pass an explicit
        cmd_id so even a WHOLE retried call dedups, not just one
        forward attempt."""
        data = encode_command(kind, args, cmd_id=cmd_id or new_id())
        backoff = FORWARD_BACKOFF_S
        retries = _forward_retries()
        last_exc: Exception = NotLeaderError(None)
        for attempt in range(retries + 1):
            if attempt:
                metrics = getattr(self, "metrics", None)
                if metrics is not None:
                    metrics.incr("raft.forward_retries")
                if backoff:
                    time.sleep(min(backoff * (2 ** (attempt - 1)), 1.0))
            leader = None
            try:
                return self.raft.apply(data)
            except StaleLeadershipError:
                raise  # replicated verdict: re-forwarding can't help
            except NotLeaderError as exc:
                leader = exc.leader or self.raft.leader_hint()
                if leader is None and isinstance(
                    last_exc, NotLeaderError
                ):
                    # a previous remote's hint beats no hint at all
                    leader = last_exc.leader
                last_exc = exc
            except TimeoutError as exc:
                # ambiguous: the entry may yet commit.  cmd_id dedup
                # makes the retry safe either way.
                last_exc = exc
                continue
            if leader is None:
                continue  # interregnum: back off and rediscover
            try:
                resp = self.transport.rpc(
                    self.addr, leader, "fsm_apply", {"data": data}
                )
            except (TransportError, TimeoutError) as exc:
                # TimeoutError: the remote's own apply timed out —
                # ambiguous like the local case, idempotent to retry
                last_exc = exc
                continue
            if resp.get("not_leader"):
                # the remote was deposed mid-forward; its hint (if
                # any) seeds the next rediscovery
                last_exc = NotLeaderError(resp.get("leader"))
                continue
            return pickle.loads(resp["result"])
        raise last_exc

    def _handle_cluster_rpc(self, method: str, payload: dict) -> dict:
        if method in _RAFT_METHODS:
            return self.raft._handle_rpc(method, payload)
        if method.startswith("gossip_"):
            return self.gossip.handle(method, payload)
        if method == "fsm_apply":
            # a just-deposed leader must answer with a structured
            # not-leader response (and its best hint), not a pickled
            # crash — the forwarding retry loop reads it and
            # rediscovers.  StaleLeadershipError stays an application
            # error: it is a replicated verdict, not a routing miss.
            try:
                result = self.raft.apply(payload["data"])
            except StaleLeadershipError:
                raise
            except NotLeaderError as exc:
                return {
                    "not_leader": True,
                    "leader": exc.leader or self.raft.leader_hint(),
                }
            return {"result": pickle.dumps(result)}
        if method == "broker_dequeue":
            return self._handle_broker_dequeue(payload)
        if method == "broker_drain_family":
            return self._handle_broker_drain_family(payload)
        if method in ("broker_ack", "broker_nack"):
            return self._handle_broker_settle(method, payload)
        if method == "submit_plan":
            return self._handle_submit_plan(payload)
        if method == "obs_query":
            # cluster observability fan-in: read-only, answered by
            # EVERY server (not leader-gated) — each server's trace
            # ring / metrics / history is its own
            return self._obs_local(
                payload["what"], payload.get("params") or {}
            )
        if method == "server_call":
            fn = getattr(self, payload["op"])
            args, kw = pickle.loads(payload["args"])
            return {"result": pickle.dumps(fn(*args, **kw))}
        if method == "region_call":
            return self._handle_region_call(payload)
        raise ValueError(f"unknown cluster rpc {method!r}")

    def _handle_region_call(self, payload: dict) -> dict:
        """The WAN half of forwardRegion (reference rpc.go:645): a
        request that entered through another region's servers lands
        here.  A pickled remote exception used to surface as a raw
        unpickle crash at the caller; every outcome is now a
        structured envelope — ``wrong_region`` (stale gossip routed
        to the wrong region; carries our actual region + leader
        hint), ``not_leader`` (interregnum; carries the hint),
        ``{error, kind}`` for unknown ops / timeouts / application
        errors — the same contract ``fsm_apply`` answers with, so
        the calling router can tell a retryable routing miss from a
        definitive verdict."""
        op = payload.get("op", "")
        want = payload.get("region")
        if want is not None and want != self.region:
            return {
                "wrong_region": True,
                "region": self.region,
                "leader": self.raft.leader_hint(),
                "error": (
                    f"server {self.addr} is in region "
                    f"{self.region!r}, not {want!r}"
                ),
                "kind": "wrong_region",
            }
        if op not in _REGION_API:
            return {
                "error": f"unknown region op {op!r}",
                "kind": "unknown_op",
            }
        try:
            args, kw = pickle.loads(payload["args"])
            result = self._leader_route(op, *args, **kw)
        except StaleLeadershipError:
            raise  # replicated verdict; the raft layer owns it
        except NotLeaderError as exc:
            return {
                "not_leader": True,
                "leader": exc.leader or self.raft.leader_hint(),
                "error": f"no leader in region {self.region!r}",
                "kind": "not_leader",
            }
        except (TimeoutError, TransportError) as exc:
            return {
                "error": str(exc) or type(exc).__name__,
                "kind": "timeout"
                if isinstance(exc, TimeoutError)
                else "transport",
            }
        except Exception as exc:  # noqa: BLE001 — envelope, not crash
            return {"error": str(exc), "kind": "app"}
        return {"result": pickle.dumps(result)}

    # -- follower fan-out RPC surface (leader side) ---------------------
    #
    # The remote half of the reference's worker/plan-queue split: any
    # server's scheduling workers lease evals from the LEADER's broker
    # and submit plans into the LEADER's serialized plan queue.  Every
    # lease-granting response is stamped with the leadership
    # generation it was issued under, so follower plans carry the
    # generation the replicated StaleLeadershipError fence judges
    # them by.

    def _fanout_not_leader(self) -> dict:
        return {"not_leader": True, "leader": self.raft.leader_hint()}

    def _fanout_serving(self) -> bool:
        return self._leader_established and self.is_leader()

    def _lease_response(self, leases) -> dict:
        """Package granted leases: pickled (the follower must get its
        OWN object graph, never aliases into our store), stamped with
        the current generation, with the ready backlog piggybacked
        for the follower's adaptive sizing."""
        gen = self._leadership_gen
        if leases and not self._leader_established:
            # revoked between the dequeue and this stamp: the broker
            # flush already unacked these tokens — hand back nothing
            # rather than leases that die on first ack
            for ev, token in leases:
                try:
                    self.broker.nack(ev.id, token)
                except ValueError:
                    pass
            return self._fanout_not_leader()
        if leases:
            self.metrics.incr(
                "fanout.remote_leases_granted", float(len(leases))
            )
            self.metrics.set_gauge(
                "fanout.remote_unacked",
                float(self.broker.remote_unacked_count()),
            )
        # distributed trace propagation: every lease ships the trace
        # context its broker-dequeue root was begun under (full trace
        # id — generation counters are per-process — plus the
        # wall-clock anchor), so the follower records its pipeline
        # spans into a segment under OUR trace id
        ctxs = {}
        for ev, _token in leases:
            ctx = TRACE.export_context(ev.id)
            if ctx is not None:
                ctxs[ev.id] = ctx
        return {
            "leases": pickle.dumps(list(leases)),
            "trace_ctx": ctxs,
            "gen": gen,
            "ready": self.broker.ready_count(),
            # the follower's apply fence: enqueued eval OBJECTS carry
            # modify_index=0 (the raft round trip stamps the FSM's
            # copy, not the enqueuer's), and the leader never noticed
            # because its own store has always applied everything it
            # proposed.  A remote planner has no such guarantee, so
            # every lease ships the leader's index AT GRANT TIME — an
            # upper bound on the eval's creating write, which is
            # certainly committed (the eval came out of the broker).
            # The client stamps it as the eval's snapshot_index and
            # the follower waits for local apply to reach it before
            # planning; without this a lagging follower reads the
            # eval's job as nonexistent and completes it as a no-op
            # deregister — a silently lost placement.
            "min_index": self.store.latest_index(),
        }

    def _handle_broker_dequeue(self, payload: dict) -> dict:
        if not self._fanout_serving():
            return self._fanout_not_leader()
        leases = self.broker.dequeue_remote(
            payload["schedulers"],
            timeout=min(1.0, float(payload.get("timeout", 0.0))),
            max_n=int(payload.get("n", 1)),
            peer=payload.get("server", "?"),
        )
        return self._lease_response(leases)

    def _handle_broker_drain_family(self, payload: dict) -> dict:
        if not self._fanout_serving():
            return self._fanout_not_leader()
        leases = self.broker.drain_family_remote(
            payload["schedulers"],
            tuple(payload["family"]),
            max_n=int(payload["max_n"]),
            min_n=int(payload.get("min_n", 1)),
            peer=payload.get("server", "?"),
        )
        return self._lease_response(leases)

    def _absorb_remote_segment(self, payload: dict) -> None:
        """Stitch a follower's shipped span segment into the local
        trace ring.  Runs BEFORE any leadership/token verdict on
        purpose: a segment straggling in from a reclaimed lease still
        documents work that happened, and trace-id routing lands it in
        the generation it ran under — never the redelivered attempt."""
        segment = payload.get("segment")
        if not segment:
            return
        absorbed = TRACE.absorb_segment(segment)
        self.metrics.incr("cluster.segments_absorbed")
        if absorbed:
            self.metrics.incr(
                "cluster.segment_spans", float(absorbed)
            )

    def _handle_broker_settle(self, method: str, payload: dict) -> dict:
        self._absorb_remote_segment(payload)
        if not self._fanout_serving():
            return self._fanout_not_leader()
        settle = (
            self.broker.ack
            if method == "broker_ack"
            else self.broker.nack
        )
        try:
            settle(payload["eval_id"], payload["token"])
        except ValueError:
            # token expired (nack-timeout redelivery beat the remote
            # worker) or died with a broker flush: structured, so the
            # follower raises its local ValueError instead of
            # unpickling a crash
            return {"error": "token"}
        self.metrics.set_gauge(
            "fanout.remote_unacked",
            float(self.broker.remote_unacked_count()),
        )
        return {}

    def _handle_submit_plan(self, payload: dict) -> dict:
        self._absorb_remote_segment(payload)
        if not self._leader_established:
            return self._fanout_not_leader()
        plan = pickle.loads(payload["plan"])
        try:
            pending = self.plan_queue.enqueue(plan)
            result = pending.wait(timeout=10.0)
        except StaleLeadershipError as exc:
            # replicated verdict — definitive, never re-forwarded
            return {"stale_leadership": (exc.gen, exc.fence)}
        except NotLeaderError as exc:
            return {
                "not_leader": True,
                "leader": exc.leader or self.raft.leader_hint(),
            }
        except TimeoutError:
            return {"timeout": True}
        if result is None:
            return {"rejected": True}
        self.metrics.incr("fanout.remote_plans")
        return {"result": pickle.dumps(result)}

    def broadcast_peer_removal(self, peer: str) -> bool:
        """Autopilot removal: commit the config change through the raft
        log so every member — including ones temporarily unreachable —
        converges on the same peer set when it applies the entry
        (reference applies raft.RemoveServer through the log).
        Returns whether the change committed."""
        try:
            self.raft.remove_server(peer)
            return True
        except (NotLeaderError, TimeoutError, TransportError):
            return False  # retried by the next autopilot pass

    def broadcast_peer_add(self, peer: str) -> bool:
        """Autopilot reconcile: commit the re-add through the raft log
        (reference leader.go addRaftPeer applies raft.AddVoter) so
        every member converges on the restored peer set.  Returns
        whether the change committed."""
        try:
            self.raft.add_server(peer)
            return True
        except (NotLeaderError, TimeoutError, TransportError):
            return False  # retried by the next autopilot pass

    # -- membership / federation ---------------------------------------

    def join(self, seed_addr: str) -> int:
        """Join the gossip pool via any known server (serf join)."""
        return self.gossip.join(seed_addr)

    def server_members(self):
        return self.gossip.member_list()

    # -- cluster observability fan-in -----------------------------------

    def cluster_query(self, what: str, params: Optional[dict] = None):
        """Fan a read-only observability query out to every known
        same-region server over the cluster transport and merge the
        answers.  Bounded by ``NOMAD_TPU_OBS_FANIN_TIMEOUT_S``:
        partial results are marked per-server ``unreachable`` rather
        than failing the whole query — a wedged peer must never make
        the CLUSTER unobservable.  Returns
        ``{"servers": {addr: result-or-{"unreachable": True}},
        "asked": n, "unreachable": k}``."""
        params = params or {}
        budget = obs_fanin_timeout_s()
        t0 = time.monotonic()
        servers: dict = {self.addr: self._obs_local(what, params)}
        unreachable = 0
        peers = [
            m
            for m in self.gossip.all_members()
            if m.addr != self.addr and m.region == self.region
            and m.status != "left"
        ]
        for member in peers:
            if time.monotonic() - t0 > budget:
                servers[member.addr] = {"unreachable": True}
                unreachable += 1
                continue
            try:
                servers[member.addr] = self.transport.rpc(
                    self.addr,
                    member.addr,
                    "obs_query",
                    {"what": what, "params": params},
                )
            except (TransportError, TimeoutError, ValueError):
                servers[member.addr] = {"unreachable": True}
                unreachable += 1
        self.metrics.incr("cluster.fanin_queries")
        if unreachable:
            self.metrics.incr(
                "cluster.fanin_unreachable", float(unreachable)
            )
        # per-eval queries mark the fan-in on the eval's own trace —
        # the waterfall shows when the operator came asking
        eval_ref = params.get("eval_id") or (
            params.get("ref", "").rsplit("#", 1)[0]
            if what == "trace"
            else ""
        )
        if eval_ref:
            TRACE.add_span(
                eval_ref,
                "cluster.fanin",
                t0,
                time.monotonic() - t0,
                what=what,
                servers=len(servers),
                unreachable=unreachable,
            )
        return {
            "servers": servers,
            "asked": len(servers),
            "unreachable": unreachable,
        }

    def _on_member_event(self, kind: str, member) -> None:
        # (reference serf.go nodeJoin/nodeFailed -> reconcile); raft
        # peers are static config here, so membership drives routing
        # tables and the agent members view only
        if hasattr(self, "metrics"):
            self.metrics.incr(f"serf.{kind}")

    def forward_region(self, region: str, op: str, *args, **kw):
        """Route an API call to a server in another region (reference
        rpc.go:645 forwardRegion).  Thin compat shim over the
        federation router, which owns retry/backoff and envelope
        interpretation."""
        return self.federation.forward(region, op, *args, **kw)

    def advertise_http(self, http_addr: str) -> None:
        """Record this server's HTTP advertise address into its gossip
        Member record (and rumor it), so every region learns where to
        send redirected HTTP traffic — the retry-region shed hint is
        built from these."""
        self.gossip.advertise_http(http_addr)

    def federated_register(self, job, fed_cmd_id: str):
        """Target-region half of cross-region job fan-out: specialize
        the fanned jobspec for THIS region (per-region count /
        datacenters / meta overrides from its MultiregionRegion
        entry), then propose job+eval as ONE FSM command under the
        fan-out's per-region command id.  A retried fan-out (lost
        ack, coordinator leadership moved) re-proposes the same id
        and dedups in the FSM; the eval id is derived from the same
        id, so the broker's eval-id dedup absorbs the re-enqueue too
        — a retried fan-out can never double-register or
        double-schedule."""
        import hashlib

        job.region = self.region
        self._validate_job(job)
        self._inject_connect_sidecars(job)
        self._interpolate_multiregion(job)
        from ..structs import (
            EVAL_STATUS_PENDING,
            EVAL_TRIGGER_JOB_REGISTER,
            Evaluation,
        )

        if job.periodic is not None or job.parameterized is not None:
            self._raft_apply(
                "upsert_job", (job, 6), cmd_id=fed_cmd_id
            )
            return None
        ev = Evaluation(
            id=hashlib.sha256(
                f"fed-eval:{fed_cmd_id}".encode()
            ).hexdigest()[:32],
            namespace=job.namespace,
            priority=job.priority,
            type=job.type,
            triggered_by=EVAL_TRIGGER_JOB_REGISTER,
            job_id=job.id,
            status=EVAL_STATUS_PENDING,
        )
        applied = self._raft_apply(
            "register_job_federated",
            (job, ev, time.time()),
            cmd_id=fed_cmd_id,
        )
        self.on_eval_update(applied if applied is not None else ev)
        return applied

    def federation_job_status(self, namespace: str, job_id: str):
        """This region's registration/placement summary for one job —
        the per-region leaf the /v1/job/<id>/federation aggregation
        collects."""
        job = self.store.job_by_id(namespace, job_id)
        if job is None:
            return {"registered": False, "region": self.region}
        evals = self.store.evals_by_job(namespace, job_id)
        statuses: dict = {}
        for ev in evals:
            statuses[ev.status] = statuses.get(ev.status, 0) + 1
        return {
            "registered": True,
            "region": self.region,
            "version": job.version,
            "groups": {tg.name: tg.count for tg in job.task_groups},
            "evals": statuses,
            "allocs": len(
                self.store.allocs_by_job(namespace, job_id)
            ),
        }

    def cluster_query_region(
        self,
        what: str,
        params: Optional[dict] = None,
        region: Optional[str] = None,
    ):
        """Observability fan-in with the region boundary enforced:
        no region (or our own) answers from the LOCAL region's
        servers only — reads never cross the WAN implicitly.  An
        explicit foreign region is the ?region= escape hatch: the
        query forwards to that region's leader and counts against
        ``federation.wan_reads`` (asserted zero for region-local
        traffic in the geo harness)."""
        if region is None or region == self.region:
            return self.cluster_query(what, params)
        self.metrics.incr("federation.wan_reads")
        return self.federation.forward(
            region, "cluster_query", what, params
        )

    def remote_call(self, op: str, *args, **kw):
        """Invoke a Server API method on the current leader
        (reference: endpoint forwarding for non-store operations)."""
        return self._leader_route(op, *args, **kw)

    def _leader_route(self, op: str, *args, **kw):
        """Run a Server API method on the leader (reference
        rpc.go:509 forward): locally when we are the leader, otherwise
        over the transport.  Ops resolve on the Server base first —
        cluster-level ops (federation, observability) are real
        ClusterServer methods, never forwarders, so falling back to
        the subclass cannot recurse."""
        if self.is_leader():
            fn = getattr(Server, op, None)
            if fn is None:
                fn = getattr(type(self), op)
            return fn(self, *args, **kw)
        leader = self.raft.leader_hint()
        if leader is None:
            raise NotLeaderError(None)
        resp = self.transport.rpc(
            self.addr, leader, "server_call",
            {"op": op, "args": pickle.dumps((args, kw))},
        )
        return pickle.loads(resp["result"])

    def on_eval_update(self, ev) -> None:
        """Eval routing happens on the leader only (reference
        fsm.go:715); a restarted/late leader recovers anything missed
        via restore_evals."""
        if self.is_leader():
            super().on_eval_update(ev)
        else:
            try:
                self._leader_route("route_eval", ev.id)
            except (NotLeaderError, TransportError):
                pass  # next election's restore_evals picks it up

    def is_leader(self) -> bool:
        return self.raft.is_leader()

    def _on_leadership(self, is_leader: bool, term: int) -> None:
        if not is_leader:
            # park the full leader-only stack: broker (unacking every
            # outstanding token, drain_family members included), plan
            # queue/applier (in-flight plans respond NotLeaderError),
            # workers (the leadership fence aborts open chunk chains
            # and mid-settle storm gulps), watchers, heartbeat timers
            self.revoke_leadership()
            return
        # make sure every committed entry is applied locally before the
        # leader services read state (reference leader.go
        # establishLeadership barrier); retry while we hold leadership —
        # giving up would leave an elected leader with its services off
        while self._running and self.raft.is_leader():
            try:
                self.raft.barrier(timeout=5.0)
                # move the REPLICATED leadership fence to this term
                # before any service starts: from here on, every
                # replica's FSM rejects plan commands stamped by an
                # older generation, however they arrive (raft apply on
                # a zombie leader, or forwarded to us)
                self._raft_apply("leadership_barrier", (term,))
            except (TimeoutError, TransportError):
                continue
            except NotLeaderError:
                return
            # re-check AFTER the barrier: _raft_apply's forwarding
            # retries mean a barrier proposed by a just-deposed
            # leader can still "succeed" (forwarded to the new
            # leader, where max(fence, term) is a no-op) — without
            # this check the deposed server would establish anyway
            # and duplicate-schedule the backlog until its queued
            # revoke notification lands
            stats = self.raft.stats()
            if stats["state"] != "leader" or stats["term"] != term:
                return
            # the broker restore inside establish_leadership reads the
            # replicated state AT OUR COMMIT INDEX (the barrier just
            # flushed the apply pipeline), so no committed eval is
            # missed and none is invented
            self.establish_leadership(gen=term)
            return

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        self._running = True
        # metric history runs on every server, leader or follower —
        # fan-in queries merge the whole cluster's rings
        self.metrics_history.start()
        self.gossip.start()
        self.raft.start()
        self.autopilot.start()
        # follower fan-out workers start/stop with this server's raft
        # role (no-op unless NOMAD_TPU_FANOUT=1)
        self.fanout.start()
        # geo router: snapshots gossip into the region health table
        self.federation.start()

    def stop(self) -> None:
        self._running = False
        # fan-out first: its workers RPC over the transport this stop
        # is about to quiesce; same for the federation router
        self.federation.stop()
        self.fanout.stop()
        self.autopilot.stop()
        self.raft.stop()
        self.metrics_history.stop()
        # graceful departure: broadcast LEFT so peers don't gossip a
        # failure (serf Leave vs. a detected member-failed)
        self.gossip.leave()
        self.revoke_leadership()
        self._heartbeat_deadlines.clear()
        # see Server.stop: a still-open overload incident settles as
        # `shed` rather than dangling in flight forever
        self.overload.close_incident()
        self.log_monitor.uninstall("nomad_tpu")


# Public Server API methods that must execute on the leader — their
# side effects (eval routing into the broker, heartbeat TTL timers,
# blocked-eval unblocking) only exist there.  Calling any of these on a
# follower transparently forwards, so the HTTP/API layer genuinely
# works unchanged on any server (reference rpc.go:509 forward).
_LEADER_API = (
    "register_job",
    "deregister_job",
    "dispatch_job",
    "plan_job",
    "register_node",
    "heartbeat",
    "update_node_status",
    "update_node_drain",
    "update_node_eligibility",
    "update_allocs_from_client",
    "force_gc",
    "route_eval",
    "scale_job",
    "revert_job",
    "stop_alloc",
    "purge_node",
)


def _make_forwarder(op):
    def method(self, *args, **kw):
        return self._leader_route(op, *args, **kw)

    method.__name__ = op
    method.__qualname__ = f"ClusterServer.{op}"
    method.__doc__ = f"Leader-forwarded Server.{op} (rpc.go:509 forward)."
    return method


for _op in _LEADER_API:
    setattr(ClusterServer, _op, _make_forwarder(_op))

# The op surface a region_call may invoke: the leader-forwarded Server
# API plus the cluster-level federation/observability ops.  Anything
# else answers a structured unknown_op envelope — the WAN boundary is
# not a generic RPC into arbitrary attributes.
_REGION_API = frozenset(_LEADER_API) | {
    "federated_register",
    "federation_job_status",
    "cluster_query",
    "fanout_multiregion",
}


def _register_job_federated(self, job):
    """Jobs carry a region (structs.Job.Region); a submission landing
    in the wrong region hops to the right one first (reference
    job_endpoint.go forwarding via rpc.go:645), with the federation
    router owning the retry/backoff and envelope handling.  A job
    that never named a region (the struct default) resolves to the
    receiving server's region, as the reference agent does, unless
    the default region actually exists in the federation.  A job
    carrying a Multiregion block goes to its home region's leader
    and fans out from there."""
    region = self.federation.home_region(job)
    if job.multiregion is not None and job.multiregion.regions:
        if not region or region == self.region:
            ev, _statuses = self._leader_route(
                "fanout_multiregion", job
            )
            return ev
        ev, _statuses = self.federation.forward(
            region, "fanout_multiregion", job
        )
        return ev
    if not region or region == self.region:
        job.region = self.region
        return self._leader_route("register_job", job)
    return self.federation.forward(region, "register_job", job)


def _fanout_multiregion(self, job):
    """Home-region coordinator entry for a Multiregion jobspec: runs
    on the home region's leader, fans per-region registrations out
    through the router (idempotent per-region cmd ids)."""
    return self.federation.fanout_job(job)


ClusterServer.register_job = _register_job_federated
ClusterServer.fanout_multiregion = _fanout_multiregion


class TestCluster:
    """Boots N in-process ClusterServers on a shared transport — the
    shape of the reference's nomad.TestServer + TestJoin clusters
    (nomad/testing.go:44)."""

    __test__ = False  # not a pytest class despite the name

    def __init__(
        self,
        n: int = 3,
        transport: Optional[InmemTransport] = None,
        region: str = "global",
        name_prefix: str = "server",
        **server_kwargs,
    ) -> None:
        self.transport = transport or InmemTransport()
        addrs = [f"{name_prefix}-{i}" for i in range(n)]
        self.servers = [
            ClusterServer(
                addr, addrs, self.transport, region=region,
                **server_kwargs,
            )
            for addr in addrs
        ]

    def start(self) -> None:
        for s in self.servers:
            s.start()
        # gossip-join everyone through the first server (TestJoin)
        seed = self.servers[0]
        for s in self.servers[1:]:
            s.join(seed.addr)

    def stop(self) -> None:
        for s in self.servers:
            s.stop()

    def wait_for_leader(self, timeout: float = 5.0) -> ClusterServer:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            leaders = [s for s in self.servers if s.is_leader()]
            if len(leaders) == 1 and leaders[0]._leader_established:
                return leaders[0]
            time.sleep(0.02)
        raise AssertionError("no established leader")

    def followers(self) -> List[ClusterServer]:
        return [s for s in self.servers if not s.is_leader()]
