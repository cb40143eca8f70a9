"""Replicated state-machine command layer (reference nomad/fsm.go).

Every control-plane write is a typed command applied through this
dispatch — when the server runs replicated, commands arrive through the
raft log and every server applies the same stream to its local
StateStore/ACLStore (reference fsm.go:180 Apply over ~40 MessageTypes);
in single-process mode the Server applies them directly.  Commands are
pickled (kind, args) tuples: self-describing like the reference's
msgpack-encoded requests, and the round-trip gives each replica its own
object graph (no cross-server aliasing).

Eval routing (broker enqueue on EvalUpdate, fsm.go:715) deliberately
stays OUT of the FSM here: the API layer routes evals on the leader
after the apply returns, and a newly-elected leader recovers pending
evals from state via restore_evals (reference leader.go:352) — same
at-least-once outcome without followers needing a broker.
"""
from __future__ import annotations

import gzip
import pickle
from collections import OrderedDict
from typing import Optional, Tuple

from ..raft import NotLeaderError
from ..state.store import StateStore
from ..trace import TRACE

SNAPSHOT_VERSION = 1

# applied command ids retained for at-least-once forward dedup; far
# above any plausible in-flight retry window, bounded so the FSM's
# memory stays O(1) under sustained traffic
CMD_DEDUP_MAX = 8192


class StaleLeadershipError(NotLeaderError):
    """A command stamped by a deposed leadership generation reached the
    FSM after a newer leader's barrier committed.  Subclasses
    NotLeaderError so the worker layer's nack-for-redelivery handling
    covers it, but it is DEFINITIVE: the forwarding retry loop must
    propagate it, never re-forward (the rejection is replicated — every
    FSM applies the same verdict)."""

    def __init__(self, gen: int, fence: int) -> None:
        Exception.__init__(
            self,
            f"command from deposed leadership gen {gen} "
            f"(fence is gen {fence})",
        )
        self.leader = None
        self.gen = gen
        self.fence = fence


def encode_command(
    kind: str, args: tuple, cmd_id: Optional[str] = None
) -> bytes:
    """Commands travel as (kind, args, cmd_id) — cmd_id is the
    client-supplied idempotency key: a forward retry after a lost ack
    re-proposes the SAME id, and the FSM's dedup table returns the
    first apply's result instead of mutating twice."""
    return pickle.dumps(
        (kind, args, cmd_id), protocol=pickle.HIGHEST_PROTOCOL
    )


def normalize_plan_result(result):
    """Wire-efficient form of a PlanResult: stopped/preempted allocs
    shrink to AllocationDiffs — an id plus the mutated status fields —
    instead of full Job-bearing Allocation graphs (reference
    plan_apply.go:324-344 normalizePlan + Plan.NormalizeAllocations).
    Placements stay whole: they carry state replicas don't have yet."""
    from ..structs import AllocationDiff, PlanResult

    if result.normalized:
        return result

    def diffs(allocs):
        return [
            AllocationDiff(
                id=a.id,
                desired_status=a.desired_status,
                desired_description=a.desired_description,
                client_status=a.client_status,
                followup_eval_id=a.followup_eval_id,
                preempted_by_allocation=a.preempted_by_allocation,
            )
            for a in allocs
        ]

    return PlanResult(
        node_update={
            nid: diffs(allocs)
            for nid, allocs in result.node_update.items()
        },
        node_allocation=result.node_allocation,
        node_preemptions={
            nid: diffs(allocs)
            for nid, allocs in result.node_preemptions.items()
        },
        deployment=result.deployment,
        deployment_updates=result.deployment_updates,
        refresh_index=result.refresh_index,
        alloc_index=result.alloc_index,
        normalized=True,
    )


def denormalize_plan_result(store: StateStore, result):
    """Reconstitute full stop/preemption allocs from AllocationDiffs
    against the replica's own state (reference fsm.go ApplyPlanResults
    -> state DenormalizeAllocationSlice).  Diffs whose alloc no longer
    exists are dropped — the stop already won."""
    from dataclasses import replace

    from ..structs import PlanResult

    if not result.normalized:
        return result

    def expand(diff_lists):
        out = {}
        for nid, diff_list in diff_lists.items():
            allocs = []
            for d in diff_list:
                existing = store.alloc_by_id(d.id)
                if existing is None:
                    continue
                alloc = replace(existing)
                alloc.desired_status = d.desired_status
                alloc.desired_description = d.desired_description
                if d.client_status:
                    alloc.client_status = d.client_status
                if d.followup_eval_id:
                    alloc.followup_eval_id = d.followup_eval_id
                if d.preempted_by_allocation:
                    alloc.preempted_by_allocation = (
                        d.preempted_by_allocation
                    )
                allocs.append(alloc)
            if allocs:
                out[nid] = allocs
        return out

    return PlanResult(
        node_update=expand(result.node_update),
        node_allocation=result.node_allocation,
        node_preemptions=expand(result.node_preemptions),
        deployment=result.deployment,
        deployment_updates=result.deployment_updates,
        refresh_index=result.refresh_index,
        alloc_index=result.alloc_index,
        normalized=False,
    )


def decode_command(
    raw: bytes,
) -> Tuple[str, tuple, Optional[str]]:
    """(kind, args, cmd_id) of a command; tolerant of the pre-cmd-id
    2-tuple wire form (cmd_id None) so mixed-version logs still
    apply."""
    loaded = pickle.loads(raw)
    return loaded[0], loaded[1], (
        loaded[2] if len(loaded) > 2 else None
    )


def state_payload(store: StateStore, acls) -> dict:
    """Capture the full replicated state (reference fsm.go Snapshot:
    every table is persisted)."""
    with store._lock:
        payload = {
            "version": SNAPSHOT_VERSION,
            "index": store.latest_index(),
            "table_indexes": dict(store._table_index),
            "nodes": list(store.nodes.values()),
            "jobs": list(store.jobs.values()),
            "job_versions": {
                k: list(v) for k, v in store.job_versions.items()
            },
            "allocs": list(store.allocs.values()),
            "evals": list(store.evals.values()),
            "deployments": list(store.deployments.values()),
            "scheduler_config": store.scheduler_config,
            "autopilot_config": store.autopilot_config,
            "csi_volumes": list(store.csi_volumes.values()),
            "namespaces": list(store.namespaces.values()),
            "scaling_policies": list(store.scaling_policies.values()),
            "scaling_events": {
                k: {g: list(evs) for g, evs in v.items()}
                for k, v in store.scaling_events.items()
            },
        }
        # bigworld allocation ballast (array-backed seeded usage) is
        # replicated state: persist it keyed by node id so restore can
        # re-row it against the rebuilt node table
        if store._seed_usage is not None:
            base = store._seed_usage
            nz = (base[0] + base[1] + base[2]).nonzero()[0]
            ids = store.node_table.node_ids
            payload["seed_usage"] = {
                ids[row]: (
                    float(base[0][row]),
                    float(base[1][row]),
                    float(base[2][row]),
                )
                for row in nz.tolist()
                if ids[row] is not None
            }
            payload["seed_alloc_count"] = store._seed_alloc_count
    if acls is not None:
        payload["acl_policies"] = list(acls.policies.values())
        payload["acl_tokens"] = list(acls.tokens_by_accessor.values())
        payload["acl_enabled"] = acls.enabled
    return payload


def install_payload(store: StateStore, acls, payload: dict) -> int:
    """Replace local state with a snapshot payload (reference fsm.go
    Restore).  Secondary indexes and the columnar node table are
    derived state and get rebuilt."""
    if payload.get("version") != SNAPSHOT_VERSION:
        raise ValueError(
            f"unsupported snapshot version {payload.get('version')}"
        )
    from ..state.node_table import NodeTable

    with store._lock:
        store.nodes.clear()
        store.jobs.clear()
        store.job_versions.clear()
        store.allocs.clear()
        store.evals.clear()
        store.deployments.clear()
        store._allocs_by_node.clear()
        store._allocs_by_job.clear()
        store._allocs_by_eval.clear()
        store._evals_by_job.clear()
        store._deployments_by_job.clear()
        # the columnar mirror is derived state: rebuild it from scratch
        # so rows/usage from pre-snapshot nodes can't survive
        store.node_table = NodeTable()

        for node in payload["nodes"]:
            store.nodes[node.id] = node
            store.node_table.upsert_node(node)
        for job in payload["jobs"]:
            store.jobs[(job.namespace, job.id)] = job
        for key, versions in payload["job_versions"].items():
            store.job_versions[key] = versions
        for alloc in payload["allocs"]:
            store.allocs[alloc.id] = alloc
            store._allocs_by_node[alloc.node_id].add(alloc.id)
            store._allocs_by_job[(alloc.namespace, alloc.job_id)].add(
                alloc.id
            )
            if alloc.eval_id:
                store._allocs_by_eval[alloc.eval_id].add(alloc.id)
        # recompute usage for every node (not just those with allocs in
        # the snapshot — a node whose allocs all stopped must read zero)
        # The port/device occupancy indexes and the per-node live
        # aggregate are derived state too: clear the pre-restore entries
        # (phantom static-port occupancy would skew the batch kernel's
        # port_used0 columns) and rebuild them — _recount_node also
        # repopulates node_table.device_used from the restored live
        # allocs.
        store._ports_live.clear()
        store._ports_by_node.clear()
        store._node_live.clear()
        # re-row the seeded allocation ballast BEFORE the usage
        # recompute below — _recount_node reads it per node
        seed_usage = payload.get("seed_usage")
        if seed_usage:
            import numpy as np

            cap = store.node_table.capacity
            base = [np.zeros(cap, dtype=np.float64) for _ in range(3)]
            for nid, (c, m, d) in seed_usage.items():
                row = store.node_table.row_of.get(nid)
                if row is None:
                    continue
                base[0][row] = c
                base[1][row] = m
                base[2][row] = d
            store._seed_usage = base
            store._seed_alloc_count = payload.get(
                "seed_alloc_count", 0
            )
        else:
            store._seed_usage = None
            store._seed_alloc_count = 0
        # an alloc whose node the snapshot lacks is counted too: the
        # aggregate has to be right when that node registers again
        for node_id in store.nodes.keys() | store._allocs_by_node.keys():
            store._recount_node(node_id)
        for ev in payload["evals"]:
            store.evals[ev.id] = ev
            store._evals_by_job[(ev.namespace, ev.job_id)].add(ev.id)
        for d in payload["deployments"]:
            store.deployments[d.id] = d
            store._deployments_by_job[(d.namespace, d.job_id)].add(d.id)
        store.scheduler_config = payload["scheduler_config"]
        store.autopilot_config = payload.get("autopilot_config")
        store.csi_volumes.clear()
        for vol in payload.get("csi_volumes", ()):
            store.csi_volumes[(vol.namespace, vol.id)] = vol
        store.namespaces.clear()
        for ns in payload.get("namespaces", ()):
            store.namespaces[ns.name] = ns
        if "default" not in store.namespaces:
            from ..structs import Namespace

            store.namespaces["default"] = Namespace(
                name="default",
                description="Default shared namespace",
            )
        store.scaling_policies.clear()
        store._scaling_by_target.clear()
        store.scaling_events.clear()
        for pol in payload.get("scaling_policies", ()):
            store.scaling_policies[pol.id] = pol
            store._scaling_by_target[pol.target_tuple()] = pol.id
        for key, per_group in payload.get("scaling_events", {}).items():
            store.scaling_events[key] = {
                g: list(evs) for g, evs in per_group.items()
            }
        store._index = payload["index"]
        store._table_index.clear()
        store._table_index.update(payload.get("table_indexes", {}))
        store._watch_cond.notify_all()
        # delta-level consumers (service catalog) must resync: the
        # restore wrote the alloc table wholesale without per-alloc
        # notifications
        store._notify_alloc_watchers(None)

    if acls is not None and "acl_enabled" in payload:
        acls.enabled = payload["acl_enabled"]
        acls.policies.clear()
        acls.tokens_by_accessor.clear()
        acls.tokens_by_secret.clear()
        for policy in payload.get("acl_policies", ()):
            acls.upsert_policy(policy)
        for token in payload.get("acl_tokens", ()):
            acls.tokens_by_accessor[token.accessor_id] = token
            acls.tokens_by_secret[token.secret_id] = token
    return payload["index"]


class ServerFSM:
    """Applies committed commands to the local store (the raft FSM).

    Pure state mutation, deterministic from the command stream — every
    replica that applies the same log prefix holds identical state and
    identical modify indexes.
    """

    def __init__(self, store: StateStore, acls=None) -> None:
        self.store = store
        self.acls = acls
        # committed leadership fence: the newest leadership generation
        # whose barrier command reached this FSM.  Checked UNDER the
        # apply (not host-side) so a deposed leader's in-flight plan —
        # even one forwarded to the new leader — is rejected by every
        # replica deterministically.
        self.leadership_fence = 0
        # cmd_id -> result of successfully applied commands (forward
        # retries re-propose the same id; the dup returns the cached
        # result without mutating state).  Part of the snapshot so a
        # compaction can't resurrect a dup on one replica only.
        self._applied_cmds: "OrderedDict[str, object]" = OrderedDict()

    # raft FSM contract -------------------------------------------------

    def apply(self, raw: bytes):
        kind, args, cmd_id = decode_command(raw)
        if cmd_id is not None and cmd_id in self._applied_cmds:
            # at-least-once forward dedup: the first apply's result,
            # no second mutation.  Failures are NOT cached — handlers
            # are deterministic functions of state, so a re-applied
            # failed command fails identically on every replica.
            return self._applied_cmds[cmd_id]
        result = self.dispatch(kind, args)
        if cmd_id is not None:
            self._applied_cmds[cmd_id] = result
            while len(self._applied_cmds) > CMD_DEDUP_MAX:
                self._applied_cmds.popitem(last=False)
        return result

    def snapshot(self) -> bytes:
        payload = state_payload(self.store, self.acls)
        payload["leadership_fence"] = self.leadership_fence
        payload["cmd_dedup"] = list(self._applied_cmds.items())
        return gzip.compress(
            pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
        )

    def restore(self, raw: bytes) -> None:
        payload = pickle.loads(gzip.decompress(raw))
        install_payload(self.store, self.acls, payload)
        self.leadership_fence = payload.get("leadership_fence", 0)
        self._applied_cmds = OrderedDict(payload.get("cmd_dedup", ()))

    # command dispatch (reference fsm.go:197-277) -----------------------

    def dispatch(self, kind: str, args: tuple):
        handler = getattr(self, f"_apply_{kind}", None)
        if handler is None:
            raise ValueError(f"unknown FSM command {kind!r}")
        return handler(*args)

    def _apply_upsert_node(self, node):
        return self.store.upsert_node(node)

    def _apply_seed_world(self, spec):
        """Deterministic synthetic-world expansion (bigworld): the log
        carries the tiny spec, every replica expands it to the same
        bulk-registered nodes + allocation ballast locally."""
        from ..loadgen.bigworld import seed_world

        return seed_world(self.store, spec)

    def _apply_delete_node(self, node_id):
        return self.store.delete_node(node_id)

    def _apply_update_node_status(self, node_id, status, now=None):
        return self.store.update_node_status(node_id, status, now)

    def _apply_update_node_statuses(
        self, node_ids, status, now=None, message=""
    ):
        # one mass node-death wave = one command = one atomic apply
        return self.store.update_node_statuses(
            node_ids, status, now, message
        )

    def _apply_update_node_eligibility(self, node_id, eligibility):
        return self.store.update_node_eligibility(node_id, eligibility)

    def _apply_update_node_drain(self, node_id, drain, strategy):
        return self.store.update_node_drain(node_id, drain, strategy)

    def _apply_upsert_node_events(self, node_id, events):
        return self.store.upsert_node_events(node_id, events)

    def _apply_upsert_job(self, job, keep_versions=6):
        return self.store.upsert_job(job, keep_versions)

    def _apply_set_job_stability(self, namespace, job_id, version, stable):
        return self.store.set_job_stability(
            namespace, job_id, version, stable
        )

    def _apply_delete_job(self, namespace, job_id):
        return self.store.delete_job(namespace, job_id)

    def _apply_upsert_evals(self, evals, now=None):
        return self.store.upsert_evals(evals, now)

    def _apply_register_job_federated(self, job, ev, now=None):
        """Cross-region fan-out registration: job + its triggering
        eval as ONE log entry, so the target region can never hold a
        registered job without its eval (or vice versa) across a
        fan-out retry.  The command id is the fan-out's per-region
        id — a re-fanned registration dedups in apply() and returns
        this first apply's eval unchanged.  Timestamps and the eval
        id are proposer-fixed so every replica applies identically."""
        self.store.upsert_job(job, 6)
        if ev is not None:
            ev.job_modify_index = job.modify_index
            self.store.upsert_evals([ev], now)
        return ev

    def _apply_delete_eval(self, eval_id):
        return self.store.delete_eval(eval_id)

    def _apply_upsert_allocs(self, allocs):
        return self.store.upsert_allocs(allocs)

    def _apply_upsert_csi_volume(self, volume):
        return self.store.upsert_csi_volume(volume)

    def _apply_deregister_csi_volume(self, namespace, volume_id, force=False):
        return self.store.deregister_csi_volume(namespace, volume_id, force)

    def _apply_release_csi_claims_for_alloc(self, alloc_id):
        return self.store.release_csi_claims_for_alloc(alloc_id)

    def _apply_upsert_scaling_event(self, namespace, job_id, group, event):
        return self.store.upsert_scaling_event(
            namespace, job_id, group, event
        )

    def _apply_upsert_deployment(self, deployment):
        return self.store.upsert_deployment(deployment)

    def _apply_upsert_namespace(self, ns):
        return self.store.upsert_namespace(ns)

    def _apply_reconcile_job_summaries(self):
        return self.store.reconcile_job_summaries()

    def _apply_delete_namespace(self, name):
        return self.store.delete_namespace(name)

    def _apply_set_scheduler_config(self, config):
        return self.store.set_scheduler_config(config)

    def _apply_set_autopilot_config(self, config):
        return self.store.set_autopilot_config(config)

    def _apply_leadership_barrier(self, gen):
        """A newly established leader's first replicated command: move
        the fence so any still-in-flight command stamped by an OLDER
        generation (a deposed leader's wave) is rejected under the
        apply on every replica (reference: the establishLeadership
        barrier, leader.go:222, hardened into the log itself)."""
        self.leadership_fence = max(self.leadership_fence, gen)
        return self.leadership_fence

    def _apply_upsert_plan_results(self, result, eval_id, leader_gen=None):
        if (
            leader_gen is not None
            and leader_gen < self.leadership_fence
        ):
            # a deposed leader's wave must not commit: the plan was
            # computed against scheduling state that predates the new
            # leader's restore.  Raised (not returned) so the proposer
            # side fails its future and nacks the eval for redelivery.
            raise StaleLeadershipError(leader_gen, self.leadership_fence)
        if getattr(result, "normalized", False):
            result = denormalize_plan_result(self.store, result)
        index = self.store.upsert_plan_results(result, eval_id)
        if eval_id:
            # flight recorder: the replicated-apply path's commit mark
            # (single-process servers commit via the store directly
            # and get only the store.commit event)
            TRACE.event(
                eval_id, "fsm.apply",
                kind="upsert_plan_results", index=index,
            )
        return index

    # ACL commands ------------------------------------------------------

    def _apply_acl_upsert_policy(self, policy):
        self.acls.upsert_policy(policy)

    def _apply_acl_delete_policy(self, name):
        self.acls.delete_policy(name)

    def _apply_acl_create_token(self, token):
        return self.acls.create_token(token)

    def _apply_acl_delete_token(self, accessor_id):
        self.acls.delete_token(accessor_id)

    def _apply_acl_bootstrap(self, token):
        self.acls.tokens_by_accessor[token.accessor_id] = token
        self.acls.tokens_by_secret[token.secret_id] = token
        return token
