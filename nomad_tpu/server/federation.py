"""Multi-region federation: the geo plane (reference nomad/rpc.go:645
forwardRegion + nomad/serf.go WAN gossip + the enterprise multiregion
job deployer, stripped to its OSS contract).

The scaling argument (Tesserae, PAPERS.md) is that placement state must
stay partitioned to scale — here the partition is the region.  Each
region is a complete, self-sufficient control plane: its own raft
quorum, eval broker, TPU batch pipeline, storm solver and fan-out
followers, none of which know federation exists.  Only three things
cross the WAN, all through this module's :class:`FederationRouter`:

* **Job routing** — a submission landing in the wrong region hops to
  its home region's leader (``Job.region`` resolves the home; the
  ``region_call`` RPC carries it) with bounded retries/backoff
  mirroring the ``_raft_apply`` leader-forward loop: every retry
  re-resolves the region's membership from gossip, honors structured
  ``not_leader`` / ``wrong_region`` responses (each with a leader
  hint), and backs off through an interregnum instead of hammering it.
* **Cross-region job fan-out** — one jobspec carrying a ``Multiregion``
  block is fanned by the receiving (home) region's leader to every
  listed region.  Each target region's leader specializes the job
  locally (per-region ``count``/``datacenters``/``meta`` overrides)
  and proposes job+eval as ONE FSM command under a fan-out-scoped
  command id, so a retried fan-out dedups in the FSM and never
  double-registers; placement stays entirely region-local.
* **Health rumors** — the WAN gossip pool (membership.py) carries every
  server's region, liveness and HTTP advertise address.  The router
  thread snapshots it into a routing/health table that serves the
  ``X-Nomad-Retry-Region`` shed hint: a SHEDDING/EMERGENCY region
  answers sheds with the nearest healthy region's HTTP address, so
  global traffic degrades to the next region instead of hammering a
  dying one.

Reads NEVER cross the WAN implicitly: blocking queries and the
``/v1/cluster/*`` observability fan-in are answered from the local
region's servers only; the explicit ``?region=`` escape hatch forwards
and is the only path that increments ``federation.wan_reads`` (the
geo harness asserts the counter stays zero for region-local traffic).
"""
from __future__ import annotations

import copy
import itertools
import os
import pickle
import random
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..decisions import DECISIONS
from ..raft import NotLeaderError
from ..raft.transport import TransportError
from ..structs import DEFAULT_REGION, new_id
from ..trace import TRACE

# federation telemetry, zero-registered at Server construction (the
# `federation-metrics` nomadlint rule enforces registry membership for
# every federation.* emission across federation.py / cluster.py /
# server.py / api/http.py): absence of a federation.* series must mean
# "single region, nothing ever crossed the WAN", never "not exported"
FEDERATION_COUNTERS = (
    "federation.forwarded",  # cross-region calls that succeeded
    "federation.rpc_errors",  # failed cross-region attempts (any kind)
    "federation.retries",  # forward attempts after the first
    "federation.wrong_region",  # structured wrong_region responses
    "federation.fanout_jobs",  # multiregion jobs fanned by this server
    "federation.fanout_regions",  # per-region registrations dispatched
    "federation.wan_reads",  # reads explicitly forwarded (?region=)
    "federation.shed_redirects",  # sheds answered with a region hint
)
FEDERATION_GAUGES = (
    "federation.regions",  # regions with >=1 ALIVE member in gossip
    "federation.healthy_regions",  # non-local regions usable as a hint
)


# bounded cross-region forward retry budget (attempts AFTER the first):
# each retry re-resolves the target region's membership, so a forward
# survives the remote leadership moving mid-call
FED_RETRIES = 4
# initial cross-region retry backoff; doubles per attempt (capped at
# 1s) so a remote interregnum is waited out, not hammered
FED_BACKOFF_S = 0.05


def region_probe_s() -> float:
    """Router-thread cadence: how often the per-region health/routing
    snapshot (and the federation.regions gauges) refresh from
    gossip."""
    try:
        return max(
            0.05,
            float(os.environ.get("NOMAD_TPU_REGION_PROBE_S", "0.5")),
        )
    except ValueError:
        return 0.5


class FederationError(RuntimeError):
    """Structured cross-region failure.  ``kind`` is one of
    ``not_leader`` / ``unknown_region`` / ``wrong_region`` /
    ``timeout`` / ``transport`` / ``unknown_op`` / ``app`` — the same
    vocabulary the hardened ``region_call`` envelope carries, so a
    caller can tell a routing miss (retryable) from a replicated
    application verdict (definitive) without unpickling a crash."""

    def __init__(
        self,
        message: str,
        kind: str = "app",
        leader: Optional[str] = None,
    ) -> None:
        super().__init__(message)
        self.kind = kind
        self.leader = leader


class FederationRouter:
    """Per-server geo router: resolves home regions, forwards
    ``region_call`` RPCs with bounded retry, fans multiregion jobs
    out, and maintains the gossip-derived region health table behind
    the shed-redirect hint.

    The router thread only REFRESHES the snapshot; every read path
    (``nearest_healthy_region``, ``http_addr_in``) falls back to a
    synchronous refresh when the snapshot is empty, so a hint is
    available before the first tick."""

    def __init__(self, server) -> None:
        self.server = server
        self.retries = FED_RETRIES
        self.backoff_s = FED_BACKOFF_S
        self._probe_s = region_probe_s()
        self._lock = threading.Lock()
        # region -> {"members": int, "http": [addr, ...]}
        self._snapshot: Dict[str, Dict] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._seq = itertools.count(1)
        # decision-ledger dedup: the retry-region pick is read on
        # every shed redirect, so the federation_retry site ledgers
        # only when the CHOICE changes (membership churn, region
        # death/heal), not on every hint read
        self._last_retry_pick: Optional[str] = "unset"

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop,
            name=f"federation-router@{self.server.addr}",
            daemon=True,
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.refresh()
            except Exception:  # noqa: BLE001 — keep the router alive
                pass
            self._stop.wait(self._probe_s)

    # -- region health table -------------------------------------------

    def refresh(self) -> Dict[str, Dict]:
        """Rebuild the per-region health snapshot from gossip and
        update the federation.* gauges."""
        snap: Dict[str, Dict] = {}
        for m in self.server.gossip.alive_members():
            entry = snap.setdefault(
                m.region, {"members": 0, "http": []}
            )
            entry["members"] += 1
            http = getattr(m, "http_addr", "")
            if http:
                entry["http"].append(http)
        with self._lock:
            self._snapshot = snap
        metrics = getattr(self.server, "metrics", None)
        if metrics is not None:
            metrics.set_gauge("federation.regions", float(len(snap)))
            metrics.set_gauge(
                "federation.healthy_regions",
                float(
                    sum(
                        1
                        for r, e in snap.items()
                        if r != self.server.region and e["members"]
                    )
                ),
            )
        return snap

    def _snap(self) -> Dict[str, Dict]:
        with self._lock:
            snap = self._snapshot
        if not snap:
            snap = self.refresh()
        return snap

    def regions(self) -> Dict[str, Dict]:
        """Routing-table view: region -> member count + HTTP addrs."""
        return {
            region: {
                "members": e["members"],
                "http": sorted(e["http"]),
                "local": region == self.server.region,
            }
            for region, e in self._snap().items()
        }

    def nearest_healthy_region(self) -> Optional[Tuple[str, str]]:
        """The shed-redirect hint: the non-local region with the most
        ALIVE members (name tiebreak — deterministic; gossip carries
        no geo distance), plus one of its HTTP advertise addresses.
        None when this server is the only region standing."""
        snap = self._snap()
        candidates = [
            (region, e)
            for region, e in snap.items()
            if region != self.server.region and e["members"] > 0
        ]
        if not candidates:
            if DECISIONS.enabled and self._last_retry_pick is not None:
                self._last_retry_pick = None
                DECISIONS.record(
                    "federation_retry",
                    "none",
                    inputs={"local_region": self.server.region},
                    outcome="no_healthy_region",
                    metrics=getattr(self.server, "metrics", None),
                )
            return None
        region, entry = min(
            candidates, key=lambda kv: (-kv[1]["members"], kv[0])
        )
        if DECISIONS.enabled and region != self._last_retry_pick:
            self._last_retry_pick = region
            DECISIONS.record(
                "federation_retry",
                f"region={region}",
                inputs={
                    "local_region": self.server.region,
                    "members": entry["members"],
                },
                alternatives=[
                    f"region={r}(members={e['members']})"
                    for r, e in sorted(candidates)
                ],
                outcome="redirect_hint",
                metrics=getattr(self.server, "metrics", None),
            )
        http = sorted(entry["http"])
        return region, (http[0] if http else "")

    def http_addr_in(self, region: str) -> Optional[str]:
        """One HTTP advertise address in ``region`` (deterministic
        pick), or None when the region has no reachable member with
        an advertised HTTP endpoint."""
        entry = self._snap().get(region)
        if not entry or not entry["http"]:
            return None
        return sorted(entry["http"])[0]

    # -- home-region resolution ----------------------------------------

    def home_region(self, job) -> str:
        """Home region of a job: ``Job.region``, except that the
        struct default resolves to the receiving server's region (as
        the reference agent does) unless a region by that name
        actually exists in the federation."""
        region = job.region or DEFAULT_REGION
        if (
            region == DEFAULT_REGION
            and region != self.server.region
            and not self.server.gossip.members_in_region(region)
        ):
            region = self.server.region
        return region

    # -- cross-region forwarding ---------------------------------------

    def forward(self, region: str, op: str, *args, **kw):
        """Route one call to ``region``'s leader (reference rpc.go:645
        forwardRegion) with bounded retries/backoff mirroring the
        ``_raft_apply`` leader-forward loop.  Local region short-
        circuits to ``_leader_route``.  Raises
        :class:`FederationError` with a structured ``kind`` when the
        budget is exhausted or the remote answers a definitive
        application error."""
        srv = self.server
        if region == srv.region:
            return srv._leader_route(op, *args, **kw)
        trace_id = f"federation:{next(self._seq)}"
        TRACE.begin(
            trace_id,
            root_span="federation.forward",
            region=region,
            op=op,
        )
        try:
            result = self._forward_with_retry(
                region, op, args, kw, trace_id
            )
        except Exception as exc:
            TRACE.annotate(trace_id, error=str(exc))
            TRACE.finish(trace_id, "error")
            raise
        TRACE.finish(trace_id, "forwarded")
        return result

    def _forward_with_retry(
        self, region: str, op: str, args, kw, trace_id: str
    ):
        srv = self.server
        payload_args = pickle.dumps((args, kw))
        metrics = getattr(srv, "metrics", None)
        backoff = self.backoff_s
        last: Exception = FederationError(
            f"no path to region {region!r}", kind="unknown_region"
        )
        target: Optional[str] = None  # leader hint from a reply
        for attempt in range(self.retries + 1):
            if attempt:
                if metrics is not None:
                    metrics.incr("federation.retries")
                if backoff:
                    time.sleep(min(backoff * (2 ** (attempt - 1)), 1.0))
            if target is None:
                members = srv.gossip.members_in_region(region)
                if not members:
                    last = FederationError(
                        f"no path to region {region!r}",
                        kind="unknown_region",
                    )
                    if metrics is not None:
                        metrics.incr("federation.rpc_errors")
                    continue  # churn may restore it within the budget
                target = random.choice(members).addr
            addr, target = target, None
            t0 = time.monotonic()
            try:
                resp = srv.transport.rpc(
                    srv.addr,
                    addr,
                    "region_call",
                    {
                        "op": op,
                        "region": region,
                        "args": payload_args,
                    },
                )
            except (TransportError, TimeoutError) as exc:
                if metrics is not None:
                    metrics.incr("federation.rpc_errors")
                last = FederationError(
                    str(exc) or type(exc).__name__,
                    kind=(
                        "timeout"
                        if isinstance(exc, TimeoutError)
                        else "transport"
                    ),
                )
                continue
            if resp.get("wrong_region"):
                # stale gossip routed us to a server that is not in
                # the region we meant: structured, with the server's
                # actual region and its leader hint; re-resolve
                if metrics is not None:
                    metrics.incr("federation.wrong_region")
                    metrics.incr("federation.rpc_errors")
                last = FederationError(
                    f"server {addr} is in region "
                    f"{resp.get('region')!r}, not {region!r}",
                    kind="wrong_region",
                    leader=resp.get("leader"),
                )
                continue
            if resp.get("not_leader"):
                # remote had no established leader (or was deposed
                # mid-call); its hint — a server in the SAME region —
                # seeds the next attempt
                if metrics is not None:
                    metrics.incr("federation.rpc_errors")
                target = resp.get("leader")
                last = FederationError(
                    f"no leader in region {region!r}",
                    kind="not_leader",
                    leader=target,
                )
                continue
            if resp.get("error"):
                # structured application error from the remote leader:
                # definitive (the remote's own forwarding already
                # retried routing misses) — never re-forwarded
                if metrics is not None:
                    metrics.incr("federation.rpc_errors")
                raise FederationError(
                    resp["error"], kind=resp.get("kind", "app")
                )
            if metrics is not None:
                metrics.incr("federation.forwarded")
            TRACE.add_span(
                trace_id,
                "federation.forward",
                t0,
                time.monotonic() - t0,
                region=region,
                op=op,
                attempt=attempt,
                server=addr,
            )
            return pickle.loads(resp["result"])
        raise last

    # -- cross-region job fan-out --------------------------------------

    def fanout_job(self, job):
        """Coordinator half of cross-region job federation: fan one
        ``Multiregion`` jobspec from the home region's leader to every
        listed region.  Each region gets a deep copy (target-side
        interpolation mutates) under the per-region command id
        ``<fanout_id>:<region>`` — a retried forward (lost ack, moved
        leadership) re-proposes the SAME id and the target FSM's
        dedup returns the first apply instead of double-registering.
        Returns ``(home_eval, {region: status})``; per-region failures
        are recorded, not raised (the OSS on_failure strategy), so one
        dead region cannot veto the rest of the fan-out."""
        srv = self.server
        metrics = getattr(srv, "metrics", None)
        fanout_id = new_id()
        regions = [
            r.name for r in job.multiregion.regions if r.name
        ] or [srv.region]
        trace_id = f"federation:fanout:{fanout_id[:8]}"
        TRACE.begin(
            trace_id,
            root_span="federation.fanout",
            job=job.id,
            regions=len(regions),
        )
        if metrics is not None:
            metrics.incr("federation.fanout_jobs")
        statuses: Dict[str, Dict] = {}
        home_ev = None
        for region in regions:
            cmd_id = f"{fanout_id}:{region}"
            regional_job = copy.deepcopy(job)
            t0 = time.monotonic()
            try:
                if region == srv.region:
                    ev = srv._leader_route(
                        "federated_register", regional_job, cmd_id
                    )
                else:
                    ev = self.forward(
                        region, "federated_register", regional_job,
                        cmd_id,
                    )
                if metrics is not None:
                    metrics.incr("federation.fanout_regions")
            except FederationError as exc:
                statuses[region] = {
                    "ok": False,
                    "error": str(exc),
                    "kind": exc.kind,
                }
                continue
            except (
                NotLeaderError, TransportError, TimeoutError,
            ) as exc:
                statuses[region] = {
                    "ok": False,
                    "error": str(exc) or type(exc).__name__,
                    "kind": "not_leader"
                    if isinstance(exc, NotLeaderError)
                    else "transport",
                }
                continue
            statuses[region] = {
                "ok": True,
                "eval": ev.id if ev is not None else "",
            }
            TRACE.add_span(
                trace_id,
                "federation.forward",
                t0,
                time.monotonic() - t0,
                region=region,
                op="federated_register",
            )
            if region == srv.region or home_ev is None:
                home_ev = ev
        ok = sum(1 for s in statuses.values() if s.get("ok"))
        TRACE.annotate(trace_id, ok=ok, failed=len(statuses) - ok)
        TRACE.finish(
            trace_id, "federated" if ok == len(statuses) else "partial"
        )
        return home_ev, statuses

    def federation_status(self, namespace: str, job_id: str) -> Dict:
        """Per-region registration/placement status for one federated
        job (the ``/v1/job/<id>/federation`` aggregation): the local
        region answers from local state; every other region listed in
        the job's ``Multiregion`` block is asked live over
        ``region_call``.  Served by any server holding a local copy
        of the job."""
        srv = self.server
        job = srv.store.job_by_id(namespace, job_id)
        if job is None:
            raise KeyError(job_id)
        regions: List[str] = (
            [r.name for r in job.multiregion.regions if r.name]
            if job.multiregion is not None
            else []
        )
        if job.region and job.region not in regions:
            regions.insert(0, job.region)
        out: Dict[str, Dict] = {}
        for region in regions:
            if region == srv.region:
                out[region] = srv.federation_job_status(
                    namespace, job_id
                )
                continue
            try:
                out[region] = self.forward(
                    region, "federation_job_status", namespace, job_id
                )
            except (FederationError, NotLeaderError) as exc:
                out[region] = {
                    "registered": False,
                    "region": region,
                    "error": str(exc),
                    "kind": getattr(exc, "kind", "not_leader"),
                }
        return {
            "job": job_id,
            "namespace": namespace,
            "home": srv.region,
            "multiregion": job.multiregion is not None,
            "regions": out,
        }
