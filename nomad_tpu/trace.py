"""Eval flight recorder: end-to-end per-eval span tracing.

The batch pipeline's aggregate telemetry (`batch_worker.*` summaries,
`replay.*` counters) says *that* a stage is slow, never *which eval*
paid for it.  This module records one bounded trace per evaluation —
spans (named, timed intervals) and events (zero-duration marks) —
across every thread the eval's lifecycle touches: broker dequeue,
batch-worker gulp/simulate/assemble/launch/fetch, speculative replay
on the pool, the commit wave's ordering wait and conflict verdicts,
plan verification/apply, and the store's commit index.

Design constraints (always-on tracing must be free enough to forget):

* **O(1) per span.**  A span append is a list append under a per-trace
  lock; no allocation beyond the span record itself.
* **Bounded retention.**  One process-wide ring of `TRACE_RING` traces
  (active and completed alike — a trace that outlives the ring under
  load is dropped, never grown), `MAX_SPANS` spans per trace
  (overflow counts into `dropped`).
* **Monotonic timestamps.**  `time.monotonic()` everywhere; one
  wall-clock anchor per trace for display.
* **Opt-out, not opt-in.**  `NOMAD_TPU_TRACE=0` turns every call into
  a no-op (`Tracer.set_enabled` flips it at runtime for benches).

The tracer is a process-wide singleton (`TRACE`), like the logging
module: the broker, store and plan applier have no server reference,
and eval ids are globally unique, so per-server registries would only
add plumbing.  Cross-thread attribution is by eval id — every call
site knows which eval it is working for — with per-(trace, thread)
open-span stacks providing parent/child nesting.  A span recorded on
another thread than the one whose work caused it names that cause
explicitly: the span id travels with the work item (``cause=``), so an
eval's trace is ONE tree — rooted where the eval was created (the
start of `ingress.register`, or of `broker.wait` for an eval the
server made itself), not where a worker first saw it.

Every span name belongs to a layer (``LAYER_OF``).  A finished trace
folds (``Trace.fold``): each instant of the eval's life goes to the
deepest span open at it, and the layers partition the life exactly.
One acked trace in ``FOLD_SAMPLE`` is folded where it closes, and its
per-layer sums land on the server's telemetry as ``trace.life``,
``trace.self.<layer>`` and ``trace.cpu.<layer>`` samples.

Span names used in instrumented modules must be declared in
``SPAN_NAMES`` below; ``tools/check_stage_accounting.py`` lints
``batch_worker.py`` and ``plan_apply.py`` against this registry so a
renamed stage can't silently orphan its dashboard queries.
"""
from __future__ import annotations

import heapq
import itertools
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, NamedTuple, Optional

# retained traces (completed or in flight); at ~30 spans x ~150 bytes
# per trace this bounds the recorder near 5 MB
TRACE_RING = 1024
# spans per trace before overflow counting kicks in
MAX_SPANS = 256
# Every eval records its whole tree, and any finished trace folds on
# demand (GET /v1/traces/<eval>); one acked trace in FOLD_SAMPLE is
# folded into the server's telemetry (`trace.*` on /v1/metrics), whose
# per-layer series are then means over the folded evals.  On the v5e
# host the recorder's time costs four to six times what a
# microbenchmark of it reads (the host threads are off the CPU 70-80%
# of the time a span is open, PERF.md PR 27), and folding every ack
# was the largest single part of it.
FOLD_SAMPLE = 8
# one trace in CPU_SAMPLE (a multiple of FOLD_SAMPLE: those traces are
# folded) records thread CPU time on its spans.  The thread CPU clock
# is a system call (0.3 us on a workstation, 5.7-6.2 us with 10 ms
# ticks on the sandboxed v5e host): read at every span edge of every
# eval it cost more than the rest of the recorder together.  The
# off-CPU share is a ratio of sums over the sampled traces' spans, so
# sampling leaves it unbiased.
CPU_SAMPLE = 32

# the documented span/event name registry.  Every `.span/.add_span/
# .event` literal in batch_worker.py and plan_apply.py must appear
# here (tools/check_stage_accounting.py); names from other modules are
# registered too so the registry is the one place to look up a trace.
SPAN_NAMES = frozenset(
    {
        # the eval's life before a worker sees it: `ingress.register`
        # spans the HTTP handler from the parsed request to the eval's
        # hand-off to the broker, `broker.wait` the eval's time in the
        # broker from (re-)enqueue to dequeue (attrs: queue, ready
        # depth at dequeue) — both recorded at dequeue from the stamp
        # the broker keeps on its own queue entry
        "ingress.register",
        "broker.wait",
        # broker lifecycle: the dequeue mark, and the ack (the broker
        # records it from its entry to the finish that settles the
        # trace: the eval's last span)
        "broker.dequeue",
        "broker.ack",
        # batch pipeline stages (per-eval attribution of the
        # batch_worker.timings stages; chunk-wide spans carry a
        # `members` attr so aggregate sums match the stage timings)
        "batch_worker.gulp",
        # continuous micro-batching: `admit` spans an admission round's
        # gate+simulate+assemble work on every admitted eval (with a
        # `members` attr like the other chunk-wide stages);
        # `admit_deferred` marks an eval that arrived mid-chain but
        # failed an admission gate and was parked for the next gulp
        "batch_worker.admit",
        "batch_worker.admit_deferred",
        "batch_worker.simulate",
        "batch_worker.assemble",
        "batch_worker.launch",
        "batch_worker.fetch",
        # sharded (NOMAD_TPU_MESH) chunk dispatch/realize: the same
        # pipeline positions as launch/fetch, under their own names so
        # mesh time is separable on every trace-keyed dashboard (and
        # budgeted separately by the supervisor's stage watchdogs)
        "batch_worker.mesh_launch",
        "batch_worker.mesh_fetch",
        # global storm solver (NOMAD_TPU_STORM=1): `storm_gulp` marks
        # a family backlog drained for one coalesced solve (with the
        # member's FIFO position), `storm_solve` spans the single
        # device-side assignment solve on every member (members attr
        # like the other chunk-wide stages), `storm_decompose` the
        # per-eval plan decomposition, and `storm.fallback` marks a
        # member handed back to the serial chain (gate reason /
        # unsolved row / commit rescore / whole-storm crash) — never
        # a dropped eval
        "batch_worker.storm_gulp",
        # policy-weighted scoring (sched/policy.py): spans one storm
        # member's weight-tensor assembly — cached-throughput lookup
        # plus the live-alloc stickiness scan — inside staging
        "batch_worker.policy_assemble",
        "batch_worker.storm_solve",
        "batch_worker.storm_decompose",
        "storm.fallback",
        "batch_worker.replay",
        "batch_worker.sequential",
        "batch_worker.fallback",
        # optimistic parallel replay
        "replay.speculate",
        "replay.serial_required",
        "replay.commit_wait",
        "replay.commit",
        "replay.conflict",
        "replay.serial_fallback",
        # sequential worker
        "worker.invoke_scheduler",
        # accelerator supervisor (nomad_tpu/device): failover
        # incidents get their own trace (``device:failover:<n>``,
        # rooted at device.incident); device.watchdog_trip also lands
        # on the eval whose guarded stage tripped
        "device.incident",
        "device.failover",
        "device.watchdog_trip",
        "device.state_change",
        "device.flush",
        "device.probe",
        "device.rewarm",
        "device.recover",
        # overload control plane: `ingress.shed` roots one incident
        # trace (``overload:<n>``) per excursion from NORMAL — its
        # annotations carry the trigger signals and final shed counts;
        # `server.node_down_wave` roots one trace per batched mass
        # node-death transition (``node_down_wave:<n>``) naming the
        # wave's node count, replan evals and storm family
        "ingress.shed",
        # `overload.mode_change` lands on BOTH the overload incident
        # trace and every in-flight eval trace at the moment the mode
        # ladder moves, so a shed or degraded eval's waterfall says
        # which regime it ran under without joining against /v1/overload
        "overload.mode_change",
        "server.node_down_wave",
        # follower scheduling fan-out (NOMAD_TPU_FANOUT=1):
        # `fanout.remote_dequeue` spans the lease RPC on every eval a
        # follower dequeued from the leader's broker (members = lease
        # batch size), `fanout.plan_submit` spans the remote
        # serialized-commit round trip into the leader's plan queue
        "fanout.remote_dequeue",
        "fanout.plan_submit",
        # cluster-scope observability: `fanout.remote_span_ship`
        # marks a follower exporting its recorded span segment back
        # to the leader (piggybacked on the settle/submit RPC;
        # spans = segment size), `cluster.fanin` spans a leader's
        # fan-in query over the cluster transport (servers = peers
        # asked, unreachable = peers that timed out)
        "fanout.remote_span_ship",
        "cluster.fanin",
        # multi-region federation: `federation.forward` roots one
        # trace (``federation:<n>``) per cross-region call — its
        # spans carry the target region, op, attempt number and the
        # server that finally answered; `federation.fanout` roots one
        # trace (``federation:fanout:<id>``) per Multiregion job
        # fanned from the home region's leader, with a forward span
        # per target region
        "federation.forward",
        "federation.fanout",
        # plan pipeline + state commit.  A plan crosses three threads
        # and back; every span here names the submitter's open span
        # as its cause (carried on the PendingPlan), and the three
        # hand-offs are spans of their own: `plan.queue_wait` (plan
        # queue enqueue -> the verifier takes it), `plan.stage_wait`
        # (end of plan.evaluate -> the committer takes it off the
        # staged queue), `plan.respond_wait` (the committer's respond
        # -> the submitter resumes)
        "plan.queue_wait",
        "plan.evaluate",
        "plan.stage_wait",
        "plan.apply",
        "plan.respond_wait",
        # leadership failover: the applier rejected an in-flight plan
        # because leadership was revoked (the submitting worker nacks
        # the eval for redelivery under the next leadership)
        "plan.not_leader",
        # `store.commit` spans upsert_plan_results from the store's
        # lock taken to the index published (a child of plan.apply);
        # `store.upsert_evals` and `explain.publish` are the eval
        # status write and the explanation publish that follow a
        # replay's plan
        "store.commit",
        "store.upsert_evals",
        "explain.publish",
        "fsm.apply",
    }
)

# the layers an eval's life is folded into; `pipeline_wait` is the
# time the eval was alive and inside no stage (the root's own self
# time, `replay.commit_wait`, and the share of a chunk-wide stage in
# which it waited on its chunk-mates)
LAYERS = (
    "ingress",
    "broker",
    "pipeline_wait",
    "bw_host",
    "replay_pool",
    "plan_handoff",
    "plan_applier",
    "store",
)
# layers whose spans are recorded by the thread that did the work and
# so carry `cpu_ms` (trace.cpu.<layer>)
CPU_LAYERS = ("bw_host", "replay_pool", "plan_applier", "store")
# spans that wait on the device by design: their time is the layer's
# (bw_host) but is left out of BOTH sides of the off-CPU share
# (`trace.cpu.*` over `trace.cpu_wall`)
DEVICE_WAIT_SPANS = frozenset(
    {"batch_worker.fetch", "batch_worker.mesh_fetch"}
)
# event-only: a zero-duration mark, or a span of an incident trace
# (device:failover, overload, federation, ...) that never acks and is
# never folded.  The fold looks through such a name.
EVENT = None

# span name -> layer.  Every SPAN_NAMES entry appears here (the
# `span-layers` nomadlint rule holds the two together), so a renamed
# stage cannot silently leave its layer's metric.
LAYER_OF: Dict[str, Optional[str]] = {
    "ingress.register": "ingress",
    "broker.wait": "broker",
    "broker.dequeue": EVENT,
    "broker.ack": "broker",
    "batch_worker.gulp": EVENT,
    "batch_worker.admit": "bw_host",
    "batch_worker.admit_deferred": EVENT,
    "batch_worker.simulate": "bw_host",
    "batch_worker.assemble": "bw_host",
    "batch_worker.launch": "bw_host",
    "batch_worker.fetch": "bw_host",
    "batch_worker.mesh_launch": "bw_host",
    "batch_worker.mesh_fetch": "bw_host",
    "batch_worker.storm_gulp": EVENT,
    "batch_worker.policy_assemble": "bw_host",
    "batch_worker.storm_solve": "bw_host",
    "batch_worker.storm_decompose": "bw_host",
    "storm.fallback": EVENT,
    "batch_worker.replay": "bw_host",
    "batch_worker.sequential": "bw_host",
    "batch_worker.fallback": EVENT,
    "replay.speculate": "replay_pool",
    "replay.serial_required": EVENT,
    "replay.commit_wait": "pipeline_wait",
    "replay.commit": "bw_host",
    "replay.conflict": EVENT,
    "replay.serial_fallback": EVENT,
    "worker.invoke_scheduler": "bw_host",
    "device.incident": EVENT,
    "device.failover": EVENT,
    "device.watchdog_trip": EVENT,
    "device.state_change": EVENT,
    "device.flush": EVENT,
    "device.probe": EVENT,
    "device.rewarm": EVENT,
    "device.recover": EVENT,
    "ingress.shed": EVENT,
    "overload.mode_change": EVENT,
    "server.node_down_wave": EVENT,
    "fanout.remote_dequeue": "broker",
    "fanout.plan_submit": "plan_handoff",
    "fanout.remote_span_ship": EVENT,
    "cluster.fanin": EVENT,
    "federation.forward": EVENT,
    "federation.fanout": EVENT,
    "plan.queue_wait": "plan_handoff",
    "plan.evaluate": "plan_applier",
    "plan.stage_wait": "plan_handoff",
    "plan.apply": "plan_applier",
    "plan.respond_wait": "plan_handoff",
    "plan.not_leader": EVENT,
    "store.commit": "store",
    "store.upsert_evals": "store",
    "explain.publish": "bw_host",
    "fsm.apply": EVENT,
}


class _NullSpan:
    """Reusable no-op context manager for disabled/unknown traces."""

    __slots__ = ()
    sid = None

    def note(self, **attrs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _SpanCtx:
    """An open span on the calling thread.  The thread that opens it
    does the work, so on a trace that samples CPU time the span
    records that thread's CPU time between its edges (`cpu_ms`)."""

    __slots__ = ("_trace", "_name", "_attrs", "_cause", "sid")

    def __init__(
        self, trace: "Trace", name: str, attrs: dict,
        cause: Optional[int],
    ) -> None:
        self._trace = trace
        self._name = name
        self._attrs = attrs
        self._cause = cause
        # the id work handed to another thread carries as its cause
        self.sid = -1

    def note(self, **attrs) -> None:
        """Attributes learned while the span is open."""
        self._attrs.update(attrs)

    def __enter__(self):
        trace = self._trace
        self.sid = trace.open_span(
            self._name, time.monotonic(), self._attrs, self._cause,
            time.thread_time() if trace.cpu else None,
        )
        return self

    def __exit__(self, *exc):
        trace = self._trace
        trace.close_span(
            self.sid, time.monotonic(),
            time.thread_time() if trace.cpu else None,
        )
        return False


class Fold(NamedTuple):
    """A finished trace folded by layer."""

    life_ms: float  # create -> ack
    self_ms: Dict[str, float]  # by layer (LAYERS); sums to life_ms
    cpu_ms: Dict[str, float]  # by layer (CPU_LAYERS)
    # the self time of exactly the spans whose CPU time cpu_ms counts:
    # the other side of the off-CPU share
    cpu_wall_ms: float
    own: List[float]  # by span id, the seconds that span owned

    def samples(self):
        """The `trace.*` samples of one folded trace, as (name,
        value) pairs in FOLD_SAMPLES' order: the life and the eight
        self times always, the CPU side where a span recorded any."""
        values = (self.life_ms, *self.self_ms.values())
        if self.cpu_wall_ms:
            values += (*self.cpu_ms.values(), self.cpu_wall_ms)
        return zip(FOLD_SAMPLES, values)


# the `trace.*` series the fold feeds (zero-registered by the Server);
# self_ms and cpu_ms are built from LAYERS and CPU_LAYERS in order
FOLD_SAMPLES = (
    ("trace.life",)
    + tuple(f"trace.self.{k}" for k in LAYERS)
    + tuple(f"trace.cpu.{k}" for k in CPU_LAYERS)
    + ("trace.cpu_wall",)
)
FOLD_COUNTERS = ("trace.folded", "trace.unfolded")


class Trace:
    """One eval's recorded lifecycle.  Span records are small lists
    ``[sid, parent, name, start, duration, thread, attrs, depth,
    cpu]`` — ``duration`` stays None while the span is open; a span's
    id is its index in ``spans``; ``parent`` is the span that caused
    it (the recording thread's innermost open span unless the caller
    names a cause); ``depth`` is the length of that chain; ``cpu`` is
    the opening thread's CPU clock while the span is open and the
    thread CPU milliseconds between its edges once it is closed (None
    where the recording thread did not do the work)."""

    __slots__ = (
        "eval_id",
        "trace_id",
        "t0",
        "wall0",
        "t_end",
        "spans",
        "attrs",
        "outcome",
        "finished",
        "dropped",
        "orphans",
        "folded",
        "folds",
        "cpu",
        "_open",
        "_lock",
        "_shipped",
    )

    def __init__(
        self, eval_id: str, gen: int, attrs: dict,
        t0: Optional[float] = None,
    ) -> None:
        self.eval_id = eval_id
        self.trace_id = f"{eval_id}#{gen}"
        now = time.monotonic()
        # the eval's creation, when the caller knows it (the broker's
        # stamp on its queue entry); the moment of recording otherwise
        self.t0 = t0 if t0 is not None and t0 < now else now
        self.wall0 = time.time() - (now - self.t0)
        self.t_end: Optional[float] = None
        self.spans: List[list] = []
        self.attrs = dict(attrs)
        self.outcome: Optional[str] = None
        self.finished = False
        self.dropped = 0
        self.orphans = 0
        # the fold's result once the trace has finished (see fold())
        self.folded: Optional["Fold"] = None
        # whether the ack folds this trace into the telemetry, and
        # whether its spans read the thread CPU clock
        self.folds = gen % FOLD_SAMPLE == 0
        self.cpu = gen % CPU_SAMPLE == 0
        # thread id -> stack of open span ids (same-thread nesting;
        # work that crossed threads names its cause instead)
        self._open: Dict[int, List[int]] = {}
        self._lock = threading.Lock()
        # span ids already exported by export_segment (segment traces
        # on fan-out followers only; empty everywhere else)
        self._shipped: set = set()

    # -- recording -----------------------------------------------------

    def open_span(
        self, name: str, start: float, attrs: dict,
        cause: Optional[int] = None, cpu0: Optional[float] = None,
    ) -> int:
        tid = threading.get_ident()
        with self._lock:
            if len(self.spans) >= MAX_SPANS or start < self.t0:
                # over the cap, or a write from a SUPERSEDED attempt:
                # after a redelivery the old attempt may still be
                # running, and its by-eval-id writes resolve to this
                # (newer) trace — an interval that began before this
                # trace did belongs to the old generation, not here
                self.dropped += 1
                return -1
            spans = self.spans
            sid = len(spans)
            stack = self._open.get(tid)
            if cause is None or not 0 <= cause < sid:
                # the thread's innermost open span caused this one
                cause = stack[-1] if stack else None
            spans.append(
                [
                    sid,
                    cause,
                    name,
                    start,
                    None,
                    threading.current_thread().name,
                    attrs,
                    1 if cause is None else spans[cause][7] + 1,
                    cpu0,
                ]
            )
            if stack is None:
                self._open[tid] = [sid]
            else:
                stack.append(sid)
            return sid

    def close_span(
        self, sid: int, end: float, cpu1: Optional[float] = None
    ) -> None:
        if sid < 0:
            return
        tid = threading.get_ident()
        with self._lock:
            stack = self._open.get(tid)
            if stack and stack[-1] == sid:
                stack.pop()
            elif stack and sid in stack:
                # pop through sid: a crash that skipped inner exits
                # must not leave phantom parents for later spans
                while stack and stack.pop() != sid:
                    pass
            span = self.spans[sid]
            if span[4] is None and span[8] is not None:
                # (a span the finish already ended keeps the CPU time
                # it read there)
                span[8] = (
                    (cpu1 - span[8]) * 1000.0
                    if cpu1 is not None
                    else None
                )
            span[4] = end - span[3]

    def add_span(
        self, name: str, start: float, duration: float, attrs: dict,
        cause: Optional[int] = None, thread: Optional[str] = None,
        cpu_ms: Optional[float] = None,
    ) -> int:
        """Record an already-timed interval (stage times measured once
        per chunk/run and attributed to each member eval; waits whose
        start was stamped on the work item).  Returns the span's id,
        -1 when it was dropped."""
        tid = threading.get_ident()
        with self._lock:
            if len(self.spans) >= MAX_SPANS or start < self.t0:
                # see open_span: pre-t0 starts are a superseded
                # attempt's writes (best-effort — a stale write whose
                # clock reads after this trace began is
                # indistinguishable and slips through)
                self.dropped += 1
                return -1
            spans = self.spans
            sid = len(spans)
            if cause is None or not 0 <= cause < sid:
                stack = self._open.get(tid)
                cause = stack[-1] if stack else None
            spans.append(
                [
                    sid,
                    cause,
                    name,
                    start,
                    duration,
                    thread or threading.current_thread().name,
                    attrs,
                    1 if cause is None else spans[cause][7] + 1,
                    cpu_ms,
                ]
            )
            return sid

    def annotate(self, attrs: dict) -> None:
        with self._lock:
            self.attrs.update(attrs)

    def finish(self, outcome: str) -> bool:
        """Settle the trace; False when it already was."""
        tid = threading.get_ident()
        with self._lock:
            if self.finished:
                return False
            self.finished = True
            now = self.t_end = time.monotonic()
            # a batch-worker path may have annotated a richer outcome
            # ("speculative", "prescored", "sequential") — but only a
            # successful ack consumes it: a nack or a redelivery
            # supersede describes an attempt that did NOT stick, and
            # must not masquerade as the annotated success
            annotated = self.attrs.pop("outcome", None)
            self.outcome = (
                annotated if annotated and outcome == "ack" else outcome
            )
            # the spans this thread still holds open enclose the
            # finish itself (the ack runs inside `replay.commit`):
            # they end here for the record and the fold, and close for
            # real a moment later.  Only a span another thread left
            # open is an orphan.
            stack = self._open.get(tid)
            if stack:
                cpu = time.thread_time() if self.cpu else 0.0
                for sid in stack:
                    span = self.spans[sid]
                    if span[4] is None:
                        span[4] = now - span[3]
                        if span[8] is not None:
                            span[8] = (cpu - span[8]) * 1000.0
            self.orphans = sum(
                1 for s in self.spans if s[4] is None
            )
        return True

    # -- the fold ------------------------------------------------------

    def fold(self) -> Optional["Fold"]:
        """Self time by layer over the eval's life [t0, t_end].

        Every instant goes to ONE span: the deepest one open at it
        (deepest in the cause tree; the later start among equals), or
        to the root when none is.  For a well-nested tree that is the
        guide's self time — a span's duration minus the part of it its
        children cover — and overlapping siblings, or a child that runs
        after the span that caused it (`replay.speculate` under its
        `batch_worker.fetch`), still count each instant once, so the
        layers partition the life.  A chunk-wide span (`members=n`)
        keeps 1/n of what it owns for its layer; the rest, in which the
        eval waited on its chunk-mates, is `pipeline_wait`, as is the
        root's own time.  CPU time is a span's `cpu_ms` less that of
        its children on the same thread; `cpu_wall` is the self time
        of the spans that carry one (device waits left out of both).

        None when the trace cannot be folded: not finished, spans
        dropped, or a span another thread still holds open."""
        if self.folded is not None:
            return self.folded
        with self._lock:
            if not self.finished or self.dropped:
                return None
            spans = list(self.spans)
        t0 = self.t0
        t_end = self.t_end
        layer_of = LAYER_OF
        items = []
        for s in spans:
            if layer_of.get(s[2]) is None:
                continue
            dur = s[4]
            if dur is None:
                return None
            start = s[3]
            end = start + dur
            if start < t0:
                start = t0
            if end > t_end:
                end = t_end
            if end > start:
                items.append((start, -s[7], -s[0], end))
        items.sort()
        own = [0.0] * len(spans)
        wait = 0.0  # the root's own time
        heap: list = []
        push, pop = heapq.heappush, heapq.heappop
        t = t0
        # one sentinel past the end flushes the tail
        items.append((t_end, 0, 0, t_end))
        for item in items:
            start = item[0]
            while t < start:
                while heap and heap[0][3] <= t:
                    pop(heap)
                if not heap:
                    wait += start - t
                    break
                top = heap[0]
                upto = top[3] if top[3] < start else start
                own[-top[2]] += upto - t
                t = upto
            t = start
            # (-depth, -start, -sid, end): deepest, then latest, wins
            push(heap, (item[1], -start, item[2], item[3]))
        # a span's own CPU time: its children on the same thread out
        cpus = [s[8] for s in spans]
        for s in spans:
            parent = s[1]
            if (
                s[8] is not None
                and parent is not None
                and cpus[parent] is not None
                and spans[parent][5] == s[5]
            ):
                cpus[parent] -= s[8]
        layers = dict.fromkeys(LAYERS, 0.0)
        cpu = dict.fromkeys(CPU_LAYERS, 0.0)
        cpu_wall = 0.0
        for s, owned, c in zip(spans, own, cpus):
            if not owned and c is None:
                continue
            layer = layer_of.get(s[2])
            if layer is None:
                continue
            members = s[6].get("members", 1)
            if members > 1:
                share = owned / members
                wait += owned - share
                owned = share
            layers[layer] += owned
            if (
                c is not None
                and layer in cpu
                and s[2] not in DEVICE_WAIT_SPANS
            ):
                cpu[layer] += c / members
                cpu_wall += owned
        layers["pipeline_wait"] += wait
        self.folded = Fold(
            (t_end - t0) * 1000.0,
            {k: v * 1000.0 for k, v in layers.items()},
            cpu,
            cpu_wall * 1000.0,
            own,
        )
        return self.folded

    # -- cross-server segment shipping ---------------------------------

    def export_segment(self, server_id: str) -> Optional[Dict]:
        """Export the CLOSED spans not shipped by a previous export as
        a wire segment (fan-out followers piggyback this on the settle
        / submit RPC).  Offsets are seconds relative to this trace's
        ``t0``; the segment carries the trace's ``wall0`` wall-clock
        anchor so the receiver can map them onto its own monotonic
        clock (clock skew between hosts shows up as shifted lanes —
        trace_report flags skew-suspect gaps rather than us trusting
        cross-host monotonic deltas)."""
        with self._lock:
            fresh = [
                s
                for s in self.spans
                if s[4] is not None and s[0] not in self._shipped
            ]
            for s in fresh:
                self._shipped.add(s[0])
            spans = [
                {
                    "id": s[0],
                    "parent": s[1],
                    "name": s[2],
                    "off": s[3] - self.t0,
                    "dur": s[4],
                    "thread": s[5],
                    "attrs": dict(s[6]),
                }
                for s in fresh
            ]
            attrs = dict(self.attrs)
        if not spans and "outcome" not in attrs:
            return None
        return {
            "trace_id": self.trace_id,
            "server_id": server_id,
            "wall0": self.wall0,
            "spans": spans,
            "attrs": attrs,
        }

    def absorb_segment(self, segment: Dict) -> int:
        """Merge a shipped segment's spans into this trace: remote
        offsets are re-anchored via the wall-clock deltas, span ids are
        remapped into this trace's sequence (parent links within the
        segment batch are preserved; a parent shipped in an *earlier*
        batch attaches flat), and every span is stamped with the
        shipping ``server_id``.  Bypasses the pre-``t0`` staleness
        guard on purpose — segment routing already matched the full
        trace id, so generation confusion is impossible here and a
        skewed remote clock must not silently drop spans."""
        base = self.t0 + (segment.get("wall0", self.wall0) - self.wall0)
        server_id = segment.get("server_id", "")
        absorbed = 0
        with self._lock:
            remap: Dict[int, int] = {}
            for s in segment.get("spans", ()):
                if len(self.spans) >= MAX_SPANS:
                    self.dropped += 1
                    continue
                sid = len(self.spans)
                remap[s["id"]] = sid
                attrs = dict(s.get("attrs") or {})
                if server_id:
                    attrs.setdefault("server_id", server_id)
                parent = remap.get(s.get("parent"))
                self.spans.append(
                    [
                        sid,
                        parent,
                        s["name"],
                        base + s["off"],
                        s["dur"],
                        s.get("thread", ""),
                        attrs,
                        1
                        if parent is None
                        else self.spans[parent][7] + 1,
                        None,
                    ]
                )
                absorbed += 1
            if self.finished:
                # late segment into an already-settled trace (the
                # normal nack/redelivery race): keep the orphan count
                # honest for the spans that just landed
                self.orphans = sum(
                    1 for s in self.spans if s[4] is None
                )
        return absorbed

    # -- serialization -------------------------------------------------

    def duration_ms(self) -> Optional[float]:
        if self.t_end is None:
            return None
        end = self.t_end
        with self._lock:
            for s in self.spans:
                if s[4] is not None:
                    end = max(end, s[3] + s[4])
        return (end - self.t0) * 1000.0

    def summary(self) -> Dict:
        return {
            "eval_id": self.eval_id,
            "trace_id": self.trace_id,
            "start": self.wall0,
            "outcome": self.outcome,
            "complete": self.finished,
            "duration_ms": self.duration_ms(),
            "spans": len(self.spans),
            "dropped": self.dropped,
            "orphans": self.orphans,
            "attrs": dict(self.attrs),
        }

    def to_dict(self) -> Dict:
        out = self.summary()
        fold = self.fold()
        own = fold.own if fold is not None else None
        with self._lock:
            out["spans"] = [
                {
                    "id": s[0],
                    "parent": s[1],
                    "name": s[2],
                    "off_ms": (s[3] - self.t0) * 1000.0,
                    "dur_ms": (
                        s[4] * 1000.0 if s[4] is not None else None
                    ),
                    "thread": s[5],
                    "attrs": dict(s[6]),
                    # thread CPU ms between the span's edges, where
                    # the recording thread did the work
                    "cpu_ms": s[8] if s[4] is not None else None,
                }
                for s in self.spans
            ]
        if fold is not None:
            # a folded trace says where its life went: per span what
            # the span owned, per layer the sums
            for span in out["spans"]:
                sid = span["id"]  # (a straggler's span came later)
                span["self_ms"] = (
                    own[sid] * 1000.0 if sid < len(own) else 0.0
                )
            out["life_ms"] = fold.life_ms
            out["layers_ms"] = dict(fold.self_ms)
        return out


class Tracer:
    def __init__(self, ring: int = TRACE_RING) -> None:
        self._lock = threading.Lock()
        self._ring: deque = deque()
        self._ring_cap = ring
        # newest trace per eval id (ring members only) — the append
        # surface every instrumented call site goes through
        self._by_id: Dict[str, Trace] = {}
        # follower-side recording buffers for evals leased from a
        # remote leader, keyed by eval id: they carry the LEADER's
        # trace id, collect this server's pipeline spans, and are
        # shipped back (export_segment) rather than retained — they
        # never enter the ring
        self._segments: Dict[str, Trace] = {}
        self._gen = itertools.count()
        # where the request on this thread was parsed (ingress())
        self._ingress = threading.local()
        self.enabled = os.environ.get("NOMAD_TPU_TRACE", "1") != "0"
        # happens-before sanitizer (NOMAD_TPU_TSAN=1)
        from .tsan import maybe_instrument

        maybe_instrument(self, "Tracer")

    def set_enabled(self, enabled: bool) -> None:
        self.enabled = bool(enabled)

    # -- lifecycle -----------------------------------------------------

    def begin(
        self, eval_id: str, root_span: str = "broker.dequeue",
        t0: Optional[float] = None, **attrs,
    ) -> Optional[Trace]:
        """Start (or restart, on redelivery) an eval's trace; records
        ``root_span`` (default `broker.dequeue`) as the root event —
        non-eval traces (the device supervisor's failover incidents)
        pass their own root name.  ``t0`` dates the trace back to the
        eval's creation (the broker's enqueue stamp); the root event
        stays at the moment of the call.  Returns the new trace, for
        the spans the caller dates back onto it."""
        if not self.enabled or not eval_id:
            return None
        trace = Trace(eval_id, next(self._gen), attrs, t0)
        with self._lock:
            prior = self._by_id.get(eval_id)
            if prior is not None and not prior.finished:
                prior.finish("superseded")
            self._by_id[eval_id] = trace
            self._ring.append(trace)
            while len(self._ring) > self._ring_cap:
                evicted = self._ring.popleft()
                if self._by_id.get(evicted.eval_id) is evicted:
                    del self._by_id[evicted.eval_id]
        trace.add_span(
            root_span, trace.t0 if t0 is None else time.monotonic(),
            0.0, attrs,
        )
        return trace

    def finish(self, eval_id: str, outcome: str) -> Optional[Trace]:
        """Settle an eval's trace; returns the trace this call
        settled (None when untracked or already settled)."""
        if not self.enabled:
            return None
        trace = self._by_id.get(eval_id)
        if trace is not None and trace.finish(outcome):
            return trace
        return None

    def publish(self, trace: Optional[Trace], sink) -> None:
        """Fold an acked eval's trace where it closed and add its
        per-layer self and CPU times to the server's telemetry
        (``sink``, a Metrics) as `trace.*` samples.  ``trace`` is what
        ``finish(eval_id, "ack")`` returned.  One trace in
        ``FOLD_SAMPLE`` is folded here; of those, an acked eval whose
        trace cannot be folded (spans dropped, a span another thread
        left open — or evicted from the ring, which is counted for
        every such eval) counts into `trace.unfolded`."""
        if not self.enabled or sink is None:
            return
        if trace is not None and not trace.folds:
            return
        fold = trace.fold() if trace is not None else None
        if fold is None:
            sink.incr("trace.unfolded")
            return
        sink.add_samples(fold.samples(), exemplar=trace.eval_id)
        sink.incr("trace.folded")

    @contextmanager
    def ingress(self):
        """Mark the calling thread as an HTTP handler registering
        work from here on: an eval this thread hands to the broker
        inside the block is stamped with the block's start, and its
        trace begins there (`ingress.register`)."""
        self._ingress.t = time.monotonic()
        try:
            yield
        finally:
            self._ingress.t = None

    def ingress_start(self) -> Optional[float]:
        return getattr(self._ingress, "t", None)

    # -- cross-server propagation --------------------------------------

    def export_context(self, eval_id: str) -> Optional[Dict]:
        """Trace context shipped with a remote broker lease: the full
        trace id (generation counters are per-process, so the string is
        the only cross-server identity) plus the wall-clock anchor the
        follower needs to re-anchor its segment offsets."""
        trace = self._by_id.get(eval_id)
        if trace is None:
            return None
        return {"trace_id": trace.trace_id, "wall0": trace.wall0}

    def begin_segment(self, eval_id: str, ctx: Dict, **attrs) -> None:
        """Follower side of lease propagation: open a local recording
        segment under the LEADER's trace id.  Instrumented call sites
        resolve by eval id, so every existing pipeline span lands here
        transparently; the segment is shipped back on settle/submit
        and never enters the local ring.  A redelivered lease opens a
        fresh segment that supersedes the old one — same semantics as
        ``begin`` on the leader."""
        if not self.enabled or not eval_id or not ctx:
            return
        trace_id = ctx.get("trace_id") or ""
        if not trace_id:
            return
        segment = Trace(eval_id, 0, attrs)
        segment.trace_id = trace_id
        with self._lock:
            prior = self._segments.get(eval_id)
            if prior is not None and not prior.finished:
                prior.finish("superseded")
            self._segments[eval_id] = segment

    def export_segment(
        self,
        eval_id: str,
        server_id: str,
        close: bool = False,
        outcome: str = "shipped",
    ) -> Optional[Dict]:
        """Export the eval's segment spans closed since the last
        export; ``close=True`` (the settle RPC) also retires the local
        segment so the follower isn't left holding in-flight buffers
        for evals it no longer owns."""
        if not self.enabled:
            return None
        with self._lock:
            segment = self._segments.get(eval_id)
        if segment is None:
            return None
        # the ship itself is part of the record: a zero-duration mark
        # on the segment (and in this batch) shows when each export
        # left this server on the stitched waterfall
        segment.add_span(
            "fanout.remote_span_ship",
            time.monotonic(),
            0.0,
            {"server_id": server_id},
        )
        out = segment.export_segment(server_id)
        if close:
            with self._lock:
                if self._segments.get(eval_id) is segment:
                    del self._segments[eval_id]
            segment.finish(outcome)
        return out

    def absorb_segment(self, segment: Optional[Dict]) -> int:
        """Leader side: merge a shipped segment into the ring trace
        with the MATCHING full trace id.  Routing by trace id — not
        bare eval id — is what makes redelivery supersede across
        servers: a segment straggling in from a dead follower carries
        the old generation's trace id and lands in that (settled)
        trace, never interleaving into the redelivered attempt."""
        if not self.enabled or not segment:
            return 0
        trace_id = segment.get("trace_id") or ""
        if not trace_id:
            return 0
        eval_id = trace_id.rsplit("#", 1)[0]
        target = self._by_id.get(eval_id)
        if target is None or target.trace_id != trace_id:
            target = None
            with self._lock:
                candidates = list(self._ring)
            for trace in reversed(candidates):
                if trace.trace_id == trace_id:
                    target = trace
                    break
        if target is None:
            return 0
        absorbed = target.absorb_segment(segment)
        attrs = segment.get("attrs") or {}
        outcome = attrs.get("outcome")
        if outcome and not target.finished:
            # the follower's richer outcome annotation ("speculative",
            # "prescored", ...) travels in the segment attrs; a
            # successful ack consumes it in Trace.finish
            target.annotate({"outcome": outcome})
        return absorbed

    def open_segments(self) -> int:
        """Count of live follower-side recording segments."""
        with self._lock:
            return len(self._segments)

    # -- recording -----------------------------------------------------

    def _resolve(self, eval_id: str) -> Optional[Trace]:
        """Recording target for an eval: a live leased segment wins
        over the ring entry, but only while it is current — if the
        eval was re-begun locally under a NEW trace id (the lease was
        reclaimed and redelivered here), the stale segment is dropped
        rather than swallowing the new attempt's spans.  Only a
        fan-out follower has segments: everywhere else this is one
        dict read and takes no lock."""
        if not self._segments:
            return self._by_id.get(eval_id)
        with self._lock:
            segment = self._segments.get(eval_id)
            if segment is not None:
                current = self._by_id.get(eval_id)
                if (
                    current is None
                    or current.trace_id == segment.trace_id
                ):
                    return segment
                del self._segments[eval_id]
        if segment is not None:
            segment.finish("superseded")
        return self._by_id.get(eval_id)

    def span(
        self, eval_id: str, name: str, cause: Optional[int] = None,
        **attrs,
    ):
        """Context manager timing a span on the eval's trace; no-op
        when tracing is off or the eval has no trace.  ``cause`` is
        the id of the span (on another thread) whose work item this
        one serves; the thread's innermost open span otherwise."""
        if not self.enabled:
            return _NULL
        trace = self._resolve(eval_id)
        if trace is None:
            return _NULL
        return _SpanCtx(trace, name, attrs, cause)

    def add_span(
        self, eval_id: str, name: str, start: float,
        duration: float, cause: Optional[int] = None,
        thread: Optional[str] = None,
        cpu_ms: Optional[float] = None, **attrs,
    ) -> Optional[int]:
        """Record an interval timed by the caller; returns its span
        id (the ``cause`` of work it hands on), None when untracked.
        ``cpu_ms`` is the calling thread's CPU time between the
        interval's edges, where that thread did the work."""
        if not self.enabled:
            return None
        trace = self._resolve(eval_id)
        if trace is None:
            return None
        sid = trace.add_span(
            name, start, duration, attrs, cause, thread, cpu_ms
        )
        return sid if sid >= 0 else None

    def cpu_clock(self, eval_id: str) -> Optional[float]:
        """The calling thread's CPU clock, for a caller that times a
        span's CPU by hand (``cpu_ms=cpu_ms_since(clock)`` on the
        ``add_span`` that follows) — or None when the eval's trace
        does not sample CPU time (one in ``CPU_SAMPLE`` does)."""
        if not self.enabled:
            return None
        trace = self._resolve(eval_id)
        if trace is None or not trace.cpu:
            return None
        return time.thread_time()

    def current(self, eval_id: str) -> Optional[int]:
        """Id of the calling thread's innermost open span on the
        eval's trace: what a work item handed to another thread
        carries as its cause."""
        if not self.enabled:
            return None
        trace = self._resolve(eval_id)
        if trace is None:
            return None
        stack = trace._open.get(threading.get_ident())
        return stack[-1] if stack else None

    def event(self, eval_id: str, name: str, **attrs) -> None:
        if not self.enabled:
            return
        trace = self._resolve(eval_id)
        if trace is not None:
            trace.add_span(name, time.monotonic(), 0.0, attrs)

    def annotate(self, eval_id: str, **attrs) -> None:
        if not self.enabled:
            return
        trace = self._resolve(eval_id)
        if trace is not None:
            trace.annotate(attrs)

    # -- reads ---------------------------------------------------------

    def trace_id_of(self, eval_id: str) -> str:
        """Current trace id for an eval (newest generation), "" when
        untracked — the placement-explanation cross-link.  On a
        fan-out follower this resolves through the leased segment, so
        the link points at the leader's stitched trace."""
        trace = self._resolve(eval_id)
        return trace.trace_id if trace is not None else ""

    def get(self, ref: str) -> Optional[Dict]:
        """Resolve a bare eval id (newest generation) OR a full
        trace id (``<eval_id>#<gen>``, as listed by /v1/traces) —
        an id copied from the listing must dereference even after a
        redelivery superseded that generation."""
        trace = self._by_id.get(ref)
        if trace is not None:
            return trace.to_dict()
        if "#" in ref:
            with self._lock:
                candidates = list(self._ring)
            for trace in reversed(candidates):
                if trace.trace_id == ref:
                    return trace.to_dict()
        return None

    def recent(
        self,
        slow_ms: Optional[float] = None,
        outcome: Optional[str] = None,
        limit: int = 64,
        full: bool = False,
    ) -> List[Dict]:
        """Completed traces, newest first, optionally filtered to
        slow (>= slow_ms total) or outcome-matching ones."""
        with self._lock:
            candidates = list(self._ring)
        out: List[Dict] = []
        for trace in reversed(candidates):
            if not trace.finished:
                continue
            if outcome is not None and trace.outcome != outcome:
                continue
            if slow_ms is not None:
                dur = trace.duration_ms()
                if dur is None or dur < slow_ms:
                    continue
            out.append(trace.to_dict() if full else trace.summary())
            if len(out) >= limit:
                break
        return out

    def in_flight_ids(self, limit: int = 64) -> List[str]:
        """Eval ids with an open (unfinished) trace, newest first.

        The broadcast hook for cross-cutting marks: the overload
        ladder stamps ``overload.mode_change`` on every in-flight
        waterfall so the evals that RAN THROUGH a regime shift say
        so.  Bounded by ``limit`` — a broadcast must never turn a
        mode flip into an O(ring) stall."""
        if not self.enabled:
            return []
        with self._lock:
            candidates = list(self._ring)
        out: List[str] = []
        for trace in reversed(candidates):
            if trace.finished:
                continue
            out.append(trace.eval_id)
            if len(out) >= limit:
                break
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._by_id.clear()
            self._segments.clear()


TRACE = Tracer()


def cpu_ms_since(clock: Optional[float]) -> Optional[float]:
    """Thread CPU milliseconds since ``Tracer.cpu_clock`` read
    ``clock``; None when it read nothing."""
    if clock is None:
        return None
    return (time.thread_time() - clock) * 1000.0


def profiler_annotation(name: str):
    """A host stage on the JAX profiler's clock, under the span's own
    name (once a chunk or plan, never once a member): a profile of the
    server then holds the host stages beside the device's operations.
    A no-op where JAX was never imported (an oracle server)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name)


__all__ = [
    "CPU_LAYERS",
    "CPU_SAMPLE",
    "FOLD_COUNTERS",
    "FOLD_SAMPLE",
    "FOLD_SAMPLES",
    "LAYERS",
    "LAYER_OF",
    "MAX_SPANS",
    "SPAN_NAMES",
    "TRACE",
    "TRACE_RING",
    "Trace",
    "Tracer",
    "cpu_ms_since",
    "profiler_annotation",
]
