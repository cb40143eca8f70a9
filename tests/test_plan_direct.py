"""The plan applier's direct path: a plan that finds the applier idle
is verified and committed on its submitter's thread, one that finds it
busy takes the pipeline, and at no instant do both work
(`PlanApplier.apply`).  The pipeline's own tests are in
tests/test_server.py and tests/test_stress.py.
"""
import copy
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.raft import NotLeaderError
from nomad_tpu.server.plan_apply import PLAN_COUNTERS, PlanApplier
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    AllocatedResources,
    AllocatedTaskResources,
    Deployment,
    DeploymentStatusUpdate,
    Plan,
    allocs_fit,
)
from nomad_tpu.telemetry import Metrics


def _resources(cpu, mem):
    return AllocatedResources(
        tasks={"t": AllocatedTaskResources(cpu=cpu, memory_mb=mem)}
    )


def _big(node):
    """An alloc of which one fits a mock node and two do not."""
    alloc = mock.alloc(node_id=node.id)
    alloc.allocated_resources = _resources(3000, 6000)
    return alloc


class _RecordingStore:
    """Store facade standing in for a replicated store: injected
    apply/read latency, injected apply failures, and a record of who
    read and wrote when — commits must never overlap, whichever thread
    makes them."""

    def __init__(self, store, apply_latency=0.0, read_latency=0.0,
                 fail_applies=0):
        self._store = store
        self.apply_latency = apply_latency
        self.read_latency = read_latency
        self.fail_applies = fail_applies
        self.applies = 0
        self.in_apply = 0
        self.overlapped = 0
        # (thread name, start, end) of every commit; (thread name,
        # instant) of every verification read
        self.commits = []
        self.reads = []
        self.entered = threading.Event()
        self._mu = threading.Lock()

    def __getattr__(self, name):
        return getattr(self._store, name)

    def _read(self):
        with self._mu:
            self.reads.append(
                (threading.current_thread().name, time.monotonic())
            )
        if self.read_latency:
            time.sleep(self.read_latency)

    # the verification's read of a node, whichever side the fit takes
    def allocs_by_node(self, node_id):
        self._read()
        return self._store.allocs_by_node(node_id)

    def node_fit_usage(self, node_id):
        self._read()
        return self._store.node_fit_usage(node_id)

    def upsert_plan_results(self, result, eval_id=""):
        start = time.monotonic()
        with self._mu:
            self.in_apply += 1
            if self.in_apply > 1:
                self.overlapped += 1
            self.applies += 1
            fail = self.applies <= self.fail_applies
        self.entered.set()
        try:
            if self.apply_latency:
                time.sleep(self.apply_latency)
            if fail:
                raise RuntimeError("injected apply failure")
            return self._store.upsert_plan_results(result, eval_id)
        finally:
            with self._mu:
                self.in_apply -= 1
                self.commits.append(
                    (threading.current_thread().name, start,
                     time.monotonic())
                )


def _applier(store, start=True, **kwargs):
    queue = PlanQueue()
    queue.set_enabled(True)
    metrics = Metrics()
    applier = PlanApplier(store, queue, metrics=metrics, **kwargs)
    if start:
        applier.start()
    return queue, applier, metrics


def _paths(metrics):
    return tuple(metrics.get_counter(name) for name in PLAN_COUNTERS)


# ---------------------------------------------------------------------------
# (a) equivalence: the same plans leave the same state by either path
# ---------------------------------------------------------------------------


def _world():
    """Four nodes — the third full, the fourth holding an alloc to
    evict — a running deployment, and one plan of each kind."""
    nodes = [mock.node() for _ in range(4)]
    filler = mock.alloc(node_id=nodes[2].id)
    filler.allocated_resources = _resources(3900, 7900)
    victim = mock.alloc(node_id=nodes[3].id)
    running = Deployment(job_id="web")
    evict = Plan(eval_id="ev-evict")
    evict.append_stopped_alloc(victim, "evicted by test")
    plans = {
        "full_fit": Plan(
            eval_id="ev-full",
            node_allocation={
                nodes[0].id: [mock.alloc(node_id=nodes[0].id)],
                nodes[1].id: [mock.alloc(node_id=nodes[1].id)],
            },
        ),
        "partial_fit": Plan(
            eval_id="ev-partial",
            node_allocation={
                nodes[0].id: [mock.alloc(node_id=nodes[0].id)],
                nodes[2].id: [mock.alloc(node_id=nodes[2].id)],
            },
            deployment=Deployment(job_id="dropped-with-the-partial"),
        ),
        "all_at_once_reject": Plan(
            eval_id="ev-aao",
            all_at_once=True,
            node_allocation={
                nodes[1].id: [mock.alloc(node_id=nodes[1].id)],
                nodes[2].id: [mock.alloc(node_id=nodes[2].id)],
            },
        ),
        "evict_only": evict,
        "deployment_update": Plan(
            eval_id="ev-deploy",
            node_allocation={
                nodes[1].id: [mock.alloc(node_id=nodes[1].id)],
            },
            deployment=Deployment(job_id="api"),
            deployment_updates=[
                DeploymentStatusUpdate(
                    deployment_id=running.id,
                    status="successful",
                    status_description="done",
                )
            ],
        ),
    }
    return nodes, [filler, victim], running, plans


def _store_of(world):
    nodes, allocs, running, _plans = copy.deepcopy(world)
    store = StateStore()
    for node in nodes:
        store.upsert_node(node)
    store.upsert_allocs(allocs)
    store.upsert_deployment(running)
    return store


def _state_of(store):
    return (
        store.latest_index(),
        dict(store.allocs),
        dict(store.deployments),
    )


@pytest.mark.parametrize(
    "kinds",
    [
        ("full_fit",),
        ("partial_fit",),
        ("all_at_once_reject",),
        ("evict_only",),
        ("deployment_update",),
        (
            "full_fit", "partial_fit", "all_at_once_reject",
            "evict_only", "deployment_update", "full_fit",
        ),
    ],
    ids=lambda kinds: "+".join(kinds),
)
def test_direct_apply_leaves_what_the_pipeline_leaves(kinds):
    """The same plans in the same order through `apply` on an idle
    applier and through enqueue/wait: identical results, alloc and
    deployment tables and store indexes."""
    world = _world()

    def run(direct):
        store = _store_of(world)
        plans = copy.deepcopy(world[3])
        queue, applier, metrics = _applier(store)
        results = []
        try:
            for kind in kinds:
                plan = copy.deepcopy(plans[kind])
                if direct:
                    results.append(applier.apply(plan))
                else:
                    results.append(queue.enqueue(plan).wait(timeout=5))
        finally:
            applier.stop()
        return results, _state_of(store), _paths(metrics)

    direct, direct_state, direct_paths = run(True)
    piped, piped_state, piped_paths = run(False)
    assert direct == piped
    assert direct_state == piped_state
    assert direct_paths == (float(len(kinds)), 0.0)
    # a plan enqueued past `apply` is counted where the verifier
    # takes it in hand
    assert piped_paths == (0.0, float(len(kinds)))
    # and the kinds did what their names say
    by_kind = dict(zip(kinds, direct))
    if "full_fit" in by_kind:
        assert len(by_kind["full_fit"].node_allocation) == 2
        assert not by_kind["full_fit"].refresh_index
    if "partial_fit" in by_kind:
        partial = by_kind["partial_fit"]
        assert len(partial.node_allocation) == 1
        assert partial.refresh_index and partial.deployment is None
    if "all_at_once_reject" in by_kind:
        rejected = by_kind["all_at_once_reject"]
        assert not rejected.node_allocation and rejected.refresh_index
        assert not rejected.alloc_index  # nothing reached the store
    if "evict_only" in by_kind:
        assert by_kind["evict_only"].node_update
    if "deployment_update" in by_kind:
        running = world[2]
        assert direct_state[2][running.id].status == "successful"
        assert len(direct_state[2]) >= 2


# ---------------------------------------------------------------------------
# (b) exclusion: the pipeline works, or one direct apply does
# ---------------------------------------------------------------------------


def test_racing_submitters_never_overlap_a_commit_or_overcommit():
    """8 threads submit 200 plans that race for the same node slots:
    every plan is answered once, by one path or the other; commits
    come strictly one at a time whichever thread makes them; no node
    ends up holding more than fits."""
    store = StateStore()
    nodes = [mock.node() for _ in range(5)]
    for node in nodes:
        store.upsert_node(node)
    slow = _RecordingStore(store, apply_latency=0.002)
    _queue, applier, metrics = _applier(slow)
    threads_n, plans_each = 8, 25
    answers = []
    answers_mu = threading.Lock()
    gate = threading.Barrier(threads_n)

    def submitter(seed):
        gate.wait()
        for i in range(plans_each):
            node = nodes[(seed + i) % len(nodes)]
            alloc = mock.alloc(node_id=node.id)
            # two fit a node, a third does not: plenty of conflicts
            alloc.allocated_resources = _resources(1500, 3000)
            plan = Plan(node_allocation={node.id: [alloc]})
            try:
                answer = applier.apply(plan, timeout=30)
            except Exception as exc:  # noqa: BLE001
                answer = exc
            with answers_mu:
                answers.append((alloc.id, answer))

    threads = [
        threading.Thread(target=submitter, args=(s,))
        for s in range(threads_n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    applier.stop()
    assert all(not t.is_alive() for t in threads), "submitter hung"
    total = threads_n * plans_each
    assert len(answers) == total
    assert len({alloc_id for alloc_id, _a in answers}) == total
    assert not [a for _i, a in answers if isinstance(a, Exception)]
    direct, queued = _paths(metrics)
    assert direct + queued == total
    # the first plan found the applier idle, its racers found it busy
    assert direct >= 1 and queued >= 1
    assert slow.overlapped == 0
    spans = sorted((s, e) for _t, s, e in slow.commits)
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))
    committers = {name for name, _s, _e in slow.commits}
    assert "plan-applier" in committers and len(committers) > 1
    placed = 0
    for node in nodes:
        live = [
            a for a in store.allocs_by_node(node.id)
            if not a.terminal_status()
        ]
        fit, dim, _util = allocs_fit(node, live)
        assert fit, (node.id, dim)
        placed += len(live)
    committed = sum(
        1 for _i, a in answers if a.node_allocation
    )
    assert placed == committed == 2 * len(nodes)


def test_plan_enqueued_during_a_direct_apply_is_verified_after_it():
    """A plan that arrives while a direct apply is in the store queues,
    and the verifier does not begin it until the applier is released:
    it sees the direct plan's placement and loses the slot."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(node)
    slow = _RecordingStore(store, apply_latency=0.3)
    _queue, applier, metrics = _applier(slow)
    first, second = _big(node), _big(node)
    results = {}

    def submit(key, alloc):
        results[key] = applier.apply(
            Plan(node_allocation={node.id: [alloc]})
        )

    a = threading.Thread(target=submit, args=("first", first), name="sub-a")
    b = threading.Thread(target=submit, args=("second", second), name="sub-b")
    try:
        a.start()
        assert slow.entered.wait(5)  # the direct apply is in the store
        b.start()
        a.join(timeout=5)
        b.join(timeout=5)
    finally:
        applier.stop()
    assert results["first"].node_allocation
    assert not results["second"].node_allocation
    assert results["second"].refresh_index > 0
    assert _paths(metrics) == (1.0, 1.0)
    (direct_commit,) = [c for c in slow.commits if c[0] == "sub-a"]
    verifier_reads = [t for name, t in slow.reads if name == "plan-verifier"]
    assert verifier_reads, "the queued plan was never verified"
    assert min(verifier_reads) >= direct_commit[2]
    assert applier.overlap_verifies == 0  # nothing was in flight by then
    live = [
        x for x in store.allocs_by_node(node.id)
        if not x.terminal_status()
    ]
    assert [x.id for x in live] == [first.id]


def test_concurrent_submitters_keep_the_pipeline_and_its_overlay():
    """Submitters that overlap find the applier busy: their plans take
    the pipeline, later ones verified on the overlay of earlier ones
    still committing.  Nothing was taken from the busy path."""
    store = StateStore()
    nodes = [mock.node() for _ in range(4)]
    for node in nodes:
        store.upsert_node(node)
    slow = _RecordingStore(store, apply_latency=0.1)
    _queue, applier, metrics = _applier(slow)
    results = []

    def submit(node):
        results.append(
            applier.apply(
                Plan(node_allocation={node.id: [mock.alloc(node_id=node.id)]})
            )
        )

    threads = [threading.Thread(target=submit, args=(n,)) for n in nodes]
    try:
        threads[0].start()
        assert slow.entered.wait(5)
        for t in threads[1:]:
            t.start()
        for t in threads:
            t.join(timeout=10)
    finally:
        applier.stop()
    assert len(results) == 4 and all(r.node_allocation for r in results)
    assert _paths(metrics) == (1.0, 3.0)
    assert applier.overlap_verifies >= 1
    assert slow.overlapped == 0


# ---------------------------------------------------------------------------
# (c) fences: leadership, the queue's switch, stop()
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "lost_at", ["before_verify", "between_verify_and_commit"]
)
def test_direct_apply_is_fenced_where_the_pipeline_is(lost_at):
    """Leadership lost before the verification, or between it and the
    commit: NotLeaderError, and nothing written."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(node)
    slow = _RecordingStore(store)
    checks = []

    def leader_check():
        checks.append(len(checks))
        return lost_at != "before_verify" and len(checks) < 2

    _queue, applier, metrics = _applier(slow, leader_check=leader_check)
    index = store.latest_index()
    try:
        with pytest.raises(NotLeaderError):
            applier.apply(
                Plan(
                    eval_id="ev-fence",
                    node_allocation={
                        node.id: [mock.alloc(node_id=node.id)]
                    },
                )
            )
        assert slow.applies == 0 and store.latest_index() == index
        assert not store.allocs_by_node(node.id)
        # verified only where leadership outlived the first fence
        assert bool(slow.reads) == (lost_at != "before_verify")
        assert metrics.get_counter("leadership.plan_rejected") == 1.0
        assert _paths(metrics) == (1.0, 0.0)
        # the applier was let go: the pipeline can have the next plan
        assert applier._direct is False
    finally:
        applier.stop()


def test_apply_on_a_disabled_queue_is_refused():
    """Revoking leadership disables the plan queue; a later submit
    raises NotLeaderError by either path, and counts as neither."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(node)
    queue, applier, metrics = _applier(store)
    plan = Plan(node_allocation={node.id: [mock.alloc(node_id=node.id)]})
    try:
        assert applier.apply(copy.deepcopy(plan)).node_allocation
        queue.set_enabled(False)
        index = store.latest_index()
        with pytest.raises(NotLeaderError):
            applier.apply(copy.deepcopy(plan))
        assert store.latest_index() == index
        assert _paths(metrics) == (1.0, 0.0)
    finally:
        applier.stop()


def test_stop_waits_for_a_direct_apply_and_fences_the_next():
    """stop() does not return while a direct apply of its generation
    is in the store, and a plan that claims the stopped applier (its
    queue not yet disabled) is refused at the first fence."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(node)
    slow = _RecordingStore(store, apply_latency=0.4)
    _queue, applier, _metrics = _applier(slow)
    done = []

    def submit():
        done.append(
            applier.apply(
                Plan(node_allocation={node.id: [mock.alloc(node_id=node.id)]})
            )
        )

    t = threading.Thread(target=submit, name="sub-slow")
    t.start()
    assert slow.entered.wait(5)
    t0 = time.monotonic()
    applier.stop()
    stopped = time.monotonic()
    # the commit had returned (and released the applier) by then
    (commit,) = slow.commits
    assert commit[0] == "sub-slow" and commit[2] <= stopped
    assert stopped - t0 >= 0.2
    t.join(timeout=5)
    assert done and done[0].node_allocation
    index = store.latest_index()
    with pytest.raises(NotLeaderError):
        applier.apply(
            Plan(node_allocation={node.id: [mock.alloc(node_id=node.id)]})
        )
    assert slow.applies == 1 and store.latest_index() == index
    # a new generation serves again
    applier.start()
    try:
        assert applier.apply(
            Plan(node_allocation={node.id: [mock.alloc(node_id=node.id)]})
        ).node_allocation
    finally:
        applier.stop()


def test_apply_needs_no_pipeline_thread():
    """`apply` on an applier that was never started (tooling, tests)
    is the same direct path: fenced, counted, committed."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(node)
    _queue, applier, metrics = _applier(store, start=False)
    result = applier.apply(
        Plan(node_allocation={node.id: [mock.alloc(node_id=node.id)]})
    )
    assert result.alloc_index == store.latest_index()
    assert applier.applied == 1
    assert _paths(metrics) == (1.0, 0.0)
    assert metrics.dump()["samples"]["plan.evaluate"]["count"] == 1
    assert metrics.dump()["samples"]["plan.apply"]["count"] == 1


# ---------------------------------------------------------------------------
# (d) a failed direct apply
# ---------------------------------------------------------------------------


def test_failed_direct_apply_bumps_the_epoch_and_frees_the_applier():
    """An apply that raises on the submitter's thread reaches the
    submitter as raised, invalidates optimistic verifications like a
    failed pipeline apply, and lets the applier go."""
    store = StateStore()
    node = mock.node()
    store.upsert_node(node)
    slow = _RecordingStore(store, fail_applies=1)
    _queue, applier, metrics = _applier(slow)
    first, second = _big(node), _big(node)
    try:
        epoch = applier._epoch
        with pytest.raises(RuntimeError, match="injected"):
            applier.apply(Plan(node_allocation={node.id: [first]}))
        assert applier._epoch == epoch + 1
        assert applier._direct is False and not applier._inflight
        # the slot the failed plan would have taken is free
        result = applier.apply(Plan(node_allocation={node.id: [second]}))
        assert result.node_allocation
        assert _paths(metrics) == (2.0, 0.0)
    finally:
        applier.stop()
    live = [
        a for a in store.allocs_by_node(node.id) if not a.terminal_status()
    ]
    assert [a.id for a in live] == [second.id]


def test_plan_counters_are_registered_at_zero():
    metrics = Metrics()
    PlanApplier(StateStore(), PlanQueue(), metrics=metrics)
    counters = metrics.dump()["counters"]
    assert [counters[name] for name in PLAN_COUNTERS] == [0.0, 0.0]
