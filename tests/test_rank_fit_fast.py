"""`BinPackIterator` computes an option's fit and score from the state's
live aggregate of the node where the network and device half of the
check is vacuous, and walks the node's proposed allocations everywhere
else: over random nodes, plans and asks the fast side's verdict, `dim`,
`util`, scores, metrics and task resources are the walk's (kept here as
the oracle), an ask, a node or a plan that carries a port, a network or
a device, an eviction pass and a state with no aggregate are never
counted fast, and the two counters are zero-registered, exported, and
cost at most two increments an eval.
"""
import copy
import json
import random
import urllib.request

import pytest

from nomad_tpu import mock
from nomad_tpu.sched.context import EvalContext
from nomad_tpu.sched.device import DeviceAllocator
from nomad_tpu.sched.rank import (
    FIT_COUNTERS,
    BinPackIterator,
    RankedNode,
    flush_fit_counts,
)
from nomad_tpu.sched.testing import Harness
from nomad_tpu.server import Server
from nomad_tpu.server.plan_apply import OptimisticState, evaluate_plan
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import (
    AllocatedDeviceResource,
    AllocatedResources,
    AllocatedSharedResources,
    AllocatedTaskResources,
    Allocation,
    AssignedPortData,
    NetworkIndex,
    NetworkResource,
    Plan,
    PlanResult,
    Port,
    RequestedDevice,
    allocs_fit,
    node_usage_after_plan,
    score_fit_binpack,
    score_fit_spread,
)
from nomad_tpu.structs.funcs import BINPACK_MAX_FIT_SCORE
from nomad_tpu.telemetry import Metrics

FAST, FULL = FIT_COUNTERS


def proposed_allocs(state, plan, node_id):
    """`EvalContext.proposed_allocs`, the oracle's own copy."""
    proposed = state.allocs_by_node_terminal(node_id, False)
    drop = {a.id for a in plan.node_update.get(node_id, ())}
    drop |= {a.id for a in plan.node_preemptions.get(node_id, ())}
    by_id = {a.id: a for a in proposed if a.id not in drop}
    for alloc in plan.node_allocation.get(node_id, ()):
        by_id[alloc.id] = alloc
    return list(by_id.values())


def walk(ctx, option, tg, score_fit):
    """`BinPackIterator.next` for one option with no eviction pass, as
    it was before the aggregate: the oracle.  Returns (option or None,
    dim, util)."""
    node = option.node
    proposed = proposed_allocs(ctx.state, ctx.plan, node.id)
    net_idx = NetworkIndex()
    net_idx.set_node(node)
    net_idx.add_allocs(proposed)
    dev_allocator = DeviceAllocator(ctx, node)
    dev_allocator.add_allocs(proposed)
    total = AllocatedResources(
        shared=AllocatedSharedResources(disk_mb=tg.ephemeral_disk.size_mb)
    )
    if tg.networks:
        ask = tg.networks[0].copy()
        offer = net_idx.assign_ports(ask)
        if offer is None:
            ctx.metrics.exhausted_node(node, "network: port collision")
            return None, "network: port collision", None
        net_idx.add_reserved_ports(offer)
        nw_res = NetworkResource(mode=ask.mode, mbits=ask.mbits)
        total.shared.networks = [nw_res]
        total.shared.ports = offer
        option.alloc_resources = AllocatedSharedResources(
            disk_mb=tg.ephemeral_disk.size_mb, networks=[nw_res],
            ports=offer,
        )
    for task in tg.tasks:
        task_resources = AllocatedTaskResources(
            cpu=task.resources.cpu, memory_mb=task.resources.memory_mb
        )
        if task.resources.networks:
            offer_net = net_idx.assign_network(
                task.resources.networks[0].copy()
            )
            if offer_net is None:
                ctx.metrics.exhausted_node(node, "network: port collision")
                return None, "network: port collision", None
            net_idx.add_reserved(offer_net)
            task_resources.networks = [offer_net]
        for req in task.resources.devices:
            offer_dev, _sum, err = dev_allocator.assign_device(req)
            if offer_dev is None:
                ctx.metrics.exhausted_node(node, f"devices: {err}")
                return None, f"devices: {err}", None
            dev_allocator.add_reserved(offer_dev)
            task_resources.devices.append(offer_dev)
        option.set_task_resources(task, task_resources)
        total.tasks[task.name] = task_resources
    probe = Allocation(allocated_resources=total)
    fit, dim, util = allocs_fit(node, proposed + [probe], net_idx, False)
    if not fit:
        ctx.metrics.exhausted_node(node, dim)
        return None, dim, util
    normalized = score_fit(node, util) / BINPACK_MAX_FIT_SCORE
    option.scores.append(normalized)
    ctx.metrics.score_node(node, "binpack", normalized)
    return option, "", util


class _One:
    """A rank source of one option."""

    def __init__(self, option) -> None:
        self.option = option

    def next(self):
        option, self.option = self.option, None
        return option


class NoAggregate:
    """A state that offers no live aggregate: every read but
    `node_fit_usage` is the wrapped state's."""

    def __init__(self, state) -> None:
        self._state = state

    def __getattr__(self, name):
        if name == "node_fit_usage":
            raise AttributeError(name)
        return getattr(self._state, name)


class NoTelemetry(NoAggregate):
    """And no `metrics` either (a test double's state)."""

    def __getattr__(self, name):
        if name == "metrics":
            raise AttributeError(name)
        return super().__getattr__(name)


def carries(alloc) -> bool:
    ar = alloc.allocated_resources
    return ar is not None and bool(
        ar.shared.ports
        or ar.shared.networks
        or any(tr.networks or tr.devices for tr in ar.tasks.values())
    )


class World:
    """A small fleet with little room, so that asks miss."""

    def __init__(self, seed: int, scenario: str) -> None:
        self.rng = rng = random.Random(seed)
        self.scenario = scenario
        self.store = StateStore()
        self.metrics = Metrics()
        self.store.attach_metrics(self.metrics)
        self.serial = 0
        self.nodes = []
        for i in range(8):
            node = mock.nvidia_node() if i == 7 else mock.node()
            node.node_class = rng.choice(("", "small", "large"))
            node.node_resources.cpu = rng.choice((1000, 2000))
            node.node_resources.memory_mb = rng.choice((1024, 2048))
            node.node_resources.disk_mb = rng.choice((1000, 2000))
            node.reserved_resources.cpu = 100
            node.reserved_resources.memory_mb = 64
            node.reserved_resources.disk_mb = 100
            if scenario == "ports" and i == 2:
                node.node_resources.networks = [
                    NetworkResource(
                        device="eth0", ip="10.0.0.2", mbits=1000,
                        reserved_ports=[Port("a", 53)],
                    )
                ]
            self.store.upsert_node(node)
            self.nodes.append(node)
        for _ in range(20):
            self.store.upsert_allocs(
                [self.alloc(rng.choice(self.nodes).id)]
            )
        # terminal ones on the nodes too: they cost nothing
        for _ in range(6):
            done = self.alloc(rng.choice(self.nodes).id, "plain")
            done.client_status = "complete"
            self.store.upsert_allocs([done])

    def resources(self, kind: str) -> AllocatedResources:
        rng = self.rng
        task = AllocatedTaskResources(
            cpu=rng.choice((100, 200, 300)),
            memory_mb=rng.choice((64, 128, 256)),
        )
        shared = AllocatedSharedResources(disk_mb=rng.choice((10, 50, 100)))
        if kind == "static":
            shared.ports = [
                AssignedPortData(label="svc", value=rng.choice((8080, 8081)))
            ]
        elif kind == "tasknet":
            task.networks = [
                NetworkResource(mbits=5, reserved_ports=[Port("admin", 8080)])
            ]
        elif kind == "groupnet":
            shared.networks = [
                NetworkResource(dynamic_ports=[Port("http", 20001)])
            ]
        elif kind == "device":
            task.devices = [
                AllocatedDeviceResource(
                    vendor="nvidia", type="gpu", name="1080ti",
                    device_ids=["gpu-0"],
                )
            ]
        return AllocatedResources(tasks={"web": task}, shared=shared)

    def alloc(self, node_id: str, kind=None) -> Allocation:
        self.serial += 1
        if kind is None:
            kind = "plain"
            if self.scenario == "ports" and self.rng.random() < 0.25:
                kind = self.rng.choice(("static", "tasknet", "groupnet"))
        return Allocation(
            id=f"rank-{self.serial:04d}",
            namespace="default",
            job_id="rank",
            task_group="web",
            name=f"rank.web[{self.serial}]",
            node_id=node_id,
            allocated_resources=self.resources(kind),
            desired_status="run",
            client_status="running",
        )

    def plan(self) -> Plan:
        """What earlier picks of an eval leave in `ctx.plan`: stops,
        preemptions, and allocs already placed — new ones, an id that
        is live on the node placed again, one placed twice, a terminal
        one."""
        rng = self.rng
        plan = Plan(eval_id="")
        for node in rng.sample(self.nodes, rng.randint(0, 5)):
            live = sorted(
                (
                    a for a in self.store.allocs_by_node(node.id)
                    if not a.terminal_status()
                ),
                key=lambda a: a.id,
            )
            placed = [self.alloc(node.id) for _ in range(rng.randint(0, 2))]
            if self.scenario == "replace_by_id" and live:
                again = copy.copy(rng.choice(live))
                again.allocated_resources = self.resources("plain")
                placed.append(again)
                if rng.random() < 0.3:
                    twice = copy.copy(again)
                    twice.allocated_resources = self.resources("plain")
                    placed.append(twice)
            if rng.random() < 0.15:
                done = self.alloc(node.id, "plain")
                done.client_status = "complete"
                placed.append(done)
            if placed:
                plan.node_allocation[node.id] = placed
            if self.scenario in ("evict_place", "replace_by_id") or (
                rng.random() < 0.3
            ):
                for victim in rng.sample(live, min(len(live), 2)):
                    stop = copy.copy(victim)
                    stop.desired_status = "stop"
                    which = (
                        plan.node_preemptions
                        if rng.random() < 0.4 else plan.node_update
                    )
                    which.setdefault(node.id, []).append(stop)
        return plan

    def room(self, plan: Plan, node) -> tuple:
        """(cpu, memory, disk) an ask may take on the node under the
        plan, by the oracle's sum."""
        used = [0, 0, 0]
        for a in proposed_allocs(self.store, plan, node.id):
            if not a.terminal_status():
                c = a.comparable_resources()
                used[0] += c.cpu
                used[1] += c.memory_mb
                used[2] += c.disk_mb
        total, reserved = node.node_resources, node.reserved_resources
        return (
            total.cpu - reserved.cpu - used[0],
            total.memory_mb - reserved.memory_mb - used[1],
            total.disk_mb - reserved.disk_mb - used[2],
        )

    def ask(self, plan: Plan, node, tasks: int = 1):
        """A job whose group asks cpu, memory and disk alone: small
        enough to fit, too large on the scenario's dimension, or (the
        `boundary` scenario) exactly the room there is, or one more."""
        rng = self.rng
        job = mock.job()
        tg = job.task_groups[0]
        cpu = rng.choice((50, 100, 250))
        mem = rng.choice((32, 64, 200))
        disk = rng.choice((10, 100, 300))
        if self.scenario == "boundary":
            room = self.room(plan, node)
            which = rng.randrange(3)
            over = rng.choice((0, 0, 1))
            if room[which] >= tasks:
                if which == 0:
                    cpu = room[0] + over
                elif which == 1:
                    mem = room[1] + over
                else:
                    disk = room[2] + over
        elif rng.random() < 0.4:
            if self.scenario == "cpu":
                cpu = rng.choice((900, 1500))
            elif self.scenario == "memory":
                mem = rng.choice((900, 1800))
            elif self.scenario == "disk":
                disk = rng.choice((900, 1800))
        tg.ephemeral_disk.size_mb = disk
        first = tg.tasks[0]
        tg.tasks = []
        for k in range(tasks):
            task = copy.deepcopy(first)
            task.name = f"web{k}"
            # the tasks' asks sum to (cpu, mem)
            task.resources.cpu = cpu // tasks + (cpu % tasks if k == 0 else 0)
            task.resources.memory_mb = (
                mem // tasks + (mem % tasks if k == 0 else 0)
            )
            tg.tasks.append(task)
        return job, tg


def rank_one(state, plan, node, job, tg, evict=False, algorithm="binpack"):
    """One option through the program's iterator on a context of its
    own: (option or None, ctx, util the score was computed from, the
    RankedNode handed in)."""
    ctx = EvalContext(state, plan, seed=1)
    ranked = RankedNode(node=node)
    it = BinPackIterator(ctx, _One(ranked), evict, job.priority, algorithm)
    it.set_job(job)
    it.set_task_group(tg)
    seen = []
    score_fit = it.score_fit

    def recording(n, util):
        seen.append(copy.copy(util))
        return score_fit(n, util)

    it.score_fit = recording
    option = it.next()
    return option, ctx, (seen[0] if seen else None), ranked


SCENARIOS = (
    "cpu", "memory", "disk", "boundary", "evict_place", "replace_by_id",
    "ports",
)


@pytest.mark.parametrize("algorithm", ["binpack", "spread"])
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", [34, 972477786])
def test_the_fast_side_is_the_walk(scenario, seed, algorithm):
    w = World(seed, scenario)
    snap = w.store.snapshot()
    score_fit = score_fit_spread if algorithm == "spread" else score_fit_binpack
    sides = {FAST: 0, FULL: 0}
    dims = set()
    fitted = exact = 0
    for _round in range(40):
        plan = w.plan()
        for node in w.nodes:
            job, tg = w.ask(plan, node, tasks=w.rng.choice((1, 1, 2)))
            ask = (
                sum(t.resources.cpu for t in tg.tasks),
                sum(t.resources.memory_mb for t in tg.tasks),
                tg.ephemeral_disk.size_mb,
            )
            # the oracle, on a context of its own
            want_ctx = EvalContext(snap, plan, seed=1)
            want_ranked = RankedNode(node=node)
            want, dim, want_util = walk(want_ctx, want_ranked, tg, score_fit)
            got, ctx, util, ranked = rank_one(
                snap, plan, node, job, tg, algorithm=algorithm
            )
            assert (got is None) == (want is None), (node.id, dim)
            assert ctx.metrics == want_ctx.metrics, (node.id, dim)
            assert ctx.metrics.dimension_exhausted == (
                {dim: 1} if dim else {}
            )
            assert ranked.scores == want_ranked.scores
            assert ranked.task_resources == want_ranked.task_resources
            assert ranked.alloc_resources == want_ranked.alloc_resources
            if got is not None:
                assert got is ranked
                assert util == want_util  # cpu, memory_mb, disk_mb
                fitted += 1
                # sits exactly on a boundary: no room left on a dimension
                exact += min(
                    r - a for r, a in zip(w.room(plan, node), ask)
                ) == 0
            dims.add(dim)
            # which side: never fast with a carrier live on the node or
            # placed there by the plan
            networked = any(
                carries(a)
                for a in snap.allocs_by_node_terminal(node.id, False)
                + list(plan.node_allocation.get(node.id, ()))
            )
            assert (ctx.fit_fast, ctx.fit_full) == (
                (0, 1) if networked else (1, 0)
            ), node.id
            sides[FULL if networked else FAST] += 1
            # the sum both readers share, against the oracle's
            used = node_usage_after_plan(snap, plan, node.id)
            assert (used is None) == networked
            if not networked:
                assert used == (
                    want_util.cpu - ask[0], want_util.memory_mb - ask[1],
                    want_util.disk_mb - ask[2],
                )
        # commit what fits, as the applier would: the state moves on
        result, _full = evaluate_plan(w.store, plan)
        w.store.upsert_plan_results(result, "")
    assert "" in dims and sides[FAST] > 0 and fitted > 0
    if scenario in ("cpu", "memory", "disk"):
        assert scenario in dims
    if scenario == "boundary":
        assert {"cpu", "memory", "disk"} <= dims and exact > 0
    if scenario == "ports":
        assert sides[FULL] > 0


def _plain_world():
    w = World(3, "plain")
    return w, w.store.snapshot()


def _carrier_on_node(kind):
    w, snap = _plain_world()
    node = w.nodes[7 if kind == "device" else 0]
    w.store.upsert_allocs([w.alloc(node.id, kind)])
    job, tg = w.ask(Plan(eval_id=""), node)
    return snap, Plan(eval_id=""), node, job, tg, False


def _carrier_in_plan(kind):
    w, snap = _plain_world()
    node = w.nodes[7 if kind == "device" else 0]
    plan = Plan(node_allocation={node.id: [w.alloc(node.id, kind)]})
    job, tg = w.ask(plan, node)
    return snap, plan, node, job, tg, False


def _ask_group_network():
    w, snap = _plain_world()
    job, tg = w.ask(Plan(eval_id=""), w.nodes[0])
    tg.networks = [NetworkResource(dynamic_ports=[Port("http", 0)])]
    return snap, Plan(eval_id=""), w.nodes[0], job, tg, False


def _ask_task_network():
    w, snap = _plain_world()
    job, tg = w.ask(Plan(eval_id=""), w.nodes[0])
    tg.tasks[0].resources.networks = [
        NetworkResource(mbits=5, reserved_ports=[Port("admin", 8080)])
    ]
    return snap, Plan(eval_id=""), w.nodes[0], job, tg, False


def _ask_device():
    w, snap = _plain_world()
    job, tg = w.ask(Plan(eval_id=""), w.nodes[7])
    tg.tasks[0].resources.devices = [RequestedDevice(name="nvidia/gpu")]
    return snap, Plan(eval_id=""), w.nodes[7], job, tg, False


def _evict_pass():
    w, snap = _plain_world()
    job, tg = w.ask(Plan(eval_id=""), w.nodes[0])
    return snap, Plan(eval_id=""), w.nodes[0], job, tg, True


def _no_aggregate():
    w, snap = _plain_world()
    job, tg = w.ask(Plan(eval_id=""), w.nodes[0])
    return NoAggregate(snap), Plan(eval_id=""), w.nodes[0], job, tg, False


def _overlay_touches_the_node():
    # the applier's optimistic view: a node an in-flight result
    # touches has no aggregate
    w, _snap = _plain_world()
    node = w.nodes[0]
    result = PlanResult(
        node_update={}, node_preemptions={},
        node_allocation={node.id: [w.alloc(node.id, "plain")]},
    )
    job, tg = w.ask(Plan(eval_id=""), node)
    state = OptimisticState(w.store, [result])
    return state, Plan(eval_id=""), node, job, tg, False


NEVER_FAST = {
    "static port on the node": lambda: _carrier_on_node("static"),
    "task network on the node": lambda: _carrier_on_node("tasknet"),
    "group network on the node": lambda: _carrier_on_node("groupnet"),
    "device on the node": lambda: _carrier_on_node("device"),
    "static port in the plan": lambda: _carrier_in_plan("static"),
    "task network in the plan": lambda: _carrier_in_plan("tasknet"),
    "group network in the plan": lambda: _carrier_in_plan("groupnet"),
    "device in the plan": lambda: _carrier_in_plan("device"),
    "the group asks a network": _ask_group_network,
    "a task asks a network": _ask_task_network,
    "a task asks a device": _ask_device,
    "an eviction pass": _evict_pass,
    "a state with no aggregate": _no_aggregate,
    "an in-flight result touches the node": _overlay_touches_the_node,
}


@pytest.mark.parametrize("case", sorted(NEVER_FAST))
def test_never_counted_fast(case):
    state, plan, node, job, tg, evict = NEVER_FAST[case]()
    got, ctx, util, ranked = rank_one(state, plan, node, job, tg, evict=evict)
    assert (ctx.fit_fast, ctx.fit_full) == (0, 1)
    # and the walk's answer is the oracle's (no eviction pass there)
    want_ctx = EvalContext(state, plan, seed=1)
    want_ranked = RankedNode(node=node)
    want, _dim, want_util = walk(want_ctx, want_ranked, tg, score_fit_binpack)
    assert (got is None) == (want is None)
    assert ranked.scores == want_ranked.scores
    assert ranked.alloc_resources == want_ranked.alloc_resources
    if case != "a task asks a device":  # the offer's ids are drawn
        assert ranked.task_resources == want_ranked.task_resources
    if got is not None:
        assert util == want_util
        assert ctx.metrics == want_ctx.metrics


def test_the_same_option_is_fast_where_nothing_is_in_the_way():
    w, snap = _plain_world()
    node = w.nodes[0]
    job, tg = w.ask(Plan(eval_id=""), node)
    _got, ctx, _util, _ranked = rank_one(snap, Plan(eval_id=""), node, job, tg)
    assert (ctx.fit_fast, ctx.fit_full) == (1, 0)
    # a stack's iterator flips to its eviction pass and back per select
    ranked = RankedNode(node=node)
    it = BinPackIterator(ctx, _One(ranked), False, 50, "binpack")
    it.set_job(job)
    it.set_task_group(tg)
    it.evict = True
    it.next()
    assert (ctx.fit_fast, ctx.fit_full) == (1, 1)


def test_counts_reach_the_telemetry_once_an_eval():
    """Plain integers a pick; `flush_fit_counts` makes at most one
    increment a side, and zeroes them."""
    w, snap = _plain_world()
    calls = []
    incr = w.metrics.incr
    w.metrics.incr = lambda name, value=1.0: (
        calls.append((name, value)), incr(name, value)
    )
    ctx = EvalContext(snap, Plan(eval_id=""), seed=1)
    ctx.fit_fast, ctx.fit_full = 10, 0
    flush_fit_counts(ctx)
    assert calls == [(FAST, 10)]
    ctx.fit_fast, ctx.fit_full = 3, 2
    flush_fit_counts(ctx)
    assert calls[1:] == [(FAST, 3), (FULL, 2)]
    assert (ctx.fit_fast, ctx.fit_full) == (0, 0)
    flush_fit_counts(ctx)
    assert len(calls) == 3
    counters = w.metrics.dump()["counters"]
    assert (counters[FAST], counters[FULL]) == (13.0, 2.0)
    # a state with no telemetry: dropped, not raised
    bare = EvalContext(NoTelemetry(snap), Plan(eval_id=""), seed=1)
    bare.fit_full = 1
    flush_fit_counts(bare)
    assert (bare.fit_fast, bare.fit_full) == (0, 0)


@pytest.mark.parametrize("use_tpu", [False, True])
def test_a_scheduled_eval_flushes_its_counts(use_tpu):
    """Every stack runs the one iterator: the sequential scheduler's
    scored nodes, and the device stack's verified winners, of a plain
    job are counted fast, in at most two increments an eval."""
    from nomad_tpu.sched.generic_sched import GenericScheduler

    h = Harness()
    metrics = Metrics()
    h.store.attach_metrics(metrics)
    for _ in range(10):
        h.store.upsert_node(mock.node())
    job = mock.job()
    job.task_groups[0].count = 4
    h.store.upsert_job(job)
    ev = mock.evaluation(job_id=job.id, triggered_by="job-register")
    h.store.upsert_evals([ev])
    calls = []
    incr = metrics.incr
    metrics.incr = lambda name, value=1.0: (
        calls.append(name), incr(name, value)
    )
    h.process(
        lambda state, planner: GenericScheduler(
            state, planner, batch=False, use_tpu=use_tpu, seed=34
        ),
        ev,
    )
    assert len(h.plans) == 1
    assert sum(len(v) for v in h.plans[0].node_allocation.values()) == 4
    counters = metrics.dump()["counters"]
    assert counters.get(FAST, 0) >= 4
    assert counters.get(FULL, 0) == 0
    assert [n for n in calls if n in FIT_COUNTERS] == [FAST]


def test_counters_are_zero_registered_and_exported():
    from nomad_tpu.api import start_http_server

    srv = Server(num_schedulers=1, seed=34, batch_pipeline=False)
    srv.start()
    http = start_http_server(srv, port=0)
    try:
        base = f"http://127.0.0.1:{http.port}"

        def counters():
            with urllib.request.urlopen(
                base + "/v1/metrics", timeout=10
            ) as resp:
                return json.loads(resp.read())["counters"]

        first = counters()
        for name in FIT_COUNTERS:
            assert first[name] == 0.0, name
        srv.register_node(mock.node())
        plain = mock.job()
        plain.task_groups[0].count = 2
        srv.register_job(plain)
        assert srv.drain_to_idle(15)
        after = counters()
        assert after[FAST] >= 2 and after[FULL] == 0
        # an ask with a static port: the walk
        ported = mock.job()
        ported.task_groups[0].count = 1
        ported.task_groups[0].networks = [
            NetworkResource(reserved_ports=[Port("svc", 8080)])
        ]
        srv.register_job(ported)
        assert srv.drain_to_idle(15)
        after = counters()
        assert after[FULL] > 0
        with urllib.request.urlopen(
            base + "/v1/metrics?format=prometheus", timeout=10
        ) as resp:
            text = resp.read().decode()
        for name in FIT_COUNTERS:
            assert name.replace(".", "_") in text
    finally:
        http.stop()
        srv.stop()
