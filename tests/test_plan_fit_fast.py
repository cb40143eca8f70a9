"""The plan's per-node fit reads the store's live aggregate where the
network half of `allocs_fit` is vacuous, and walks the node's allocs
everywhere else: over random nodes and plans the fast verdict and `dim`
are the full walk's, a node or plan that carries a port, a network or
a device is never counted fast, and the four counters are
zero-registered and exported.
"""
import copy
import json
import random
import urllib.request

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server
from nomad_tpu.server.plan_apply import (
    FIT_COUNTERS,
    EvaluatePool,
    OptimisticState,
    PlanApplier,
    _node_verdict,
    evaluate_node_plan,
    evaluate_plan,
)
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.state.store import USAGE_COUNTERS, StateStore
from nomad_tpu.structs import (
    AllocatedDeviceResource,
    AllocatedResources,
    AllocatedSharedResources,
    AllocatedTaskResources,
    Allocation,
    AssignedPortData,
    NetworkResource,
    Plan,
    PlanResult,
    Port,
    allocs_fit,
)
from nomad_tpu.telemetry import Metrics

FAST, FULL = FIT_COUNTERS


def full_walk(store, plan, node_id):
    """`evaluate_node_plan` as it was before the aggregate: the oracle."""
    if not plan.node_allocation.get(node_id):
        return True, ""
    node = store.node_by_id(node_id)
    if node is None:
        return False, "node does not exist"
    if node.status != "ready":
        return False, "node is not ready for placements"
    if node.scheduling_eligibility != "eligible":
        return False, "node is not eligible"
    if node.drain:
        return False, "node is draining"
    proposed = [
        a for a in store.allocs_by_node(node_id) if not a.terminal_status()
    ]
    remove_ids = {a.id for a in plan.node_update.get(node_id, ())}
    remove_ids |= {a.id for a in plan.node_preemptions.get(node_id, ())}
    proposed = [a for a in proposed if a.id not in remove_ids]
    by_id = {a.id: a for a in proposed}
    for alloc in plan.node_allocation.get(node_id, ()):
        by_id[alloc.id] = alloc
    fit, dim, _util = allocs_fit(node, list(by_id.values()))
    return fit, dim


def carries(alloc) -> bool:
    ar = alloc.allocated_resources
    return ar is not None and bool(
        ar.shared.ports
        or ar.shared.networks
        or any(tr.networks or tr.devices for tr in ar.tasks.values())
    )


class World:
    """A small fleet with little room, so that plans overcommit it."""

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
            node.node_resources.cpu = rng.choice((1000, 2000))
            node.node_resources.memory_mb = rng.choice((1024, 2048))
            node.node_resources.disk_mb = rng.choice((1000, 2000))
            node.reserved_resources.cpu = 100
            node.reserved_resources.memory_mb = 64
            node.reserved_resources.disk_mb = 100
            if scenario == "node_states" and i < 4:
                what = i % 4
                if what == 0:
                    node.status = "down"
                elif what == 1:
                    node.scheduling_eligibility = "ineligible"
                elif what == 2:
                    node.drain = True
            if scenario == "ports" and i in (0, 1):
                # the node's OWN reserved ports: 0 collides, 1 does not
                node.reserved_resources.reserved_ports = (
                    [22, 22] if i == 0 else [22, 80]
                )
            if scenario == "ports" and i == 2:
                node.node_resources.networks = [
                    NetworkResource(
                        device="eth0", ip="10.0.0.2", mbits=1000,
                        reserved_ports=[Port("a", 53), Port("b", 53)],
                    )
                ]
            self.store.upsert_node(node)
            self.nodes.append(node)
        for _ in range(20):
            self.store.upsert_allocs(
                [self.alloc(rng.choice(self.nodes).id)]
            )

    def resources(self, kind: str) -> AllocatedResources:
        rng = self.rng
        heavy = self.scenario
        task = AllocatedTaskResources(
            cpu=rng.choice((100, 300, 900 if heavy == "cpu" else 200)),
            memory_mb=rng.choice(
                (64, 256, 1200 if heavy == "memory" else 128)
            ),
        )
        shared = AllocatedSharedResources(
            disk_mb=rng.choice((10, 100, 1200 if heavy == "disk" else 50))
        )
        if kind == "static":
            shared.ports = [
                AssignedPortData(label="svc", value=rng.choice((8080, 8081)))
            ]
        elif kind == "tasknet":
            task.networks = [
                NetworkResource(
                    mbits=5, reserved_ports=[Port("admin", 8080)]
                )
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
                kind = self.rng.choice(
                    ("static", "tasknet", "groupnet", "device")
                )
        return Allocation(
            id=f"fit-{self.serial:04d}",
            namespace="default",
            job_id="fit",
            task_group="web",
            name=f"fit.web[{self.serial}]",
            node_id=node_id,
            allocated_resources=self.resources(kind),
            desired_status="run",
            client_status="running",
        )

    def plan(self) -> Plan:
        rng = self.rng
        plan = Plan(eval_id="")
        for node in rng.sample(self.nodes, rng.randint(1, 5)):
            live = sorted(
                (
                    a for a in self.store.allocs_by_node(node.id)
                    if not a.terminal_status()
                ),
                key=lambda a: a.id,
            )
            placed = [
                self.alloc(node.id) for _ in range(rng.randint(0, 3))
            ]
            if self.scenario == "replace_by_id" and live:
                # an id already live on the node, placed again with
                # other resources: replaced, not doubled
                again = copy.copy(rng.choice(live))
                again.allocated_resources = self.resources("plain")
                placed.append(again)
                if rng.random() < 0.3:
                    placed.append(copy.copy(again))  # twice in one plan
            if placed and rng.random() < 0.1:
                done = self.alloc(node.id)
                done.client_status = "complete"  # placed terminal: no cost
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
        if rng.random() < 0.2:
            ghost = "no-such-node"
            plan.node_allocation[ghost] = [self.alloc(ghost)]
        return plan


SCENARIOS = (
    "cpu", "memory", "disk", "evict_place", "replace_by_id", "node_states",
    "ports",
)


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", [32, 972477786])
def test_fast_verdict_and_dim_are_the_full_walks(scenario, seed):
    w = World(seed, scenario)
    seen = set()
    hows = {"": 0, FAST: 0, FULL: 0}
    for _round in range(60):
        plan = w.plan()
        node_ids = (
            set(plan.node_allocation) | set(plan.node_update)
            | set(plan.node_preemptions)
        )
        for node_id in sorted(node_ids):
            want = full_walk(w.store, plan, node_id)
            fit, reason, how = _node_verdict(w.store, plan, node_id)
            assert (fit, reason) == want, (node_id, how)
            assert evaluate_node_plan(w.store, plan, node_id) == want
            seen.add(reason)
            hows[how] += 1
            if how:
                live = [
                    a for a in w.store.allocs_by_node(node_id)
                    if not a.terminal_status()
                ]
                networked = any(
                    carries(a)
                    for a in live + plan.node_allocation[node_id]
                )
                # never fast with a port, a network or a device in sight
                assert how == (FULL if networked else FAST)
        # commit what fits, as the applier would: the state moves on
        result, _full = evaluate_plan(w.store, plan)
        w.store.upsert_plan_results(result, "")
    assert "" in seen and hows[FAST] > 0
    if scenario in ("cpu", "memory", "disk"):
        assert scenario in seen
    if scenario == "node_states":
        assert {
            "node is not ready for placements", "node is not eligible",
            "node is draining",
        } <= seen
    if scenario == "ports":
        assert "reserved port collision" in seen and hows[FULL] > 0
    # one increment a node whose fit was computed: twice by the loop
    # above (evaluate_node_plan), once more by evaluate_plan, and
    # _node_verdict itself counts nothing
    counters = w.metrics.dump()["counters"]
    assert counters[FAST] >= 2 * hows[FAST]
    assert (counters.get(FULL, 0) > 0) == (hows[FULL] > 0)


def test_own_reserved_port_collision_is_the_nodes_answer_on_the_fast_side():
    """What is left of the network half with no alloc in it: the
    collision among the node's own reserved ports, after the
    dimensions, with `allocs_fit`'s string."""
    w = World(1, "ports")
    clash, clean = w.nodes[0], w.nodes[1]
    for node in (clash, clean):
        for a in w.store.allocs_by_node(node.id):
            gone = copy.copy(a)
            gone.desired_status = "stop"
            w.store.upsert_allocs([gone])
    plan = Plan(node_allocation={
        clash.id: [w.alloc(clash.id, "plain")],
        clean.id: [w.alloc(clean.id, "plain")],
    })
    assert _node_verdict(w.store, plan, clash.id) == (
        False, "reserved port collision", FAST
    )
    assert _node_verdict(w.store, plan, clean.id) == (True, "", FAST)
    big = w.alloc(clash.id, "plain")
    big.allocated_resources.tasks["web"].cpu = 10**6
    assert _node_verdict(
        w.store, Plan(node_allocation={clash.id: [big]}), clash.id
    ) == (False, "cpu", FAST)


@pytest.mark.parametrize("kind", ["static", "tasknet", "groupnet", "device"])
def test_a_carrier_on_the_node_or_in_the_plan_takes_the_full_walk(kind):
    w = World(3, "plain")
    node = w.nodes[7 if kind == "device" else 0]
    other = w.nodes[1]
    plain = Plan(node_allocation={
        node.id: [w.alloc(node.id, "plain")],
        other.id: [w.alloc(other.id, "plain")],
    })
    assert _node_verdict(w.store, plain, node.id)[2] == FAST
    # in the plan
    carrying = Plan(node_allocation={node.id: [w.alloc(node.id, kind)]})
    assert _node_verdict(w.store, carrying, node.id)[2] == FULL
    # live on the node
    resident = w.alloc(node.id, kind)
    w.store.upsert_allocs([resident])
    assert w.store.node_fit_usage(node.id) is None
    evaluate_plan(w.store, plain)
    counters = w.metrics.dump()["counters"]
    assert (counters[FAST], counters[FULL]) == (1.0, 1.0)
    # and fast again once it has stopped
    gone = copy.copy(resident)
    gone.client_status = "complete"
    w.store.upsert_allocs([gone])
    assert _node_verdict(w.store, plain, node.id)[2] == FAST


def test_a_node_under_an_in_flight_result_is_walked():
    """The pipeline's overlay: the store's sum knows nothing of a
    verified result whose commit is in flight."""
    w = World(4, "plain")
    a, b = w.nodes[0], w.nodes[1]
    in_flight = PlanResult(node_allocation={a.id: [w.alloc(a.id, "plain")]})
    in_flight.node_allocation[a.id][0].allocated_resources.tasks[
        "web"
    ].cpu = 10**6
    state = OptimisticState(w.store, [in_flight])
    plan = Plan(node_allocation={
        a.id: [w.alloc(a.id, "plain")], b.id: [w.alloc(b.id, "plain")],
    })
    assert _node_verdict(state, plan, a.id) == (False, "cpu", FULL)
    assert _node_verdict(state, plan, b.id)[2] == FAST
    assert _node_verdict(w.store, plan, a.id)[2] == FAST


@pytest.mark.parametrize("way", ["direct", "pipeline"])
def test_both_ways_into_the_applier_count_every_node(way):
    """`PlanApplier.apply` runs the per-node fit at the commit for every
    node of the plan, directly or through the pipeline's pool."""
    w = World(6, "plain")
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(
        w.store, queue, metrics=w.metrics, pool=EvaluatePool(2)
    )
    applier.start()
    try:
        plan = Plan(node_allocation={
            n.id: [w.alloc(n.id, "plain")] for n in w.nodes[:6]
        })
        if way == "direct":
            result = applier.apply(plan)
        else:
            result = queue.enqueue(plan).wait(timeout=10)
    finally:
        applier.stop()
    counters = w.metrics.dump()["counters"]
    assert counters["plan.direct" if way == "direct" else "plan.queued"] == 1
    assert counters[FAST] + counters[FULL] == 6
    assert counters[FAST] == 6
    assert len(result.node_allocation) <= 6


def test_the_four_counters_are_zero_registered_and_exported():
    from nomad_tpu.api import start_http_server

    srv = Server(num_schedulers=1, seed=32, batch_pipeline=False)
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
        for name in FIT_COUNTERS + USAGE_COUNTERS:
            assert first[name] == 0.0, name
        srv.register_node(mock.node())
        plain = mock.job()
        plain.task_groups[0].count = 2
        srv.register_job(plain)
        assert srv.drain_to_idle(15)
        after = counters()
        assert after[FAST] > 0 and after[FULL] == 0
        assert after["store.usage_delta"] >= 2
        # a static port in the plan, then live on the node: the walk
        ported = mock.job()
        ported.task_groups[0].count = 1
        ported.task_groups[0].networks = [
            NetworkResource(reserved_ports=[Port("svc", 8080)])
        ]
        srv.register_job(ported)
        assert srv.drain_to_idle(15)
        assert srv.store.live_port_nodes(8080)
        after = counters()
        assert after[FULL] > 0
        with urllib.request.urlopen(
            base + "/v1/metrics?format=prometheus", timeout=10
        ) as resp:
            text = resp.read().decode()
        for name in FIT_COUNTERS + USAGE_COUNTERS:
            assert name.replace(".", "_") in text
    finally:
        http.stop()
        srv.stop()
