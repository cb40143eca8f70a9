"""The store keeps each node's live usage and port / device holders by
delta; the full recompute stays as the oracle.

Seeded random sequences of alloc writes over a small fleet, one
parametrised case a writer (and one of all of them mixed); some allocs
hold static ports, task networks and devices.  After EVERY write, for
every node: the node table's usage row equals `_live_usage_for_node`,
the live aggregate the plan's fit reads equals a recount from nothing,
and the port / device index equals a full recount written here.
"""
import copy
import random

import pytest

from nomad_tpu import mock
from nomad_tpu.sched.core_sched import CoreScheduler
from nomad_tpu.server.fsm import install_payload, state_payload
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import (
    AllocatedDeviceResource,
    AllocatedResources,
    AllocatedSharedResources,
    AllocatedTaskResources,
    Allocation,
    AssignedPortData,
    Evaluation,
    NetworkResource,
    PlanResult,
    Port,
)
from nomad_tpu.structs.network import MIN_DYNAMIC_PORT
from nomad_tpu.telemetry import Metrics

N_NODES = 6
STEPS = 120


# ---------------------------------------------------------------------------
# the oracle: everything the store derives from its allocs, from nothing
# ---------------------------------------------------------------------------


def _carries(alloc) -> bool:
    ar = alloc.allocated_resources
    if ar is None:
        return False
    return bool(
        ar.shared.ports
        or ar.shared.networks
        or any(tr.networks or tr.devices for tr in ar.tasks.values())
    )


def check(store: StateStore) -> None:
    table = store.node_table
    ports: dict = {}
    devices: dict = {}
    node_ids = set(store.nodes) | {
        n for n, ids in store._allocs_by_node.items() if ids
    }
    for node_id in node_ids:
        live = [
            a for a in store.allocs_by_node(node_id)
            if not a.terminal_status()
        ]
        usage = store._live_usage_for_node(node_id)
        row = table.row_of.get(node_id)
        if row is not None:
            assert (
                table.cpu_used[row], table.mem_used[row], table.disk_used[row]
            ) == tuple(float(u) for u in usage), node_id
        ballast = store._ballast(node_id)
        want = tuple(u - b for u, b in zip(usage, ballast)) + (
            sum(1 for a in live if _carries(a)),
        )
        assert store._node_live.get(node_id, (0, 0, 0, 0)) == want, node_id
        assert store.node_fit_usage(node_id) == (
            None if want[3] else want[:3]
        )
        for a in live:
            ar = a.allocated_resources
            if ar is None:
                continue
            values = [p.value for p in ar.shared.ports]
            for tr in ar.tasks.values():
                for net in tr.networks:
                    values.extend(p.value for p in net.reserved_ports)
                if row is not None:
                    for dv in tr.devices:
                        key = (row, (dv.vendor, dv.type, dv.name))
                        devices[key] = devices.get(key, 0) + len(
                            dv.device_ids
                        )
            for value in values:
                if 0 < value < MIN_DYNAMIC_PORT:
                    by_node = ports.setdefault(value, {})
                    by_node[node_id] = by_node.get(node_id, 0) + 1
    assert store._ports_live == ports
    assert {
        n: set(held) for n, held in store._ports_by_node.items()
    } == {
        n: {p for p, by in ports.items() if n in by}
        for n in {n for by in ports.values() for n in by}
    }
    assert table.device_used == devices


# ---------------------------------------------------------------------------
# the world: allocs of four kinds, on a fleet whose last node has devices
# ---------------------------------------------------------------------------


class World:
    def __init__(self, seed: int) -> None:
        self.rng = random.Random(seed)
        self.store = StateStore()
        self.metrics = Metrics()
        self.store.attach_metrics(self.metrics)
        self.job = mock.job(id="delta")
        self.store.upsert_job(self.job)
        self.nodes = [mock.node() for _ in range(N_NODES - 1)]
        self.nodes.append(mock.nvidia_node())
        for n in self.nodes:
            self.store.upsert_node(n)
        self.serial = 0

    def resources(self, kind: str) -> AllocatedResources:
        rng = self.rng
        task = AllocatedTaskResources(
            cpu=rng.choice((100, 250, 500)),
            memory_mb=rng.choice((64, 128, 256)),
        )
        shared = AllocatedSharedResources(disk_mb=rng.choice((0, 50, 150)))
        if kind == "static":
            shared.ports = [
                AssignedPortData(
                    label="svc",
                    value=rng.choice((8080, 9090, MIN_DYNAMIC_PORT + 7)),
                )
            ]
        elif kind == "tasknet":
            task.networks = [
                NetworkResource(
                    mbits=10,
                    reserved_ports=[Port("admin", rng.choice((22, 8080)))],
                    dynamic_ports=[Port("http", MIN_DYNAMIC_PORT + 1)],
                )
            ]
        elif kind == "dynamic":
            # a network the index never shows: the aggregate counts its
            # holder, the port index stays as it was
            task.networks = [
                NetworkResource(
                    dynamic_ports=[Port("http", MIN_DYNAMIC_PORT + 2)]
                )
            ]
        elif kind == "device":
            task.devices = [
                AllocatedDeviceResource(
                    vendor="nvidia", type="gpu", name="1080ti",
                    device_ids=[f"gpu-{rng.randrange(4)}"],
                )
            ]
        return AllocatedResources(tasks={"web": task}, shared=shared)

    def new_alloc(self, terminal: bool = False) -> Allocation:
        self.serial += 1
        kind = self.rng.choice(
            ("plain", "plain", "plain", "static", "tasknet", "dynamic",
             "device")
        )
        return Allocation(
            id=f"alloc-{self.serial:04d}",
            namespace="default",
            eval_id=f"eval-{self.serial % 7}",
            job_id=self.job.id,
            job=self.job,
            task_group="web",
            name=f"delta.web[{self.serial}]",
            node_id=self.rng.choice(self.nodes).id,
            allocated_resources=self.resources(kind),
            desired_status="run",
            client_status="complete" if terminal else "running",
        )

    def pick(self, live=None):
        """A copy of a stored alloc (None when there is none)."""
        pool = [
            a for a in self.store.allocs.values()
            if live is None or (not a.terminal_status()) == live
        ]
        if not pool:
            return None
        return copy.copy(self.rng.choice(sorted(pool, key=lambda a: a.id)))


# ---------------------------------------------------------------------------
# the writers
# ---------------------------------------------------------------------------


def place(w: World) -> None:
    w.store.upsert_allocs(
        [w.new_alloc() for _ in range(w.rng.randint(1, 3))]
    )


def place_by_plan(w: World) -> None:
    result = PlanResult()
    for _ in range(w.rng.randint(1, 4)):
        a = w.new_alloc()
        result.node_allocation.setdefault(a.node_id, []).append(a)
    w.store.upsert_plan_results(result, "")


def place_terminal(w: World) -> None:
    w.store.upsert_allocs([w.new_alloc(terminal=True)])


def inplace_update(w: World) -> None:
    """live -> live under the same id with OTHER resources: the repair
    (the old condition recounted nothing here)."""
    a = w.pick(live=True)
    if a is None:
        return place(w)
    a.allocated_resources = w.resources(
        w.rng.choice(("plain", "static", "tasknet", "device"))
    )
    w.store.upsert_allocs([a])


def stop(w: World) -> None:
    a = w.pick(live=True)
    if a is None:
        return place(w)
    a.desired_status = "stop"
    w.store.upsert_plan_results(
        PlanResult(node_update={a.node_id: [a]}), ""
    )


def evict(w: World) -> None:
    a = w.pick(live=True)
    if a is None:
        return place(w)
    a.desired_status = "evict"
    w.store.upsert_allocs([a])


def preempt(w: World) -> None:
    a = w.pick(live=True)
    if a is None:
        return place(w)
    a.desired_status = "evict"
    a.preempted_by_allocation = "someone"
    newcomer = w.new_alloc()
    newcomer.node_id = a.node_id
    w.store.upsert_plan_results(
        PlanResult(
            node_preemptions={a.node_id: [a]},
            node_allocation={a.node_id: [newcomer]},
        ),
        "",
    )


def client_terminal(w: World) -> None:
    a = w.pick(live=True)
    if a is None:
        return place(w)
    a.client_status = w.rng.choice(("complete", "failed", "lost"))
    w.store.upsert_allocs([a])


def client_running(w: World) -> None:
    """A status update that changes nothing the aggregate holds, and a
    terminal alloc written terminal again."""
    a = w.pick()
    if a is None:
        return place(w)
    if not a.terminal_status():
        a.client_status = "running"
    w.store.upsert_allocs([a])


def aliasing(w: World) -> None:
    """`existing is alloc`: the caller mutates the STORED object in
    place and writes it back — status, resources, either way."""
    pool = sorted(w.store.allocs.values(), key=lambda a: a.id)
    if not pool:
        return place(w)
    a = w.rng.choice(pool)
    what = w.rng.randrange(3)
    if what == 0:
        a.client_status = "complete"
    elif what == 1:
        a.client_status, a.desired_status = "running", "run"
    else:
        a.allocated_resources = w.resources(
            w.rng.choice(("plain", "static", "device"))
        )
    w.store.upsert_allocs([a])


def gc(w: World) -> None:
    """Delete / GC: the core scheduler reaps terminal evals with their
    (all terminal) allocs."""
    for i in range(7):
        ev = Evaluation(id=f"eval-{i}", job_id=w.job.id, status="complete")
        ev.modify_time = 0.0
        if w.store.eval_by_id(ev.id) is None:
            w.store.upsert_evals([ev], now=0.0)
    CoreScheduler(w.store.snapshot(), None).eval_gc(force=True)


def node_churn(w: World) -> None:
    """A node leaves and registers again: its allocs stayed, and its
    fresh row holds them from the registration on."""
    node = w.rng.choice(w.nodes)
    w.store.delete_node(node.id)
    check(w.store)
    w.store.upsert_node(node)
    check(w.store)
    a = w.new_alloc()
    a.node_id = node.id
    w.store.upsert_allocs([a])


def snapshot_restore(w: World) -> None:
    payload = copy.deepcopy(state_payload(w.store, None))
    if w.rng.random() < 0.5:
        # into a store that holds other state: none of it may survive
        place(w)
        evict(w)
    install_payload(w.store, None, payload)
    w.nodes = sorted(w.store.nodes.values(), key=lambda n: n.id)
    w.job = w.store.job_by_id("default", "delta")


WRITERS = {
    "place": place,
    "place_by_plan": place_by_plan,
    "place_terminal": place_terminal,
    "inplace_update": inplace_update,
    "stop": stop,
    "evict": evict,
    "preempt": preempt,
    "client_terminal": client_terminal,
    "client_running": client_running,
    "aliasing": aliasing,
    "gc": gc,
    "node_churn": node_churn,
    "snapshot_restore": snapshot_restore,
}


@pytest.mark.parametrize("writer", sorted(WRITERS) + ["mixed"])
@pytest.mark.parametrize("seed", [32, 1826525683])
def test_usage_and_indexes_equal_the_recount_after_every_write(writer, seed):
    w = World(seed)
    check(w.store)
    for step in range(STEPS):
        # the writer under test every other step, fed by the others
        if writer == "mixed" or step % 2:
            name = w.rng.choice(sorted(WRITERS))
        else:
            name = writer
        WRITERS[name](w)
        try:
            check(w.store)
        except AssertionError as exc:
            raise AssertionError(f"step {step}, after {name}") from exc
    counters = w.metrics.dump()["counters"]
    assert counters["store.usage_delta"] > 0
    if writer in ("aliasing", "mixed"):
        assert counters["store.usage_recount"] > 0


def test_ballast_rides_under_the_delta():
    """Seeded usage (bigworld ballast, no Allocation objects) stays in
    the columns under delta writes and stays out of the fit's sum."""
    import numpy as np

    w = World(7)
    table = w.store.node_table
    rows = np.array(
        [table.row_of[n.id] for n in w.nodes[:3]], dtype=np.int64
    )
    w.store.bulk_seed_usage(
        rows, np.array([1000.0, 2000.0, 3000.0]),
        np.array([100.0, 200.0, 300.0]), np.array([10.0, 20.0, 30.0]),
        alloc_count=3,
    )
    check(w.store)
    for _ in range(40):
        WRITERS[w.rng.choice(("place", "stop", "client_terminal"))](w)
        check(w.store)
    node = w.nodes[0]
    row = table.row_of[node.id]
    live = w.store._node_live.get(node.id, (0, 0, 0, 0))
    assert table.cpu_used[row] == 1000.0 + live[0]


def test_an_id_that_changes_nodes_leaves_the_old_node():
    w = World(11)
    a = w.new_alloc()
    w.store.upsert_allocs([a])
    moved = copy.copy(a)
    moved.node_id = next(n.id for n in w.nodes if n.id != a.node_id)
    w.store.upsert_allocs([moved])
    check(w.store)
    assert a.id not in w.store._allocs_by_node[a.node_id]
    assert w.metrics.dump()["counters"]["store.usage_recount"] == 1


def test_delta_writes_dirty_the_rows_the_recount_dirtied():
    """The kernel's mirror reads `usage_delta_since`: a write bumps the
    usage generation exactly where it did before (a first sight, a
    live <-> terminal flip), and not on a status update."""
    w = World(5)
    table = w.store.node_table
    a = w.new_alloc()
    g0 = table.usage_generation
    w.store.upsert_allocs([a])
    assert table.usage_generation == g0 + 1
    same = copy.copy(a)
    same.client_status = "running"
    w.store.upsert_allocs([same])
    assert table.usage_generation == g0 + 1
    gone = copy.copy(a)
    gone.desired_status = "stop"
    w.store.upsert_allocs([gone])
    assert table.usage_generation == g0 + 2
    gen, rows = w.store.usage_delta_since(g0)
    assert gen == g0 + 2 and rows == [table.row_of[a.node_id]]
