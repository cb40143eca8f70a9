"""The limit walk's counters (`batch_worker.walk_pulls`,
`batch_worker.walk_picks`): zero-registered at construction, and
incremented once a fetched chunk from the pulls the kernel hands back.
Beside them `batch_worker.pair_decided_picks`, the picks a float32
trace's `lo` half chose, which rides in the same pulls as a flag bit."""
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.ops.batch import PAIR_DECIDED, split_pulls
from nomad_tpu.server import Server
from nomad_tpu.server import batch_worker as bw
from nomad_tpu.structs import compute_node_class


def test_walk_counters_are_registered_at_zero():
    server = Server(num_schedulers=1, seed=4, batch_pipeline=True)
    try:
        counters = server.metrics.dump()["counters"]
        assert bw.WALK_COUNTERS == (
            "batch_worker.walk_pulls", "batch_worker.walk_picks",
        )
        assert [counters[name] for name in bw.WALK_COUNTERS] == [0.0, 0.0]
        assert bw.PAIR_COUNTER == "batch_worker.pair_decided_picks"
        assert counters[bw.PAIR_COUNTER] == 0.0
    finally:
        server.stop()


def test_a_sequential_server_registers_none():
    server = Server(num_schedulers=1, seed=4, batch_pipeline=False)
    try:
        counters = server.metrics.dump()["counters"]
        assert not set(bw.WALK_COUNTERS) & set(counters)
        assert bw.PAIR_COUNTER not in counters
    finally:
        server.stop()


@pytest.mark.parametrize("n_nodes,count,jobs", [(40, 3, 9), (200, 10, 5)])
def test_walk_counters_count_picks_and_pulls_of_prescored_evals(
    n_nodes, count, jobs
):
    server = Server(num_schedulers=1, seed=11, batch_pipeline=True)
    for i in range(n_nodes):
        node = mock.node(id=f"walk-node-{i}")
        node.computed_class = compute_node_class(node)
        server.store.upsert_node(node)
    for i in range(jobs):
        job = mock.job(id=f"walk-{i}")
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = 20
        tg.tasks[0].resources.memory_mb = 16
        server.register_job(job)
    server.start()
    try:
        assert server.drain_to_idle(timeout=120)
        worker = server.workers[0]
        counters = server.metrics.dump()["counters"]
        assert worker.prescored == jobs
        picks = counters["batch_worker.walk_picks"]
        pulls = counters["batch_worker.walk_pulls"]
        assert picks == jobs * count
        # a binpack-only service pick scores max(2, ceil(log2 N))
        # feasible nodes of an empty fleet and stops there
        limit = max(2, (n_nodes - 1).bit_length())
        assert pulls == picks * limit
        # the session's trace is float64: a score has no lo to decide
        assert counters[bw.PAIR_COUNTER] == 0.0
    finally:
        server.stop()


@pytest.mark.parametrize(
    "pulls, flagged",
    [([[3, 7], [120000, 0]], []), ([[3, 7], [120000, 1]], [(0, 1), (1, 0)])],
    ids=["float64", "flagged"],
)
def test_split_pulls_takes_the_flag_off_a_pull_count(pulls, flagged):
    arr = np.asarray(pulls, np.int32)
    for at in flagged:
        arr[at] += PAIR_DECIDED
    clean, decided = split_pulls(arr)
    assert clean.tolist() == pulls and clean.dtype == np.int32
    assert sorted(zip(*np.nonzero(decided))) == flagged
    # a count never reaches the flag: an arena holds far under 2^30 rows
    assert PAIR_DECIDED == 2**30 and bw.PIPELINE_CHUNK < PAIR_DECIDED


def test_the_worker_counts_the_flagged_picks_and_replays_the_clean_pulls(
    monkeypatch,
):
    """What a float32 trace hands back, fed to the host's side at
    float64: the kernel's answer with the flag set on the first pick of
    every evaluation.  The counter counts them, the pulls the replay
    and the walk's counters read are the kernel's own."""
    real = bw.chained_plan_picks_cols

    def flagging(*args, **kwargs):
        rows, pulls, carry = real(*args, **kwargs)
        return rows, pulls.at[:, 0].add(PAIR_DECIDED), carry

    monkeypatch.setattr(bw, "chained_plan_picks_cols", flagging)
    n_nodes, count, jobs = 40, 3, 9
    server = Server(num_schedulers=1, seed=11, batch_pipeline=True)
    for i in range(n_nodes):
        node = mock.node(id=f"pair-node-{i}")
        node.computed_class = compute_node_class(node)
        server.store.upsert_node(node)
    for i in range(jobs):
        job = mock.job(id=f"pair-{i}")
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = 20
        tg.tasks[0].resources.memory_mb = 16
        server.register_job(job)
    server.start()
    try:
        assert server.drain_to_idle(timeout=120)
        counters = server.metrics.dump()["counters"]
        assert server.workers[0].prescored == jobs
        assert counters[bw.PAIR_COUNTER] == jobs
        assert counters["batch_worker.walk_picks"] == jobs * count
        limit = max(2, (n_nodes - 1).bit_length())
        assert counters["batch_worker.walk_pulls"] == jobs * count * limit
        assert counters.get("batch_worker.fallbacks", 0.0) == 0.0
    finally:
        server.stop()


@pytest.mark.parametrize("flagged", [False, True], ids=["float64", "flagged"])
def test_walk_counters_count_on_the_whole_fleet_branch_of_a_spread_world(
    monkeypatch, flagged
):
    """A spread and an affinity lift the visit limit: every pick draws
    the whole ring, and the counters count on that branch of the walk
    as on the limited one — picks, pulls and, where a float32 trace's
    answer carries the flag, the picks its `lo` half chose."""
    from nomad_tpu.structs import Affinity, Spread, SpreadTarget

    if flagged:
        real = bw.chained_plan_picks_cols

        def flagging(*args, **kwargs):
            rows, pulls, carry = real(*args, **kwargs)
            return rows, pulls.at[:, :2].add(PAIR_DECIDED), carry

        monkeypatch.setattr(bw, "chained_plan_picks_cols", flagging)
    n_nodes, count, jobs = 45, 6, 7
    server = Server(num_schedulers=1, seed=38, batch_pipeline=True)
    for i in range(n_nodes):
        node = mock.node(id=f"spread-node-{i}", datacenter=f"dc{1 + i % 3}")
        node.computed_class = compute_node_class(node)
        server.store.upsert_node(node)
    for i in range(jobs):
        job = mock.job(id=f"spread-{i}")
        job.datacenters = ["dc1", "dc2", "dc3"]
        job.spreads = [Spread(
            attribute="${node.datacenter}", weight=60,
            targets=(SpreadTarget("dc1", 50), SpreadTarget("dc2", 30)),
        )]
        job.affinities = [
            Affinity("${node.datacenter}", "dc2", "=", 35),
            Affinity("${node.datacenter}", "dc3", "=", 60),
        ]
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = 20
        tg.tasks[0].resources.memory_mb = 16
        server.register_job(job)
    server.start()
    try:
        assert server.drain_to_idle(timeout=120)
        counters = server.metrics.dump()["counters"]
        assert server.workers[0].prescored == jobs
        assert counters.get("batch_worker.fallbacks", 0.0) == 0.0
        picks = counters["batch_worker.walk_picks"]
        assert picks == jobs * count
        assert counters["batch_worker.walk_pulls"] == picks * n_nodes
        assert counters[bw.PAIR_COUNTER] == (2 * jobs if flagged else 0.0)
        # every job placed its six, over all three datacenters
        for i in range(jobs):
            allocs = server.store.allocs_by_job("default", f"spread-{i}")
            assert len(allocs) == count
            assert len({
                server.store.node_by_id(a.node_id).datacenter for a in allocs
            }) == 3
    finally:
        server.stop()
