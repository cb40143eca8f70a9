"""The limit walk's counters (`batch_worker.walk_pulls`,
`batch_worker.walk_picks`): zero-registered at construction, and
incremented once a fetched chunk from the pulls the kernel hands back."""
import pytest

from nomad_tpu import mock
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
    finally:
        server.stop()


def test_a_sequential_server_registers_none():
    server = Server(num_schedulers=1, seed=4, batch_pipeline=False)
    try:
        counters = server.metrics.dump()["counters"]
        assert not set(bw.WALK_COUNTERS) & set(counters)
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
    finally:
        server.stop()
