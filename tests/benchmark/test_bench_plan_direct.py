"""The plan applier's path counter's reader
(``benchmark/layers/plan_direct_share_pct.deploy.py``): silent on a
program without the two counters, the share of plans that went direct
on a ``/v1/metrics`` delta as ``run.delta`` builds it."""
import pytest

from benchmark import run
from benchmark.manifest import Manifest

NAME = "plan_direct_share_pct.deploy"


@pytest.fixture(scope="module")
def read():
    return Manifest().layer_reader(NAME)


def _obs(counters):
    return {
        "window_s": 51.0, "evals": 5650, "attempted": 5650, "refused": 0,
        "counters": counters, "samples": {},
    }


def test_the_manifest_lists_it_last_on_the_deploy_cell():
    manifest = Manifest()
    manifest.check()
    entry = manifest.doc["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "plan applier",
        "moves": "placements_per_s",
        "workloads": ["spread-5k-f64.deploy"],
    }
    reported = manifest.metrics_of("per_layer", "spread-5k-f64.deploy")
    assert entry in reported


@pytest.mark.parametrize(
    "counters",
    [
        {},  # the parent's program: neither counter
        {"overload.shed": 3.0, "trace.folded": 400.0},
        {"plan.direct": 0.0, "plan.queued": 0.0},  # no plan in the window
    ],
    ids=["empty", "parent", "zero_registered"],
)
def test_silent_where_there_is_nothing_to_read(read, counters):
    assert read(_obs(counters)) is None


@pytest.mark.parametrize(
    "before, after, expected",
    [
        # one synchronous submitter: every plan found the applier idle
        ((1200.0, 0.0), (6850.0, 0.0), 100.0),
        # overlapping submitters: 3 in 4 found it busy
        ((100.0, 300.0), (600.0, 1800.0), 25.0),
        # every plan queued
        ((0.0, 10.0), (0.0, 510.0), 0.0),
        # counters that appear inside the window (a late first plan)
        (None, (40.0, 10.0), 80.0),
    ],
    ids=["all_direct", "mostly_queued", "all_queued", "born_in_window"],
)
def test_reads_the_share_of_a_metrics_delta(read, before, after, expected):
    def snap(pair):
        counters = {"trace.folded": 7.0}
        if pair is not None:
            counters["plan.direct"], counters["plan.queued"] = pair
        return {"counters": counters, "samples": {}}

    window = run.delta(snap(after), snap(before))
    assert read(_obs(window["counters"])) == pytest.approx(expected)


def test_a_served_program_feeds_the_reader():
    """The counters the reader names are the ones the program
    zero-registers and counts: a server's one submitter goes direct."""
    from nomad_tpu import mock
    from nomad_tpu.server import Server
    from nomad_tpu.server.plan_apply import PLAN_COUNTERS

    assert PLAN_COUNTERS == ("plan.direct", "plan.queued")
    server = Server(num_schedulers=1, seed=28)
    server.start()
    try:
        before = server.metrics.dump()["counters"]
        assert [before[name] for name in PLAN_COUNTERS] == [0.0, 0.0]
        for _ in range(4):
            server.register_node(mock.node())
        for i in range(3):
            job = mock.job(id=f"direct-{i}")
            job.task_groups[0].count = 2
            server.register_job(job)
        assert server.drain_to_idle(60)
        after = server.metrics.dump()["counters"]
    finally:
        server.stop()
    manifest_read = Manifest().layer_reader(NAME)
    assert after["plan.direct"] >= 3.0
    assert manifest_read(_obs(after)) == pytest.approx(100.0)
