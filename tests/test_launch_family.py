"""The cold-compile shield compiles a launch shape's whole family the
first time one member is met (`BatchWorker._launch_ready(family=True)`,
`_launch_family`): the job shape's other chunk widths and the
carry-donating variant, each on arguments of its own.  These cases run
with NOMAD_TPU_SYNC_COMPILE unset (the conftest sets it), so the shield
is live: a cold shape sends its chunk down the host path and compiles
in the background.
"""
import copy
import random
import threading
import time

import jax
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server
from nomad_tpu.server import batch_worker as bw
from nomad_tpu.structs import (
    Affinity,
    Spread,
    SpreadTarget,
    compute_node_class,
)


@pytest.fixture(autouse=True)
def _live_shield(monkeypatch):
    monkeypatch.delenv("NOMAD_TPU_SYNC_COMPILE", raising=False)


def _nodes(n=24, seed=5):
    rng = random.Random(seed)
    nodes = []
    for i in range(n):
        node = mock.node(id=f"fam-node-{seed}-{i}")
        node.datacenter = rng.choice(["dc1", "dc2", "dc3"])
        node.computed_class = compute_node_class(node)
        nodes.append(node)
    return nodes


def _job(i, count=6):
    """The benchmark cell's job shape: percent-target spread over the
    datacenters and an affinity to one of them."""
    job = mock.job(id=f"fam-{i}")
    job.datacenters = ["dc1", "dc2", "dc3"]
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = 30
    tg.tasks[0].resources.memory_mb = 16
    job.spreads = [
        Spread(
            attribute="${node.datacenter}",
            weight=60,
            targets=[
                SpreadTarget(value="dc1", percent=50),
                SpreadTarget(value="dc2", percent=30),
            ],
        )
    ]
    job.affinities = [
        Affinity(
            ltarget="${node.datacenter}", operand="=", rtarget="dc2",
            weight=35,
        )
    ]
    return job


def _server(donate, batch_pipeline=True, seed=4):
    server = Server(
        num_schedulers=1, seed=seed, batch_pipeline=batch_pipeline
    )
    server.start()
    if batch_pipeline:
        # donation is resolved from the backend (off on the CPU):
        # forced, so the donating variant is a member of the family
        server.workers[0]._donate_carries = donate
    for node in _nodes():
        server.register_node(copy.deepcopy(node))
    return server


def _settle(worker, timeout=120.0):
    """Wait until no shield compile is in flight."""
    deadline = time.monotonic() + timeout
    while worker._compiling and time.monotonic() < deadline:
        time.sleep(0.02)
    assert not worker._compiling, "a shield compile never ended"
    assert not any(
        t.name == "kernel-compile" and t.is_alive()
        for t in threading.enumerate()
    )


def _spy_first_sight(monkeypatch, worker):
    """Record the arguments of the worker's family sightings."""
    sightings = []
    real = worker._launch_ready

    def spy(args, kwargs, fn=None, clone_args=False, family=False):
        if family:
            sightings.append((args, kwargs, fn))
        return real(
            args, kwargs, fn=fn, clone_args=clone_args, family=family
        )

    monkeypatch.setattr(worker, "_launch_ready", spy)
    return sightings


def _family_keys(worker, args, kwargs, donate):
    fns = [bw.chained_plan_picks_cols]
    if donate:
        fns.append(bw.chained_plan_picks_cols_donated())
    return {
        (width, fn.__name__): worker._compile_key(
            fn, *worker._resize_launch(args, kwargs, width)
        )
        for width in worker._chunk_buckets()
        for fn in fns
    }


def _placements(server, job_id):
    return sorted(
        (a.name, a.node_id)
        for a in server.store.allocs_by_job("default", job_id)
        if not a.terminal_status()
    )


@pytest.mark.parametrize("donate", [False, True], ids=["plain", "donating"])
def test_first_sighting_compiles_every_member(donate, monkeypatch):
    """One job, one eval, one 2-wide chunk: the first sighting leaves
    every width of the ladder compiled (and the donating variant where
    donation is on) without a second sighting, `sibling_compiles`
    counts the members beyond its own, and the wider chunks and the
    mid-chain launches that follow find theirs ready."""
    server = _server(donate)
    try:
        worker = server.workers[0]
        sightings = _spy_first_sight(monkeypatch, worker)
        server.register_job(_job(0, count=1))
        assert server.drain_to_idle(60)
        _settle(worker)
        args, kwargs, fn = sightings[0]
        assert fn is None and args[7].shape[0] == 2
        assert worker._chunk_buckets() == (2, 4, 8)
        keys = _family_keys(worker, args, kwargs, donate)
        assert len(keys) == (6 if donate else 3)
        missing = [m for m, key in keys.items() if key not in worker._compiled]
        assert not missing, missing
        assert worker.sibling_compiles == len(keys) - 1
        assert not worker._compile_failed
        # the first eval went down the host path while its shape compiled
        assert worker.cold_shape_fallbacks == 1 and worker.prescored == 0
        counters = server.metrics.dump()["counters"]
        assert counters["batch_worker.sibling_compiles"] == len(keys) - 1
        assert counters["batch_worker.cold_shape_fallbacks"] == 1.0
        compiled = set(worker._compiled)

        # a burst wide enough for the 4- and 8-wide chunks and for
        # chains past their first chunk: nothing is left to meet.
        # Staged behind the worker's pause (honored between gulps; a
        # dequeue in flight times out in 0.1 s), so that the backlog is
        # there whatever the registering thread's pace against the
        # worker's
        worker.set_pause(True)
        time.sleep(0.3)
        for i in range(1, 40):
            server.register_job(_job(i))
        worker.set_pause(False)
        assert server.drain_to_idle(120)
        _settle(worker)
        widths = {a[7].shape[0] for a, _k, _f in sightings}
        assert {2, 8} <= widths <= {2, 4, 8}, widths
        assert worker._compiled == compiled
        assert worker.cold_shape_fallbacks == 1
        assert worker.sibling_compiles == len(keys) - 1
        assert worker.prescored == 39
        if donate:
            assert worker.donated_launches > 0
    finally:
        server.stop()


def test_shield_counters_are_registered_at_zero():
    server = Server(num_schedulers=1, seed=4, batch_pipeline=True)
    try:
        counters = server.metrics.dump()["counters"]
        assert [counters[name] for name in bw.SHIELD_COUNTERS] == [0.0, 0.0]
    finally:
        server.stop()


def test_failing_sibling_is_parked_and_fails_nobody(monkeypatch):
    """A member the compiler refuses lands in `_compile_failed`, is
    counted, and fails neither the sighting that scheduled it nor the
    launches that later meet it: they stay on the exact host path."""
    real = bw.chained_plan_picks_cols

    def refuses_width_4(*args, **kwargs):
        if args[7].shape[0] == 4:
            raise RuntimeError("compiler says no")
        return real(*args, **kwargs)

    refuses_width_4.__name__ = real.__name__
    monkeypatch.setattr(bw, "chained_plan_picks_cols", refuses_width_4)
    server = _server(False)
    oracle = _server(False, batch_pipeline=False)
    try:
        worker = server.workers[0]
        sightings = _spy_first_sight(monkeypatch, worker)
        server.register_job(_job(0, count=1))
        assert server.drain_to_idle(60)
        _settle(worker)
        args, kwargs, _fn = sightings[0]
        keys = _family_keys(worker, args, kwargs, False)
        assert worker._compile_failed == {keys[(4, real.__name__)]}
        assert worker.compile_failures == 1
        assert keys[(2, real.__name__)] in worker._compiled
        assert keys[(8, real.__name__)] in worker._compiled
        for i in range(1, 24):
            server.register_job(_job(i))
        assert server.drain_to_idle(120)
        oracle.register_job(_job(0, count=1))
        for i in range(1, 24):
            oracle.register_job(_job(i))
        assert oracle.drain_to_idle(120)
        for i in range(24):
            assert _placements(server, f"fam-{i}") == _placements(
                oracle, f"fam-{i}"
            ), i
        # parked, not retried
        assert worker.compile_failures == 1
    finally:
        server.stop()
        oracle.stop()


def test_backend_epoch_bump_schedules_the_family_again(monkeypatch):
    """The keys carry the backend epoch: after a flip (failover or
    recovery) the next sighting compiles the family for the new
    backend, all of it."""
    server = _server(False)
    try:
        worker = server.workers[0]
        sightings = _spy_first_sight(monkeypatch, worker)
        server.register_job(_job(0, count=1))
        assert server.drain_to_idle(60)
        _settle(worker)
        assert worker.sibling_compiles == 2
        old = set(worker._compiled)
        # what `_on_device_transition` does to the shield
        with worker._compile_lock:
            worker._backend_epoch += 1
            worker._compiled.clear()
            worker._compile_failed.clear()
        server.register_job(_job(1, count=1))
        assert server.drain_to_idle(60)
        _settle(worker)
        args, kwargs, _fn = sightings[-1]
        keys = set(_family_keys(worker, args, kwargs, False).values())
        assert keys <= worker._compiled
        assert not keys & old
        assert worker.sibling_compiles == 4
        assert worker.cold_shape_fallbacks == 2
    finally:
        server.stop()


def test_mesh_launches_keep_first_sight_only(monkeypatch):
    """The node-sharded runner's launches are collectives: executed
    out of lockstep they deadlock a pod, so the mesh path compiles
    the shape it met and no other."""
    monkeypatch.setenv("NOMAD_TPU_MESH", "1")
    server = Server(num_schedulers=1, seed=4, batch_pipeline=True)
    server.start()
    try:
        worker = server.workers[0]
        assert worker._mesh is not None
        for i in range(16):
            node = mock.node(id=f"fam-mesh-{i}")
            node.computed_class = compute_node_class(node)
            server.register_node(node)
        for round_ in range(2):
            job = mock.job(id=f"fam-mesh-job-{round_}")
            job.task_groups[0].count = 2
            job.task_groups[0].tasks[0].resources.cpu = 100
            server.register_job(job)
            assert server.drain_to_idle(60)
            _settle(worker)
        assert worker.mesh_used >= 1
        assert worker.cold_shape_fallbacks >= 1
        assert worker.sibling_compiles == 0
        assert len(worker._compiled) == 1
    finally:
        server.stop()


def test_members_run_on_arguments_of_their_own(monkeypatch):
    """No member's execution may read a buffer a later launch donates,
    or donate one a launch still reads: every array a member is handed
    is a copy made on the caller's thread, before the caller launches
    anything.  The donating members consume their copies of the carry;
    the sighting's live arguments outlive the whole family."""
    server = _server(True)
    try:
        worker = server.workers[0]
        sightings = _spy_first_sight(monkeypatch, worker)
        handed = []
        real = worker._launch_family

        def spy(args, kwargs):
            members = real(args, kwargs)
            handed.append((members, threading.current_thread()))
            return members

        monkeypatch.setattr(worker, "_launch_family", spy)
        server.register_job(_job(0, count=1))
        assert server.drain_to_idle(60)
        _settle(worker)

        def arrays(args, kwargs):
            return [
                leaf
                for leaf in jax.tree_util.tree_leaves((args, kwargs))
                if hasattr(leaf, "shape")
            ]

        live_args, live_kwargs, _fn = sightings[0]
        live = arrays(live_args, live_kwargs)
        # one sighting met the family, on the worker's own thread,
        # before it launched anything: its five other members
        ((members, thread),) = handed
        assert thread.name != "kernel-compile"
        assert len(members) == 5
        donated_away = 0
        for _sig, fn, args, kwargs in members:
            leaves = arrays(args, kwargs)
            assert len(leaves) == len(live)
            for leaf in leaves:
                assert not any(leaf is other for other in live)
                if isinstance(leaf, np.ndarray):
                    assert not any(
                        np.shares_memory(leaf, other)
                        for other in live
                        if isinstance(other, np.ndarray)
                    )
            gone = sum(
                1 for leaf in leaves
                if isinstance(leaf, jax.Array) and leaf.is_deleted()
            )
            if fn.__name__.endswith("_donated"):
                donated_away += gone
            else:
                assert gone == 0
        # the donating members burned copies, not the live carry
        assert donated_away >= 3
        assert not any(
            leaf.is_deleted() for leaf in live if isinstance(leaf, jax.Array)
        )
    finally:
        server.stop()


def test_stream_served_through_compiling_family_matches_sequential():
    """A stream that keeps arriving while the family compiles — host
    path first, then launches beside the members' executions, then
    chains that donate their carries — places every job where the
    sequential scheduler does."""
    server = _server(True)
    oracle = _server(False, batch_pipeline=False)
    try:
        worker = server.workers[0]
        sent = 0
        for _burst in range(40):
            for _ in range(12):
                server.register_job(_job(sent, count=2))
                oracle.register_job(_job(sent, count=2))
                sent += 1
            assert server.drain_to_idle(120)
            if worker.donated_launches and not worker._compiling:
                break
        assert oracle.drain_to_idle(120)
        _settle(worker)
        for i in range(sent):
            assert _placements(server, f"fam-{i}") == _placements(
                oracle, f"fam-{i}"
            ), i
        assert worker.sibling_compiles == 5
        assert not worker._compile_failed
        assert worker.cold_shape_fallbacks >= 1
        assert worker.prescored > 0 and worker.donated_launches > 0
    finally:
        server.stop()
        oracle.stop()
