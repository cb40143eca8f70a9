"""Batched eval pipeline tests: the prescored path must produce plans
identical to the sequential scheduler and fall back safely.
"""
import copy
import random
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server
from nomad_tpu.structs import compute_node_class


def make_nodes(n, seed=0):
    rng = random.Random(seed)
    nodes = []
    for _ in range(n):
        node = mock.node()
        node.node_resources.cpu = rng.choice([4000, 8000])
        node.node_resources.memory_mb = rng.choice([8192, 16384])
        node.computed_class = compute_node_class(node)
        nodes.append(node)
    return nodes


def make_jobs(n, seed=1):
    rng = random.Random(seed)
    jobs = []
    for i in range(n):
        job = mock.job(id=f"batch-pipe-{i}")
        job.task_groups[0].count = rng.randint(1, 5)
        job.task_groups[0].tasks[0].resources.cpu = rng.choice([200, 500])
        jobs.append(job)
    return jobs


def placements(server, job_id):
    return sorted(
        (a.name, a.node_id)
        for a in server.store.allocs_by_job("default", job_id)
        if not a.terminal_status()
    )


def test_batch_pipeline_matches_sequential():
    nodes = make_nodes(20)
    jobs = make_jobs(8)

    seq = Server(num_schedulers=1, seed=99, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=99, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        for job in jobs:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(15)
        for job in jobs:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(30)

        for job in jobs:
            assert placements(seq, job.id) == placements(bat, job.id), (
                f"divergence for {job.id}"
            )
        worker = bat.workers[0]
        assert worker.prescored > 0
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_fallback_for_complex_evals():
    """Evals the prescorer cannot handle still complete correctly."""
    from nomad_tpu.structs import Spread, SpreadTarget

    server = Server(num_schedulers=1, seed=7, batch_pipeline=True)
    server.start()
    try:
        for node in make_nodes(10, seed=3):
            server.register_node(node)
        # spread job: not batchable
        job = mock.job(id="spready")
        job.task_groups[0].count = 4
        job.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
        server.register_job(job)
        assert server.drain_to_idle(15)
        assert len(placements(server, "spready")) == 4

        # scale-up of an existing job: not batchable (live allocs)
        job2 = mock.job(id="grower")
        job2.task_groups[0].count = 2
        server.register_job(job2)
        assert server.drain_to_idle(15)
        job3 = mock.job(id="grower")
        job3.task_groups[0].count = 4
        server.register_job(job3)
        assert server.drain_to_idle(15)
        assert len(placements(server, "grower")) == 4
    finally:
        server.stop()


def test_batch_pipeline_blocked_eval_on_exhaustion():
    server = Server(num_schedulers=1, seed=8, batch_pipeline=True)
    server.start()
    try:
        node = mock.node()
        node.node_resources.cpu = 1000
        node.node_resources.memory_mb = 1024
        node.computed_class = compute_node_class(node)
        server.register_node(node)
        job = mock.job(id="toolarge")
        job.task_groups[0].count = 5
        job.task_groups[0].tasks[0].resources.cpu = 400
        server.register_job(job)
        assert server.drain_to_idle(15)

        def settled():
            placed = placements(server, "toolarge")
            return (
                0 < len(placed) < 5
                and server.blocked.blocked_count() >= 1
            )

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not settled():
            time.sleep(0.05)
        assert settled()
    finally:
        server.stop()


def test_batch_pipeline_spread_in_kernel_matches_sequential():
    """Percent-target spread jobs run through the in-kernel carry and
    produce placements identical to the sequential scheduler
    (spread.go:163 boost semantics, SpreadInputs in ops/batch.py)."""
    from nomad_tpu.structs import Affinity, Spread, SpreadTarget

    rng = random.Random(5)
    nodes = []
    for i in range(24):
        node = mock.node()
        node.datacenter = rng.choice(["dc1", "dc2", "dc3"])
        node.node_resources.cpu = rng.choice([4000, 8000])
        node.node_resources.memory_mb = rng.choice([8192, 16384])
        node.computed_class = compute_node_class(node)
        nodes.append(node)

    def spread_job(i):
        job = mock.job(id=f"spread-{i}")
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = 6
        tg.tasks[0].resources.cpu = 300
        job.spreads = [
            Spread(
                attribute="${node.datacenter}",
                weight=60,
                targets=[
                    SpreadTarget(value="dc1", percent=50),
                    SpreadTarget(value="dc2", percent=30),
                    # dc3 via the implicit "*" remainder
                ],
            )
        ]
        if i % 2:
            job.affinities = [
                Affinity(
                    ltarget="${node.datacenter}",
                    operand="=",
                    rtarget="dc2",
                    weight=40,
                )
            ]
        return job

    jobs = [spread_job(i) for i in range(6)]
    # plus interleaved plain jobs: mixed batches must stack correctly
    plain = make_jobs(3, seed=9)

    seq = Server(num_schedulers=1, seed=42, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=42, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        for job in jobs + plain:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(20)
        for job in jobs + plain:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(40)

        for job in jobs + plain:
            assert placements(seq, job.id) == placements(bat, job.id), (
                f"divergence for {job.id}"
            )
        worker = bat.workers[0]
        assert worker.prescored >= len(jobs) + len(plain), (
            f"spread jobs fell back: prescored={worker.prescored} "
            f"fallbacks={worker.fallbacks}"
        )
        # distribution sanity: dc1 got the most (50% target)
        by_dc = {}
        node_dc = {n.id: n.datacenter for n in nodes}
        for _name, node_id in placements(bat, "spread-0"):
            by_dc[node_dc[node_id]] = by_dc.get(node_dc[node_id], 0) + 1
        assert by_dc.get("dc1", 0) >= max(by_dc.values()) - 1
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_even_spread_in_kernel_matches():
    """Even-spread mode (no targets) runs in-kernel: min/max balance
    boosts over the observed use map, bit-identical to the sequential
    SpreadIterator (spread.py even_spread_score_boost)."""
    import random as _random

    from nomad_tpu.structs import Spread

    nodes = make_nodes(12, seed=3)
    rng = _random.Random(5)
    for n in nodes:
        n.datacenter = rng.choice(["dc1", "dc2", "dc3"])
        n.computed_class = compute_node_class(n)

    def even_job(i, count):
        job = mock.job(
            id=f"even-{i}", datacenters=["dc1", "dc2", "dc3"]
        )
        job.task_groups[0].count = count
        job.spreads = [
            Spread(attribute="${node.datacenter}", weight=50)
        ]
        return job

    seq = Server(num_schedulers=1, seed=7, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=7, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        jobs = [even_job(i, 3 + i) for i in range(4)]
        for job in jobs:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(30)
        for job in jobs:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(60)
        for job in jobs:
            assert placements(seq, job.id) == placements(bat, job.id), (
                job.id
            )
        worker = bat.workers[0]
        assert worker.prescored >= 1, (
            worker.prescored, worker.fallbacks,
        )
        # scale-up: steady-state even-spread (live allocs feed the
        # use map) stays identical too
        for server in (seq, bat):
            grown = even_job(0, 8)
            grown.version = 1
            server.register_job(grown)
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        assert placements(seq, "even-0") == placements(bat, "even-0")
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_mixed_percent_and_even_spreads_match():
    """A job mixing a percent-target stanza with an even stanza on a
    different attribute exercises both kernel paths at once."""
    import random as _random

    from nomad_tpu.structs import Spread, SpreadTarget

    nodes = make_nodes(12, seed=9)
    rng = _random.Random(11)
    for n in nodes:
        n.datacenter = rng.choice(["dc1", "dc2"])
        n.attributes["rack"] = rng.choice(["r0", "r1", "r2"])
        n.computed_class = compute_node_class(n)

    def mixed_job(count):
        job = mock.job(id="mixed", datacenters=["dc1", "dc2"])
        job.task_groups[0].count = count
        job.spreads = [
            Spread(
                attribute="${node.datacenter}",
                weight=60,
                targets=[
                    SpreadTarget(value="dc1", percent=70),
                    SpreadTarget(value="dc2", percent=30),
                ],
            ),
            Spread(attribute="${attr.rack}", weight=40),
        ]
        return job

    seq = Server(num_schedulers=1, seed=13, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=13, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        seq.register_job(mixed_job(6))
        assert seq.drain_to_idle(30)
        bat.register_job(mixed_job(6))
        assert bat.drain_to_idle(60)
        assert placements(seq, "mixed") == placements(bat, "mixed")
        worker = bat.workers[0]
        assert worker.prescored >= 1, (
            worker.prescored, worker.fallbacks,
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_duplicate_spread_attribute_matches():
    """Job- and group-level spreads on the same attribute: the
    attribute-keyed info map double-applies the overwrite winner
    (reference computeSpreadInfo semantics) — the kernel must match."""
    from nomad_tpu.structs import Spread, SpreadTarget

    rng = random.Random(11)
    nodes = []
    for _ in range(18):
        node = mock.node()
        node.datacenter = rng.choice(["dc1", "dc2", "dc3"])
        node.computed_class = compute_node_class(node)
        nodes.append(node)

    job = mock.job(id="dup-spread")
    job.datacenters = ["dc1", "dc2", "dc3"]
    tg = job.task_groups[0]
    tg.count = 6
    job.spreads = [
        Spread(
            attribute="${node.datacenter}",
            weight=80,
            targets=[SpreadTarget(value="dc1", percent=70)],
        )
    ]
    tg.spreads = [
        Spread(
            attribute="${node.datacenter}",
            weight=20,
            targets=[SpreadTarget(value="dc2", percent=60)],
        )
    ]

    seq = Server(num_schedulers=1, seed=13, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=13, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(20)
        bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(20)
        assert placements(seq, "dup-spread") == placements(
            bat, "dup-spread"
        )
        assert bat.workers[0].prescored >= 1
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_steady_state_churn_matches_sequential():
    """The VERDICT r1 target: a mixed churn stream — new jobs,
    scale-ups, node-down reschedules, failed-alloc reschedules with
    penalty nodes — prescores the large majority of evals with plans
    bit-identical to the sequential worker (generic_sched.go:332
    computeJobAllocs semantics end to end)."""
    from nomad_tpu.structs import ReschedulePolicy

    nodes = make_nodes(24, seed=21)
    jobs = make_jobs(8, seed=22)
    for j in jobs:
        j.task_groups[0].reschedule_policy = ReschedulePolicy(
            delay_s=0.0, unlimited=True
        )

    seq = Server(num_schedulers=1, seed=77, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=77, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        for job in jobs:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(20)
        for job in jobs:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(40)
        for job in jobs:
            assert placements(seq, job.id) == placements(bat, job.id), (
                f"phase-1 divergence for {job.id}"
            )

        # -- phase 2: churn ------------------------------------------
        def churn(server):
            # scale-ups (steady-state evals over live allocs)
            for i in (0, 2, 5):
                grown = copy.deepcopy(jobs[i])
                grown.task_groups[0].count += 3
                server.register_job(grown)
            # brand-new jobs interleaved
            for k in range(2):
                nj = mock.job(id=f"churn-new-{k}")
                nj.task_groups[0].count = 2
                server.register_job(nj)
            # drain BEFORE the node dies: a node-down racing an
            # in-flight eval gives the two servers legitimately
            # different interleavings (whether the eval's snapshot sees
            # the node ready is timing), and bit-identity is only
            # defined per interleaving
            assert server.drain_to_idle(30)
            # a node dies: its allocs go lost and reschedule
            server.update_node_status(nodes[3].id, "down")

        churn(seq)
        assert seq.drain_to_idle(20)
        churn(bat)
        assert bat.drain_to_idle(40)

        all_ids = [j.id for j in jobs] + ["churn-new-0", "churn-new-1"]
        for jid in all_ids:
            assert placements(seq, jid) == placements(bat, jid), (
                f"phase-2 divergence for {jid}"
            )

        # -- phase 3: failed allocs reschedule with penalty ----------
        def fail_alloc(server, job_id, name):
            for a in server.store.allocs_by_job("default", job_id):
                if a.name == name and not a.terminal_status():
                    failed = copy.deepcopy(a)
                    failed.client_status = "failed"
                    server.update_allocs_from_client([failed])
                    return
            raise AssertionError(f"no live alloc {name}")

        victims = [
            (jobs[1].id, placements(seq, jobs[1].id)[0][0]),
            (jobs[4].id, placements(seq, jobs[4].id)[0][0]),
        ]
        for jid, name in victims:
            fail_alloc(seq, jid, name)
        assert seq.drain_to_idle(20)
        for jid, name in victims:
            fail_alloc(bat, jid, name)
        assert bat.drain_to_idle(40)

        for jid in all_ids:
            assert placements(seq, jid) == placements(bat, jid), (
                f"phase-3 divergence for {jid}"
            )

        worker = bat.workers[0]
        total = worker.prescored + worker.fallbacks
        assert total > 0
        rate = worker.prescored / total
        assert rate > 0.8, (
            f"steady-state prescore rate too low: {worker.prescored}/"
            f"{total} = {rate:.2f}"
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_distinct_hosts_matches_sequential():
    """distinct_hosts jobs prescore (the kernel's collision carry IS
    the proposed-allocs-per-node count for single-TG jobs) and match
    the sequential scheduler bit for bit — including a scale-up where
    existing allocs exclude their nodes (feasible.go:470)."""
    import copy

    from nomad_tpu.structs import Constraint

    nodes = make_nodes(12, seed=31)

    def dh_job(count):
        job = mock.job(id="dh-job")
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = 200
        job.constraints = list(job.constraints) + [
            Constraint(operand="distinct_hosts")
        ]
        return job

    seq = Server(num_schedulers=1, seed=41, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=41, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        for srv in (seq, bat):
            srv.register_job(dh_job(5))
            assert srv.drain_to_idle(20)
        assert placements(seq, "dh-job") == placements(bat, "dh-job")
        # all on distinct nodes
        node_ids = [n for _, n in placements(bat, "dh-job")]
        assert len(set(node_ids)) == 5

        # scale up: existing allocs must exclude their nodes
        for srv in (seq, bat):
            srv.register_job(dh_job(9))
            assert srv.drain_to_idle(20)
        assert placements(seq, "dh-job") == placements(bat, "dh-job")
        node_ids = [n for _, n in placements(bat, "dh-job")]
        assert len(node_ids) == 9 and len(set(node_ids)) == 9
        worker = bat.workers[0]
        assert worker.prescored >= 1, (
            worker.prescored, worker.fallbacks, worker.errors,
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_steady_state_spread_matches_sequential():
    """Scale-ups and reschedules of percent-target spread jobs stay on
    the prescored path: the kernel's existing/cleared carries reproduce
    propertySet.GetCombinedUseMap (propertyset.go) including the
    PopulateProposed cleared-decrement quirk."""
    import copy

    from nomad_tpu.structs import Spread, SpreadTarget

    rng = random.Random(51)
    nodes = []
    for _ in range(18):
        node = mock.node()
        node.datacenter = rng.choice(["dc1", "dc2", "dc3"])
        node.node_resources.cpu = rng.choice([4000, 8000])
        node.computed_class = compute_node_class(node)
        nodes.append(node)

    def spread_job(count, cpu=250):
        job = mock.job(id="ss-spread")
        job.datacenters = ["dc1", "dc2", "dc3"]
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = cpu
        job.spreads = [
            Spread(
                attribute="${node.datacenter}",
                weight=70,
                targets=[
                    SpreadTarget(value="dc1", percent=60),
                    SpreadTarget(value="dc2", percent=20),
                ],
            )
        ]
        return job

    seq = Server(num_schedulers=1, seed=61, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=61, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        # initial placement, then a scale-up (existing allocs feed
        # used0), then a destructive update (cpu bump -> evictions feed
        # the cleared carry per pick)
        node_dc = {n.id: n.datacenter for n in nodes}
        for count, cpu in ((4, 250), (9, 250), (9, 400)):
            for srv in (seq, bat):
                srv.register_job(spread_job(count, cpu))
                assert srv.drain_to_idle(25)
            ps = placements(seq, "ss-spread")
            pb = placements(bat, "ss-spread")
            assert ps == pb, (
                f"divergence at count={count} cpu={cpu}: "
                f"seq={[(n, node_dc[i]) for n, i in ps]} "
                f"bat={[(n, node_dc[i]) for n, i in pb]} "
                f"prescored={bat.workers[0].prescored} "
                f"fallbacks={bat.workers[0].fallbacks}"
            )
        worker = bat.workers[0]
        assert worker.prescored >= 2, (
            worker.prescored, worker.fallbacks, worker.errors,
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_network_jobs_match_sequential():
    """Host-mode dynamic-port jobs ride the fast path: the kernel is
    port-blind but the winner's exact verification assigns real ports,
    so plans match the sequential worker bit-for-bit."""
    from nomad_tpu.structs import NetworkResource, Port

    nodes = make_nodes(12, seed=31)
    jobs = make_jobs(4, seed=32)
    for j in jobs:
        j.task_groups[0].networks = [
            NetworkResource(
                dynamic_ports=[Port("http"), Port("admin")]
            )
        ]

    seq = Server(num_schedulers=1, seed=55, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=55, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        for job in jobs:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(20)
        for job in jobs:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(40)
        for job in jobs:
            assert placements(seq, job.id) == placements(bat, job.id)
        # the network jobs actually used the fast path
        worker = bat.workers[0]
        assert worker.prescored >= 1, (
            worker.prescored,
            worker.fallbacks,
        )
        # placed allocs carry real port assignments
        some = [
            a
            for a in bat.store.allocs_by_job("default", jobs[0].id)
            if not a.terminal_status()
        ]
        assert some
        for a in some:
            ports = a.allocated_resources.shared.ports
            assert {p.label for p in ports} == {"http", "admin"}
            assert all(p.value > 0 for p in ports)
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_static_port_contention_identical():
    """Reserved-port jobs take the sequential path (a port-collided
    node is skipped by binpack without consuming a walk-limit slot —
    an asymmetry the kernel can't see), and outcomes stay identical,
    including the blocked eval when every node's port is taken."""
    from nomad_tpu.structs import NetworkResource, Port

    nodes = make_nodes(3, seed=41)

    def static_job(jid, count):
        job = mock.job(id=jid)
        job.task_groups[0].count = count
        job.task_groups[0].tasks[0].resources.cpu = 100
        job.task_groups[0].networks = [
            NetworkResource(reserved_ports=[Port("svc", 8080)])
        ]
        return job

    seq = Server(num_schedulers=1, seed=66, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=66, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        for server in (seq, bat):
            server.register_job(static_job("port-a", 3))
        assert seq.drain_to_idle(20)
        assert bat.drain_to_idle(20)
        assert placements(seq, "port-a") == placements(bat, "port-a")
        assert len(placements(bat, "port-a")) == 3
        # every node's 8080 is now taken: the second job must block on
        # both servers
        for server in (seq, bat):
            server.register_job(static_job("port-b", 1))
        assert seq.drain_to_idle(20)
        assert bat.drain_to_idle(20)
        assert placements(seq, "port-b") == placements(bat, "port-b")
        assert placements(bat, "port-b") == []
        for server in (seq, bat):
            evs = server.store.evals_by_job("default", "port-b")
            assert any(e.status == "blocked" for e in evs)
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_even_mode_edge_cases_match():
    """Review regressions: (a) duplicate attribute with mixed target
    presence follows the merged info's mode on both paths; (b) an
    even-spread job whose update stages destructive evictions (cleared
    can zero a use-map value, where the oracle's zero-reset min/max
    idiom is iteration-order dependent) falls back to the exact path —
    outcomes identical either way."""
    import random as _random

    from nomad_tpu.structs import Spread, SpreadTarget

    nodes = make_nodes(10, seed=17)
    rng = _random.Random(19)
    for n in nodes:
        n.datacenter = rng.choice(["dc1", "dc2"])
        n.computed_class = compute_node_class(n)

    # (a) tg stanza has targets, job stanza (overwrite winner) does
    # not -> sequential scores BOTH psets in even mode
    def dup_job(count):
        job = mock.job(id="dup-mode", datacenters=["dc1", "dc2"])
        job.task_groups[0].count = count
        job.task_groups[0].spreads = [
            Spread(
                attribute="${node.datacenter}",
                weight=70,
                targets=[SpreadTarget(value="dc1", percent=80)],
            )
        ]
        job.spreads = [
            Spread(attribute="${node.datacenter}", weight=30)
        ]
        return job

    seq = Server(num_schedulers=1, seed=23, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=23, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        seq.register_job(dup_job(5))
        assert seq.drain_to_idle(30)
        bat.register_job(dup_job(5))
        assert bat.drain_to_idle(60)
        assert placements(seq, "dup-mode") == placements(
            bat, "dup-mode"
        )

        # (b) destructive update on an even-spread job: new config
        # forces stop+replace; batch must fall back yet match
        def even_destr(version):
            job = mock.job(id="even-destr", datacenters=["dc1", "dc2"])
            job.task_groups[0].count = 4
            job.spreads = [
                Spread(attribute="${node.datacenter}", weight=50)
            ]
            if version:
                job.task_groups[0].tasks[0].config = {
                    "command": "/bin/true"
                }
                job.version = version
            return job

        for server in (seq, bat):
            server.register_job(even_destr(0))
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        assert placements(seq, "even-destr") == placements(
            bat, "even-destr"
        )
        for server in (seq, bat):
            server.register_job(even_destr(1))
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        assert placements(seq, "even-destr") == placements(
            bat, "even-destr"
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_multi_task_group_matches_sequential():
    """Multi-task-group jobs run the prescored path (per-pick group
    routing, ops/batch.py TGInputs) bit-identically to the sequential
    scheduler: the walk offset continues across groups within one eval
    (reference generic_sched.go:468 computePlacements iterating task
    groups), asks/feasibility/anti-affinity are per group."""
    import dataclasses

    from nomad_tpu.structs import Task, TaskGroup

    def add_group(job, name, count, cpu, mem, driver="mock_driver"):
        tg0 = job.task_groups[0]
        tg = TaskGroup(
            name=name,
            count=count,
            restart_policy=tg0.restart_policy,
            reschedule_policy=tg0.reschedule_policy,
            tasks=[
                Task(
                    name=f"{name}-task",
                    driver=driver,
                    resources=dataclasses.replace(
                        tg0.tasks[0].resources,
                        cpu=cpu,
                        memory_mb=mem,
                    ),
                )
            ],
            ephemeral_disk=tg0.ephemeral_disk,
        )
        job.task_groups.append(tg)

    def make_stream():
        rng = random.Random(7)
        jobs = []
        for i in range(10):
            job = mock.job(id=f"mtg-{i}")
            job.task_groups[0].count = rng.randint(1, 4)
            job.task_groups[0].tasks[0].resources.cpu = rng.choice(
                [200, 500]
            )
            if i % 3 != 2:  # mixed stream: mostly multi-group
                add_group(
                    job, "api", rng.randint(1, 3),
                    rng.choice([300, 700]), 512,
                )
            if i % 4 == 1:  # three groups
                add_group(job, "cache", 2, 250, 256)
            jobs.append(job)
        return jobs

    nodes = make_nodes(24, seed=5)
    seq = Server(num_schedulers=1, seed=41, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=41, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        jobs = make_stream()
        for job in jobs:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(30)
        for job in jobs:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(60)

        for job in jobs:
            assert placements(seq, job.id) == placements(
                bat, job.id
            ), f"divergence for {job.id}"
        worker = bat.workers[0]
        total = worker.prescored + worker.fallbacks
        assert total > 0
        rate = worker.prescored / total
        assert rate > 0.8, (
            f"multi-group stream prescore rate too low: "
            f"{worker.prescored}/{total}"
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_multi_tg_failure_coalescing_matches():
    """Per-group failure coalescing: a group whose ask exceeds every
    node fails while its sibling group keeps placing — bit-identical
    to the sequential path (generic_sched.go:482 coalesces failures
    PER task group)."""
    import dataclasses

    from nomad_tpu.structs import Task, TaskGroup

    nodes = make_nodes(12, seed=9)
    seq = Server(num_schedulers=1, seed=13, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=13, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))

        def giant_job():
            job = mock.job(id="mtg-fail")
            tg0 = job.task_groups[0]
            tg0.count = 3
            tg0.tasks[0].resources.cpu = 300
            giant = TaskGroup(
                name="giant",
                count=2,
                restart_policy=tg0.restart_policy,
                reschedule_policy=tg0.reschedule_policy,
                tasks=[
                    Task(
                        name="giant-task",
                        driver="mock_driver",
                        resources=dataclasses.replace(
                            tg0.tasks[0].resources,
                            cpu=50_000,  # no node fits
                            memory_mb=512,
                        ),
                    )
                ],
                ephemeral_disk=tg0.ephemeral_disk,
            )
            # giant placed between web groups in the placement stream
            job.task_groups.append(giant)
            return job

        for server in (seq, bat):
            server.register_job(giant_job())
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        assert placements(seq, "mtg-fail") == placements(
            bat, "mtg-fail"
        )
        # the web group placed, the giant group failed on both paths
        seq_evals = seq.store.evals_by_job("default", "mtg-fail")
        bat_evals = bat.store.evals_by_job("default", "mtg-fail")
        def failed_tgs(evs):
            return sorted(
                {
                    name
                    for e in evs
                    for name in (e.failed_tg_allocs or {})
                }
            )
        assert failed_tgs(seq_evals) == failed_tgs(bat_evals)
        assert "giant" in failed_tgs(bat_evals)
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_multi_tg_steady_state_matches():
    """Steady-state multi-group churn (version bump -> destructive
    updates across BOTH groups in one eval) stays bit-identical and
    prescored."""
    import dataclasses

    from nomad_tpu.structs import Task, TaskGroup

    nodes = make_nodes(20, seed=11)
    seq = Server(num_schedulers=1, seed=23, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=23, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))

        def versioned(version):
            job = mock.job(id="mtg-churn", type="batch")
            tg0 = job.task_groups[0]
            tg0.count = 3
            tg0.tasks[0].resources.cpu = 400
            api = TaskGroup(
                name="api",
                count=2,
                restart_policy=tg0.restart_policy,
                reschedule_policy=tg0.reschedule_policy,
                tasks=[
                    Task(
                        name="api-task",
                        driver="mock_driver",
                        resources=dataclasses.replace(
                            tg0.tasks[0].resources,
                            cpu=600,
                            memory_mb=512,
                        ),
                    )
                ],
                ephemeral_disk=tg0.ephemeral_disk,
            )
            job.task_groups.append(api)
            if version:
                for tg in job.task_groups:
                    tg.tasks[0].config = {"command": "/bin/true"}
                job.version = version
            return job

        for server in (seq, bat):
            server.register_job(versioned(0))
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        assert placements(seq, "mtg-churn") == placements(
            bat, "mtg-churn"
        )
        # destructive update across both groups in one eval
        for server in (seq, bat):
            server.register_job(versioned(1))
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        assert placements(seq, "mtg-churn") == placements(
            bat, "mtg-churn"
        )
        assert bat.workers[0].prescored >= 2, (
            bat.workers[0].prescored,
            bat.workers[0].fallbacks,
        )
    finally:
        seq.stop()
        bat.stop()


def test_warm_shapes_are_recognized_by_launch_gate(monkeypatch):
    """warm_shapes must register signatures under the same key
    _launch_ready looks up (fn-name prefix included) — otherwise every
    pre-warmed shape still counts a cold_shape_fallback on first
    production sighting and the warm-up is defeated."""
    monkeypatch.delenv("NOMAD_TPU_SYNC_COMPILE", raising=False)
    bat = Server(num_schedulers=1, seed=3, batch_pipeline=True)
    bat.start()
    try:
        bat.register_node(mock.node())
        worker = bat.workers[0]
        worker.warm_shapes(
            e_buckets=(8,), p_buckets=(16,), t_buckets=(1,)
        )
        table = bat.store.node_table
        inert = worker._inert_inputs(table, P=16, T=1)
        import numpy as np
        stacked = type(inert)(
            *[
                np.stack([getattr(inert, f)] * 8)
                for f in type(inert)._fields
            ]
        )
        args = (
            table.cpu_total, table.mem_total, table.disk_total,
            table.cpu_used, table.mem_used, table.disk_used,
            stacked, np.full(8, 1, np.int32), 16,
        )
        kwargs = dict(
            spread_fit=False, wanted=np.zeros(8, np.int32),
            coll0=None, affinity=None, spread=None,
            deltas=worker._zero_deltas(8, 16),
            pre=worker._zero_pre(8),
            # production chunk launches always ask for the carry
            return_carry=True,
        )
        assert worker._launch_ready(args, kwargs), (
            "pre-warmed launch shape not recognized"
        )
    finally:
        bat.stop()


def test_batch_pipeline_static_ports_match_sequential():
    """Reserved/static host ports run the prescored path with the
    kernel's walk-slot-neutral collision mask (ops/batch.py
    PortInputs): contended static ports produce placements
    bit-identical to the sequential scheduler (rank.go network path
    skips collided nodes without consuming a walk-limit slot)."""
    from nomad_tpu.structs import NetworkResource, Port

    nodes = make_nodes(10, seed=3)
    seq = Server(num_schedulers=1, seed=77, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=77, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))

        # three jobs fighting over :8080 (each instance needs the
        # port exclusively per node) + one uncontended + one portless
        jobs = []
        for i in range(3):
            job = mock.job(id=f"port-{i}")
            tg = job.task_groups[0]
            tg.count = 3
            tg.tasks[0].resources.cpu = 200
            tg.networks = [
                NetworkResource(
                    mode="host",
                    reserved_ports=[Port(label="http", value=8080)],
                )
            ]
            jobs.append(job)
        other = mock.job(id="port-other")
        other.task_groups[0].count = 2
        other.task_groups[0].networks = [
            NetworkResource(
                mode="host",
                reserved_ports=[Port(label="admin", value=9443)],
            )
        ]
        jobs.append(other)
        plain = mock.job(id="port-plain")
        plain.task_groups[0].count = 2
        jobs.append(plain)

        for job in jobs:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(30)
        for job in jobs:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(60)

        for job in jobs:
            assert placements(seq, job.id) == placements(
                bat, job.id
            ), f"divergence for {job.id}"
        # :8080 really is exclusive per node
        holders = [
            a.node_id
            for i in range(3)
            for a in bat.store.allocs_by_job(
                "default", f"port-{i}"
            )
            if not a.terminal_status()
        ]
        assert len(holders) == len(set(holders)), holders
        worker = bat.workers[0]
        assert worker.prescored >= 3, (
            worker.prescored, worker.fallbacks, worker.errors,
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_static_port_exhaustion_and_release():
    """Port exhaustion fails identically on both paths, and a port
    released by stopping a job is reusable afterwards (the release
    gate in _flush_run keeps the monotone kernel carry exact)."""
    from nomad_tpu.structs import NetworkResource, Port

    nodes = make_nodes(4, seed=21)
    seq = Server(num_schedulers=1, seed=31, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=31, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))

        def port_job(jid, count):
            job = mock.job(id=jid)
            tg = job.task_groups[0]
            tg.count = count
            tg.tasks[0].resources.cpu = 100
            tg.networks = [
                NetworkResource(
                    mode="host",
                    reserved_ports=[Port(label="p", value=7070)],
                )
            ]
            return job

        # 6 asks onto 4 nodes: 4 place, 2 fail/block identically
        for server in (seq, bat):
            server.register_job(port_job("exh", 6))
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        assert placements(seq, "exh") == placements(bat, "exh")
        assert len(placements(bat, "exh")) == 4

        # stop the job; the ports free; a new job reuses them
        for server in (seq, bat):
            server.deregister_job("default", "exh")
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        for server in (seq, bat):
            server.register_job(port_job("reuse", 3))
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        assert placements(seq, "reuse") == placements(bat, "reuse")
        assert len(placements(bat, "reuse")) == 3
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_task_level_static_ports_match():
    """Task-level network asks store their offers in
    tasks[*].networks (never shared.ports) — the port index and the
    kernel mask must see them (code-review r4 finding)."""
    from nomad_tpu.structs import NetworkResource, Port

    nodes = make_nodes(6, seed=2)
    seq = Server(num_schedulers=1, seed=19, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=19, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))

        def task_port_job(jid, count):
            job = mock.job(id=jid)
            tg = job.task_groups[0]
            tg.count = count
            tg.tasks[0].resources.cpu = 100
            tg.tasks[0].resources.networks = [
                NetworkResource(
                    mode="host",
                    reserved_ports=[Port(label="t", value=6060)],
                )
            ]
            return job

        # first job occupies 6060 on 3 nodes via TASK-level offers;
        # the second (separate batch) must see those occupations
        for server in (seq, bat):
            server.register_job(task_port_job("tport-a", 3))
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        for server in (seq, bat):
            server.register_job(task_port_job("tport-b", 3))
        assert seq.drain_to_idle(30)
        assert bat.drain_to_idle(60)
        for jid in ("tport-a", "tport-b"):
            assert placements(seq, jid) == placements(bat, jid), jid
        holders = [
            a.node_id
            for jid in ("tport-a", "tport-b")
            for a in bat.store.allocs_by_job("default", jid)
            if not a.terminal_status()
        ]
        assert len(holders) == 6 and len(set(holders)) == 6, holders
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_device_asks_match_sequential():
    """Device asks run the prescored path with chained free-instance
    accounting (ops/batch.py DeviceInputs): GPU jobs place
    bit-identically to the sequential scheduler, capacity is consumed
    across chained evals, and exhaustion fails identically."""
    from nomad_tpu.structs import RequestedDevice

    nodes = make_nodes(8, seed=6)
    gpu_nodes = [mock.nvidia_node() for _ in range(3)]  # 4 GPUs each
    seq = Server(num_schedulers=1, seed=55, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=55, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes + gpu_nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))

        def gpu_job(jid, count, gpus):
            job = mock.job(id=jid)
            tg = job.task_groups[0]
            tg.count = count
            tg.tasks[0].resources.cpu = 100
            tg.tasks[0].resources.devices = [
                RequestedDevice(name="gpu", count=gpus)
            ]
            return job

        # 3 jobs x 2 instances x 2 GPUs each = 12 GPUs = exactly the
        # cluster's capacity; a 4th job must fail/block
        jobs = [gpu_job(f"gpu-{i}", 2, 2) for i in range(3)]
        jobs.append(gpu_job("gpu-over", 1, 2))
        plain = mock.job(id="gpu-plain")
        plain.task_groups[0].count = 2
        jobs.append(plain)
        for job in jobs:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(30)
        for job in jobs:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(60)

        for job in jobs:
            assert placements(seq, job.id) == placements(
                bat, job.id
            ), f"divergence for {job.id}"
        # every GPU alloc landed on a GPU node, never more than
        # capacity per node
        gpu_ids = {n.id for n in gpu_nodes}
        per_node: dict = {}
        for i in range(3):
            for a in bat.store.allocs_by_job(
                "default", f"gpu-{i}"
            ):
                if a.terminal_status():
                    continue
                assert a.node_id in gpu_ids
                per_node[a.node_id] = per_node.get(
                    a.node_id, 0
                ) + 2
        assert all(v <= 4 for v in per_node.values()), per_node
        worker = bat.workers[0]
        assert worker.prescored >= 3, (
            worker.prescored, worker.fallbacks, worker.errors,
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_all_bad_scores_replay_original_order():
    """When EVERY feasible node scores below the skip threshold (e.g.
    heavy anti-affinity on a small feasible set), the oracle's
    LimitIterator exhausts the source inside the first skip loop and
    replays the diverted nodes in ORIGINAL order — the two-diverted
    reversal quirk applies only when a good emission preceded the
    replay (select.py next()).  Regression for the walk divergence
    found via device asks (kernel picked B where the oracle
    alternates A/B)."""
    import numpy as np

    from nomad_tpu.ops.batch import (
        ChainInputs,
        chained_plan_picks_cols,
    )

    C, E, P, T = 6, 1, 4, 1
    cpu_total = np.full(C, 4000.0)
    mem_total = np.full(C, 8192.0)
    disk_total = np.full(C, 100000.0)
    used_cpu = np.zeros(C)
    used_mem = np.zeros(C)
    used_disk = np.zeros(C)
    used_cpu[[0, 4]] = 100.0
    used_mem[[0, 4]] = 256.0
    feas = np.zeros((E, T, C), bool)
    feas[0, 0, [0, 4]] = True
    stacked = ChainInputs(
        feasible=feas,
        perm=np.arange(C, dtype=np.int32)[None, :],
        ask_cpu=np.full((E, P), 100.0),
        ask_mem=np.full((E, P), 256.0),
        ask_disk=np.full((E, P), 300.0),
        desired_count=np.full((E, P), 4, np.int32),
        limit=np.full((E, P), 3, np.int32),
        distinct_hosts=np.zeros(E, bool),
        tg_idx=np.zeros((E, P), np.int32),
    )
    rows = np.asarray(
        chained_plan_picks_cols(
            cpu_total, mem_total, disk_total,
            used_cpu, used_mem, used_disk,
            stacked, np.full(E, C, np.int32), P,
            wanted=np.full(E, 4, np.int32),
        )[0]
    )
    # picks 2/3: both nodes carry one collision (anti-penalty pushes
    # both below the threshold); the walk must emit them in ORIGINAL
    # shuffle order, alternating exactly like the sequential path
    assert rows[0].tolist() == [0, 4, 0, 4], rows[0]


def test_batch_worker_exports_pipeline_metrics():
    """BatchWorker exports prescored/fallback/mesh-used counters and
    eval-latency percentiles via /v1/metrics (VERDICT r3 weak #7: the
    north-star latency metric must be visible to an operator, not just
    the bench)."""
    import json
    import urllib.request

    from nomad_tpu.api import start_http_server

    bat = Server(num_schedulers=1, seed=9, batch_pipeline=True)
    bat.start()
    http = start_http_server(bat, port=0)
    try:
        for node in make_nodes(8, seed=1):
            bat.register_node(node)
        for job in make_jobs(6, seed=2):
            bat.register_job(job)
        assert bat.drain_to_idle(30)
        base = f"http://127.0.0.1:{http.port}"
        with urllib.request.urlopen(
            base + "/v1/metrics", timeout=10
        ) as resp:
            dump = json.loads(resp.read())
        counters = dump["counters"]
        assert counters.get("batch_worker.prescored", 0) > 0, (
            counters
        )
        # fallback/mesh counters exist (possibly zero on this stream)
        lat = dump["samples"].get("batch_worker.eval_latency_ms")
        assert lat is not None and lat["count"] > 0, dump["samples"]
        assert "p50" in lat and "p99" in lat
        assert lat["p99"] >= lat["p50"] > 0.0
        # prometheus rendering carries the quantiles too
        with urllib.request.urlopen(
            base + "/v1/metrics?format=prometheus", timeout=10
        ) as resp:
            text = resp.read().decode()
        assert 'batch_worker_eval_latency_ms{quantile="0.99"}' in text
    finally:
        http.stop()
        bat.stop()


def test_adaptive_batch_cap_tracks_latency_and_backlog():
    """The adaptive gulp size closes the loop from measured launch/
    replay latency: small batches when keeping up and the full-batch
    estimate blows the budget, full batches under saturation (VERDICT
    r3 #2)."""
    bat = Server(num_schedulers=1, seed=1, batch_pipeline=True)
    try:
        worker = bat.workers[0]
        # keeping up + fast launches: a full batch of 8-wide chunk
        # launches fits the budget
        worker._launch_ewma = {2: 10.0, 4: 12.0, 8: 20.0}
        worker._replay_ewma_ms = 1.0
        assert worker._adaptive_cap() == worker.batch_max

        # keeping up + slow launches: the full batch's chunk chain
        # blows the budget, one wide chunk still fits -> cap 8
        worker._launch_ewma = {2: 30.0, 4: 35.0, 8: 40.0}
        worker._replay_ewma_ms = 5.0
        assert worker._adaptive_cap() == 8

        # launches so slow even one widest chunk misses the budget:
        # the ladder lets the cap narrow to a 4-eval gulp (the old
        # {8, batch_max} candidate set bottomed out at 8)
        worker._launch_ewma = {2: 60.0, 4: 90.0, 8: 260.0}
        worker._replay_ewma_ms = 5.0
        assert worker._adaptive_cap() == 4

        # saturation: backlog >= a full batch -> throughput wins
        class _Broker:
            def ready_count(self, schedulers):
                return worker.batch_max + 5

        real = bat.broker
        bat.broker = _Broker()
        try:
            assert worker._adaptive_cap() == worker.batch_max
        finally:
            bat.broker = real

        # explicit opt-out
        worker.latency_budget_ms = 0.0
        worker._launch_ewma = {2: 9999.0, 4: 9999.0, 8: 9999.0}
        assert worker._adaptive_cap() == worker.batch_max
    finally:
        bat.stop()


def test_adaptive_cap_respects_operator_ceiling(monkeypatch):
    """With NOMAD_TPU_BATCH_MAX below the widest chunk bucket, the
    adaptive cap (and the chunk ladder itself) must never exceed the
    operator's ceiling, and the measured chunk-cost EWMAs still drive
    the decision for non-default ceilings (code-review r4
    findings)."""
    monkeypatch.setenv("NOMAD_TPU_BATCH_MAX", "4")
    bat = Server(num_schedulers=1, seed=1, batch_pipeline=True)
    try:
        worker = bat.workers[0]
        assert worker.batch_max == 4
        assert worker._chunk_buckets() == (2, 4)
        worker._launch_ewma = {2: 10.0, 4: 10.0}
        worker._replay_ewma_ms = 1.0
        assert worker._adaptive_cap() <= 4
    finally:
        bat.stop()
    monkeypatch.setenv("NOMAD_TPU_BATCH_MAX", "32")
    bat = Server(num_schedulers=1, seed=1, batch_pipeline=True)
    try:
        worker = bat.workers[0]
        # a widest-bucket launch too slow for the budget narrows the
        # chunk width AND the gulp: with an unmeasured narrow bucket
        # (seeded at the 50 ms default) only a 4-eval gulp fits
        worker._launch_ewma = {8: 400.0}
        worker._replay_ewma_ms = 5.0
        assert worker._adaptive_cap() == 4
    finally:
        bat.stop()


def test_batch_pipeline_device_affinities_match_sequential():
    """Device AFFINITIES run the prescored path (r5): the allocator's
    matched-weight fraction (reference rank.go:443-461) becomes a
    static per-node kernel score column, exact because the chain gates
    guarantee at most one matching group per node.  Jobs preferring
    big-memory GPUs place bit-identically to the sequential scheduler
    WITHOUT falling back."""
    from nomad_tpu.structs import Affinity, NodeDeviceResource, RequestedDevice

    nodes = make_nodes(6, seed=9)
    big = [mock.nvidia_node() for _ in range(2)]  # memory=11169
    small = []
    for _ in range(2):
        n = mock.node()
        n.node_resources.devices = [
            NodeDeviceResource(
                vendor="nvidia",
                type="gpu",
                name="2070",
                instance_ids=[mock.new_id() for _ in range(4)],
                attributes={"memory": "8000"},
            )
        ]
        n.computed_class = compute_node_class(n)
        small.append(n)

    seq = Server(num_schedulers=1, seed=77, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=77, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes + big + small:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))

        def aff_job(jid, count, weight):
            job = mock.job(id=jid)
            tg = job.task_groups[0]
            tg.count = count
            tg.tasks[0].resources.cpu = 100
            tg.tasks[0].resources.devices = [
                RequestedDevice(
                    name="gpu",
                    count=1,
                    affinities=[
                        Affinity(
                            ltarget="${device.attr.memory}",
                            rtarget="10000",
                            operand=">=",
                            weight=weight,
                        )
                    ],
                )
            ]
            return job

        jobs = [
            aff_job("gaff-pos", 3, 75),   # prefers 11169-memory nodes
            aff_job("gaff-neg", 2, -40),  # avoids them
            aff_job("gaff-more", 4, 75),  # spills after big fills
        ]
        for job in jobs:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(30)
        for job in jobs:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(60)

        for job in jobs:
            assert placements(seq, job.id) == placements(
                bat, job.id
            ), f"divergence for {job.id}"
        # sanity: every alloc landed on a GPU-bearing node and the
        # big-memory nodes got at least one positive-affinity pick
        # (the affinity is soft — binpack + anti-affinity + the
        # unlifted walk limit legitimately spread the rest)
        gpu_ids = {n.id for n in big + small}
        placed = [
            a.node_id
            for a in bat.store.allocs_by_job("default", "gaff-pos")
            if not a.terminal_status()
        ]
        assert placed and set(placed) <= gpu_ids, placed
        assert set(placed) & {n.id for n in big}, placed
        worker = bat.workers[0]
        assert worker.prescored >= 3, (
            worker.prescored, worker.fallbacks, worker.errors,
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_preemption_retry_matches_sequential():
    """Preemption retries run from the prescored path (r5): when a
    prescored pick fails and preemption is enabled, PrescoredStack
    seeds the inner oracle with the recorded shuffle order and the
    kernel's walk-offset (pulls) and hands the eval's remainder to it
    — placements AND preempted-alloc sets must match the sequential
    scheduler bit for bit, without a full-eval fallback."""
    from nomad_tpu.structs import (
        PreemptionConfig,
        SchedulerConfiguration,
    )

    def small_node():
        n = mock.node()
        n.node_resources.cpu = 2000
        n.node_resources.memory_mb = 2048
        n.computed_class = compute_node_class(n)
        return n

    nodes = [small_node() for _ in range(6)]
    seq = Server(num_schedulers=1, seed=91, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=91, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for server in (seq, bat):
            for node in nodes:
                server.register_node(copy.deepcopy(node))
            server.store.set_scheduler_config(
                SchedulerConfiguration(
                    preemption_config=PreemptionConfig(
                        service_scheduler_enabled=True
                    )
                )
            )

        # fill the whole fleet with low-priority occupants
        low = mock.job(id="occ")
        low.priority = 20
        low.task_groups[0].count = 6
        low.task_groups[0].tasks[0].resources.cpu = 1500
        low.task_groups[0].tasks[0].resources.memory_mb = 1200
        # then a high-priority job that can only place by preempting
        high = mock.job(id="vip")
        high.priority = 80
        high.task_groups[0].count = 2
        high.task_groups[0].tasks[0].resources.cpu = 1200
        high.task_groups[0].tasks[0].resources.memory_mb = 1000

        for server in (seq, bat):
            server.register_job(copy.deepcopy(low))
            assert server.drain_to_idle(30)
            server.register_job(copy.deepcopy(high))
            assert server.drain_to_idle(30)

        assert placements(seq, "vip") == placements(bat, "vip")
        assert len(placements(seq, "vip")) == 2

        def preempted(server):
            return sorted(
                a.name
                for a in server.store.allocs_by_job("default", "occ")
                if a.desired_status == "evict"
            )

        assert preempted(seq) == preempted(bat)
        assert preempted(bat)  # something actually got preempted

        worker = bat.workers[0]
        # the vip eval went through the prescored path and the
        # preemption PASSTHROUGH engaged (not a full-eval fallback)
        assert worker.prescored >= 2, (
            worker.prescored, worker.fallbacks, worker.errors,
        )
        assert worker.preempt_passthroughs >= 1
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_preemption_mid_eval_offset():
    """The passthrough seeds the oracle's rotating walk offset from
    the kernel's pulls: picks that SUCCEED before the failing one
    advance the walk, so the preempt retry (and later picks) must
    start from the same rotation as the sequential run.  One node is
    left free so pick 1 places normally and pick 2+ preempt."""
    from nomad_tpu.structs import (
        PreemptionConfig,
        SchedulerConfiguration,
    )

    def small_node():
        n = mock.node()
        n.node_resources.cpu = 2000
        n.node_resources.memory_mb = 2048
        n.computed_class = compute_node_class(n)
        return n

    nodes = [small_node() for _ in range(8)]
    seq = Server(num_schedulers=1, seed=23, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=23, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for server in (seq, bat):
            for node in nodes:
                server.register_node(copy.deepcopy(node))
            server.store.set_scheduler_config(
                SchedulerConfiguration(
                    preemption_config=PreemptionConfig(
                        service_scheduler_enabled=True,
                        batch_scheduler_enabled=True,
                    )
                )
            )

        # occupants on 7 of 8 nodes (count=7 < fleet): one node stays
        # free for the vip's first pick
        low = mock.job(id="occ2")
        low.priority = 10
        low.task_groups[0].count = 7
        low.task_groups[0].tasks[0].resources.cpu = 1500
        low.task_groups[0].tasks[0].resources.memory_mb = 1200
        vip = mock.job(id="vip2")
        vip.priority = 90
        vip.task_groups[0].count = 3
        vip.task_groups[0].tasks[0].resources.cpu = 1200
        vip.task_groups[0].tasks[0].resources.memory_mb = 900

        for server in (seq, bat):
            server.register_job(copy.deepcopy(low))
            assert server.drain_to_idle(30)
            server.register_job(copy.deepcopy(vip))
            assert server.drain_to_idle(30)

        assert placements(seq, "vip2") == placements(bat, "vip2")
        assert len(placements(seq, "vip2")) == 3

        def preempted(server):
            return sorted(
                a.name
                for a in server.store.allocs_by_job(
                    "default", "occ2"
                )
                if a.desired_status == "evict"
            )

        assert preempted(seq) == preempted(bat)
        assert preempted(bat)
        assert bat.workers[0].preempt_passthroughs >= 1
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_mixed_group_device_affinity():
    """A multi-task-group job where only ONE group's device ask has
    affinities must still prescore (regression: stacking [col, None]
    raised and demoted the whole flush to the sequential path)."""
    from nomad_tpu.structs import Affinity, RequestedDevice, TaskGroup, Task, Resources

    nodes = make_nodes(4, seed=3)
    gpus = [mock.nvidia_node() for _ in range(2)]
    seq = Server(num_schedulers=1, seed=41, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=41, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes + gpus:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        job = mock.job(id="mixed-aff")
        g1 = job.task_groups[0]
        g1.count = 2
        g1.tasks[0].resources.cpu = 100
        g1.tasks[0].resources.devices = [
            RequestedDevice(
                name="gpu",
                count=1,
                affinities=[
                    Affinity(
                        ltarget="${device.attr.memory}",
                        rtarget="10000",
                        operand=">=",
                        weight=60,
                    )
                ],
            )
        ]
        job.task_groups.append(
            TaskGroup(
                name="plain",
                count=2,
                tasks=[
                    Task(
                        name="p",
                        driver="mock_driver",
                        resources=Resources(cpu=100, memory_mb=64),
                    )
                ],
            )
        )
        seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(30)
        bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(30)
        assert placements(seq, "mixed-aff") == placements(
            bat, "mixed-aff"
        )
        assert len(placements(bat, "mixed-aff")) == 4
        worker = bat.workers[0]
        assert worker.errors == 0, (
            worker.prescored, worker.fallbacks, worker.errors,
        )
        assert worker.prescored >= 1
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_multi_tg_distinct_hosts():
    """Multi-task-group jobs WITH distinct_hosts run the prescored
    path (r5): the job-wide occupancy = per-group collision carries +
    an occ_extra column for groups placing nothing this eval.  The
    second eval (scaling ONE group) must see the other group's
    existing allocs as occupied nodes, bit-identically to the
    sequential scheduler."""
    from nomad_tpu.structs import (
        CONSTRAINT_DISTINCT_HOSTS,
        Constraint,
        Resources,
        Task,
        TaskGroup,
    )

    nodes = make_nodes(10, seed=5)
    seq = Server(num_schedulers=1, seed=13, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=13, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))

        def dh_job(count_a, count_b):
            job = mock.job(id="dh-multi")
            job.constraints.append(
                Constraint(operand=CONSTRAINT_DISTINCT_HOSTS)
            )
            ga = job.task_groups[0]
            ga.name = "a"
            ga.count = count_a
            ga.tasks[0].resources.cpu = 100
            job.task_groups.append(
                TaskGroup(
                    name="b",
                    count=count_b,
                    tasks=[
                        Task(
                            name="t",
                            driver="mock_driver",
                            resources=Resources(
                                cpu=100, memory_mb=64
                            ),
                        )
                    ],
                )
            )
            return job

        for server in (seq, bat):
            server.register_job(copy.deepcopy(dh_job(3, 3)))
            assert server.drain_to_idle(30)
        assert placements(seq, "dh-multi") == placements(
            bat, "dh-multi"
        )
        assert len(placements(bat, "dh-multi")) == 6
        # scale ONLY group b: group a's allocs have no picks this
        # eval and must still block their nodes (occ_extra)
        for server in (seq, bat):
            job2 = dh_job(3, 6)
            job2.version = 1
            server.register_job(copy.deepcopy(job2))
            assert server.drain_to_idle(30)
        p_seq = placements(seq, "dh-multi")
        p_bat = placements(bat, "dh-multi")
        assert p_seq == p_bat
        assert len(p_bat) == 9
        # distinct_hosts really held: no node carries two allocs
        nodes_used = [n for _name, n in p_bat]
        assert len(nodes_used) == len(set(nodes_used))
        worker = bat.workers[0]
        assert worker.prescored >= 2, (
            worker.prescored, worker.fallbacks, worker.errors,
        )
    finally:
        seq.stop()
        bat.stop()


def test_batch_pipeline_group_level_distinct_hosts():
    """GROUP-level distinct_hosts has per-group semantics (feasible.py
    _satisfies: job AND task collision): group A's picks avoid only
    A's own allocs while group B packs freely — the kernel's dh_tg
    mask must reproduce the sequential scheduler bit for bit, NOT
    job-wide blocking."""
    from nomad_tpu.structs import (
        CONSTRAINT_DISTINCT_HOSTS,
        Constraint,
        Resources,
        Task,
        TaskGroup,
    )

    nodes = make_nodes(4, seed=8)
    seq = Server(num_schedulers=1, seed=19, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=19, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))

        job = mock.job(id="dh-group")
        ga = job.task_groups[0]
        ga.name = "a"
        ga.count = 4  # one per node: group-level distinct
        ga.constraints.append(
            Constraint(operand=CONSTRAINT_DISTINCT_HOSTS)
        )
        ga.tasks[0].resources.cpu = 100
        job.task_groups.append(
            TaskGroup(
                name="b",
                count=6,  # MORE than nodes: must co-locate with a's
                tasks=[
                    Task(
                        name="t",
                        driver="mock_driver",
                        resources=Resources(cpu=100, memory_mb=64),
                    )
                ],
            )
        )
        for server in (seq, bat):
            server.register_job(copy.deepcopy(job))
            assert server.drain_to_idle(30)
        p_seq = placements(seq, "dh-group")
        p_bat = placements(bat, "dh-group")
        assert p_seq == p_bat
        assert len(p_bat) == 10  # 4 + 6: B placed despite A's spread
        # A's allocs really are one-per-node; B co-locates freely
        a_nodes = [n for name, n in p_bat if ".a[" in name]
        assert len(a_nodes) == len(set(a_nodes)) == 4
        worker = bat.workers[0]
        assert worker.prescored >= 1, (
            worker.prescored, worker.fallbacks, worker.errors,
        )
    finally:
        seq.stop()
        bat.stop()


# ---------------------------------------------------------------------------
# pipelined prescore: chunked carry launches + snapshot-delta input cache
# ---------------------------------------------------------------------------


def test_chunked_carry_launches_match_single_launch():
    """Splitting one E-eval chain into PIPELINE_CHUNK-wide launches
    threaded through the kernel's carry output (return_carry=True) is
    bit-identical to the single launch — the invariant the pipelined
    prescore rests on (a lax.scan cut at an eval boundary)."""
    import numpy as np

    from nomad_tpu.ops.batch import (
        ChainInputs,
        chained_plan_picks_cols,
    )

    rng = np.random.default_rng(7)
    C, E, P = 32, 16, 4
    cpu_total = np.full(C, 4000.0)
    mem_total = np.full(C, 8192.0)
    disk_total = np.full(C, 100000.0)
    used = (
        rng.random(C) * 1000,
        rng.random(C) * 2000,
        rng.random(C) * 100,
    )
    stacked = ChainInputs(
        feasible=np.ones((E, 1, C), bool),
        perm=np.stack(
            [rng.permutation(C).astype(np.int32) for _ in range(E)]
        ),
        ask_cpu=np.full((E, P), 100.0),
        ask_mem=np.full((E, P), 256.0),
        ask_disk=np.full((E, P), 300.0),
        desired_count=np.full((E, P), 4, np.int32),
        limit=np.full((E, P), 5, np.int32),
        distinct_hosts=np.zeros(E, bool),
        tg_idx=np.zeros((E, P), np.int32),
    )
    nc = np.full(E, C, np.int32)
    wanted = np.full(E, 4, np.int32)
    r_full, p_full = (
        np.asarray(x)
        for x in chained_plan_picks_cols(
            cpu_total, mem_total, disk_total, *used,
            stacked, nc, P, wanted=wanted,
        )
    )

    def sl(x, a, b):
        return type(x)(*[f[a:b] for f in x])

    carry = None
    rows, pulls = [], []
    for a in range(0, E, 8):
        b = a + 8
        u = used if carry is None else carry[0]
        r, p, carry = chained_plan_picks_cols(
            cpu_total, mem_total, disk_total, u[0], u[1], u[2],
            sl(stacked, a, b), nc[a:b], P, wanted=wanted[a:b],
            return_carry=True,
        )
        rows.append(np.asarray(r))
        pulls.append(np.asarray(p))
    assert (np.concatenate(rows) == r_full).all()
    assert (np.concatenate(pulls) == p_full).all()


def test_pipelined_multi_chunk_gulp_matches_sequential():
    """A burst larger than PIPELINE_CHUNK forces multi-chunk pipelined
    runs (chunk N+1 chains on N's device carry while N-1 replays);
    placements must stay bit-identical to the sequential scheduler."""
    nodes = make_nodes(24, seed=21)
    jobs = make_jobs(20, seed=22)

    seq = Server(num_schedulers=1, seed=55, batch_pipeline=False)
    bat = Server(num_schedulers=1, seed=55, batch_pipeline=True)
    seq.start()
    bat.start()
    try:
        for node in nodes:
            seq.register_node(copy.deepcopy(node))
            bat.register_node(copy.deepcopy(node))
        for job in jobs:
            seq.register_job(copy.deepcopy(job))
        assert seq.drain_to_idle(30)
        # burst-register so the worker drains multi-chunk gulps
        for job in jobs:
            bat.register_job(copy.deepcopy(job))
        assert bat.drain_to_idle(60)
        for job in jobs:
            assert placements(seq, job.id) == placements(
                bat, job.id
            ), f"divergence for {job.id}"
        worker = bat.workers[0]
        assert worker.prescored > 0
        assert worker.timings["assemble"] > 0.0
        # mesh workers (NOMAD_TPU_MESH=1) realize under mesh_fetch
        assert (
            worker.timings["fetch"] > 0.0
            or worker.timings["mesh_fetch"] > 0.0
        )
    finally:
        seq.stop()
        bat.stop()


def test_input_cache_delta_patch_bit_identical():
    """The device-resident usage mirror, delta-patched from the
    store's dirty-row log, must stay bit-identical to from-scratch
    assembly (the live table columns) after a plan commit, a node
    drain, a node register and a driver re-fingerprint."""
    import numpy as np

    bat = Server(num_schedulers=1, seed=31, batch_pipeline=True)
    bat.start()
    try:
        nodes = make_nodes(10, seed=5)
        for node in nodes:
            bat.register_node(node)
        worker = bat.workers[0]
        table = bat.store.node_table

        def assert_mirror_exact(label):
            cols = worker._device_columns(table)
            for got, want in zip(
                cols,
                (
                    table.cpu_total, table.mem_total,
                    table.disk_total, table.cpu_used,
                    table.mem_used, table.disk_used,
                ),
            ):
                np.testing.assert_array_equal(
                    np.asarray(got), want, err_msg=label
                )

        assert_mirror_exact("initial sync")

        # plan commit: usage columns change, topology doesn't -> the
        # dirty-row patch path must reproduce the columns exactly
        for job in make_jobs(3, seed=9):
            bat.register_job(job)
        assert bat.drain_to_idle(30)
        assert_mirror_exact("after plan commit")
        assert worker._input_cache_hits > 0, (
            worker._input_cache_hits, worker._input_cache_misses
        )

        # node drain: topology generation bumps -> full resync
        bat.store.update_node_drain(nodes[0].id, True)
        assert_mirror_exact("after node drain")

        # node register: arena may grow / new row
        extra = make_nodes(1, seed=77)[0]
        bat.register_node(extra)
        assert_mirror_exact("after node register")

        # driver re-fingerprint: re-upsert with changed attributes
        # (totals untouched, but rows could have been reassigned)
        refp = nodes[1]
        refp.attributes = dict(refp.attributes)
        refp.attributes["driver.raw_exec"] = "1"
        bat.store.upsert_node(refp)
        assert_mirror_exact("after driver re-fingerprint")

        # steady state again: another commit after the topo churn
        for job in make_jobs(2, seed=13):
            job.id = job.id + "-post"
            bat.register_job(job)
        assert bat.drain_to_idle(30)
        assert_mirror_exact("after post-churn commit")
    finally:
        bat.stop()


def test_input_cache_hit_rate_exported_on_second_flush():
    """Smoke: two consecutive flushes through the BatchWorker must
    export a batch_worker.input_cache_hit_rate gauge > 0 on /v1/metrics
    after the second flush — the delta cache can't silently stop
    engaging."""
    import json
    import urllib.request

    from nomad_tpu.api import start_http_server

    bat = Server(num_schedulers=1, seed=17, batch_pipeline=True)
    bat.start()
    http = start_http_server(bat, port=0)
    try:
        for node in make_nodes(8, seed=4):
            bat.register_node(node)
        # flush 1: first sync of the device mirror (a miss)
        bat.register_job(make_jobs(1, seed=41)[0])
        assert bat.drain_to_idle(30)
        # flush 2: the plan commit above dirtied rows -> delta patch
        job2 = make_jobs(1, seed=42)[0]
        job2.id = "cache-hit-probe"
        bat.register_job(job2)
        assert bat.drain_to_idle(30)
        worker = bat.workers[0]
        assert worker.prescored >= 2, (
            worker.prescored, worker.fallbacks, worker.errors
        )
        base = f"http://127.0.0.1:{http.port}"
        with urllib.request.urlopen(
            base + "/v1/metrics", timeout=10
        ) as resp:
            dump = json.loads(resp.read())
        # a mesh worker's flushes sync the SHARDED mirror instead;
        # its hit rate is the mesh.mirror_hit_rate gauge
        rate = dump["gauges"].get(
            "batch_worker.input_cache_hit_rate"
        )
        if worker._mesh is not None and not rate:
            rate = dump["gauges"].get("mesh.mirror_hit_rate")
        assert rate is not None, dump["gauges"]
        assert rate > 0.0, dump["gauges"]
    finally:
        http.stop()
        bat.stop()


def test_assembly_caches_are_lru_not_clear_all():
    """A one-off job signature must evict only the coldest cache entry,
    not every warm one (the old clear-all-on-overflow behavior)."""
    from nomad_tpu.server.batch_worker import _LRUCache

    lru = _LRUCache(3)
    for i in range(3):
        lru.put(("gen", i), i)
    # touch entry 0 so it is the warmest
    assert lru.get(("gen", 0)) == 0
    lru.put(("gen", 99), 99)  # one-off: evicts only the coldest (1)
    assert lru.get(("gen", 1)) is None
    assert lru.get(("gen", 0)) == 0
    assert lru.get(("gen", 2)) == 2
    assert lru.get(("gen", 99)) == 99


# ---------------------------------------------------------------------------
# optimistic parallel replay (PR 2)
# ---------------------------------------------------------------------------


def _eval_outcomes(server, job_id):
    """Terminal eval outcomes for a job, order-insensitive (eval ids
    are random per server, so compare the decision-bearing fields)."""
    return sorted(
        (
            e.status,
            e.status_description,
            tuple(sorted(e.queued_allocations.items())),
        )
        for e in server.store.evals_by_job("default", job_id)
    )


def _run_conflict_pair(monkeypatch, strict):
    """Serial-replay vs parallel-replay servers on a tiny cluster
    where every plan in a wave touches nodes an earlier-committed
    plan mutated.  Returns (serial, par, jobs) after both drained."""
    nodes = make_nodes(6, seed=5)
    jobs = []
    for i in range(10):
        job = mock.job(id=f"conflict-{i}")
        job.task_groups[0].count = random.Random(i).randint(2, 3)
        job.task_groups[0].tasks[0].resources.cpu = 300
        jobs.append(job)

    monkeypatch.setenv("NOMAD_TPU_PARALLEL_REPLAY", "0")
    serial = Server(num_schedulers=1, seed=42, batch_pipeline=True)
    monkeypatch.setenv("NOMAD_TPU_PARALLEL_REPLAY", "1")
    if strict:
        monkeypatch.setenv("NOMAD_TPU_REPLAY_STRICT", "1")
    par = Server(num_schedulers=1, seed=42, batch_pipeline=True)
    assert not serial.workers[0].parallel_replay
    assert par.workers[0].parallel_replay
    assert par.workers[0].replay_strict == strict
    serial.start()
    par.start()
    for node in nodes:
        serial.register_node(copy.deepcopy(node))
        par.register_node(copy.deepcopy(node))
    for job in jobs:
        serial.register_job(copy.deepcopy(job))
    assert serial.drain_to_idle(30)
    for job in jobs:
        par.register_job(copy.deepcopy(job))
    assert par.drain_to_idle(30)
    return serial, par, jobs


def test_parallel_replay_bit_identical_under_forced_conflicts(
    monkeypatch,
):
    """The acceptance contract, strict mode: with a tiny cluster
    every plan in a wave touches nodes an earlier-committed plan
    mutated, forcing the conflict ledger to discard speculations and
    re-replay serially — and the committed outcome must stay
    bit-identical to the serial replay loop."""
    serial, par, jobs = _run_conflict_pair(monkeypatch, strict=True)
    try:
        for job in jobs:
            assert placements(serial, job.id) == placements(
                par, job.id
            ), f"divergence for {job.id}"
            assert _eval_outcomes(serial, job.id) == _eval_outcomes(
                par, job.id
            ), f"eval outcome divergence for {job.id}"
        worker = par.workers[0]
        # the forced contention must actually exercise the conflict
        # path (otherwise this test proves nothing)
        assert worker.replay_conflicts > 0
        assert worker.replay_serial_fallbacks > 0
        assert worker.prescored > 0
    finally:
        serial.stop()
        par.stop()


def test_parallel_replay_relaxed_mode_decisions_match_under_contention(
    monkeypatch,
):
    """Default (relaxed) mode on the same contended cluster: own-wave
    plan-node touches are expected (the kernel chain modeled them),
    so speculations commit — and placements plus eval outcomes must
    still match the serial replay loop exactly."""
    serial, par, jobs = _run_conflict_pair(monkeypatch, strict=False)
    try:
        for job in jobs:
            assert placements(serial, job.id) == placements(
                par, job.id
            ), f"divergence for {job.id}"
            assert _eval_outcomes(serial, job.id) == _eval_outcomes(
                par, job.id
            ), f"eval outcome divergence for {job.id}"
        worker = par.workers[0]
        # fresh jobs have no strict nodes, so the relaxed check must
        # actually commit speculations despite the node contention
        assert worker.replay_speculative > 0
    finally:
        serial.stop()
        par.stop()


def test_parallel_replay_commits_speculations_without_conflicts():
    """Disjoint candidate sets (one job per datacenter) commit their
    speculative replays — the fast path must actually engage, with
    zero conflicts, and the counters must be visible on /v1/metrics."""
    server = Server(num_schedulers=1, seed=11, batch_pipeline=True)
    server.start()
    try:
        n_dcs = 6
        for dc in range(n_dcs):
            for node in make_nodes(2, seed=dc):
                node.datacenter = f"dc{dc}"
                node.computed_class = compute_node_class(node)
                server.register_node(node)
        for dc in range(n_dcs):
            job = mock.job(id=f"dc-job-{dc}")
            job.datacenters = [f"dc{dc}"]
            job.task_groups[0].count = 2
            server.register_job(job)
        assert server.drain_to_idle(30)
        worker = server.workers[0]
        for dc in range(n_dcs):
            assert len(placements(server, f"dc-job-{dc}")) == 2
        assert worker.replay_speculative > 0
        assert worker.replay_conflicts == 0
        assert server.metrics.get_counter("replay.speculative") > 0
        assert (
            server.metrics.get_gauge("batch_worker.replay_parallelism")
            >= 1
        )
        assert (
            server.metrics.get_gauge(
                "batch_worker.parallel_replay_enabled"
            )
            == 1.0
        )
    finally:
        server.stop()


def test_parallel_replay_failed_placements_match_serial(monkeypatch):
    """Exhaustion (failed picks -> blocked evals) through the
    speculative wave must produce the same blocked/complete eval
    outcomes as the serial replay loop."""
    nodes = make_nodes(3, seed=2)
    jobs = []
    for i in range(6):
        job = mock.job(id=f"exhaust-{i}")
        job.task_groups[0].count = 4
        job.task_groups[0].tasks[0].resources.cpu = 3000
        jobs.append(job)

    monkeypatch.setenv("NOMAD_TPU_PARALLEL_REPLAY", "0")
    serial = Server(num_schedulers=1, seed=3, batch_pipeline=True)
    monkeypatch.setenv("NOMAD_TPU_PARALLEL_REPLAY", "1")
    par = Server(num_schedulers=1, seed=3, batch_pipeline=True)
    serial.start()
    par.start()
    try:
        for node in nodes:
            serial.register_node(copy.deepcopy(node))
            par.register_node(copy.deepcopy(node))
        for job in jobs:
            serial.register_job(copy.deepcopy(job))
        assert serial.drain_to_idle(30)
        for job in jobs:
            par.register_job(copy.deepcopy(job))
        assert par.drain_to_idle(30)
        for job in jobs:
            assert placements(serial, job.id) == placements(
                par, job.id
            ), f"divergence for {job.id}"
    finally:
        serial.stop()
        par.stop()


def test_adaptive_cap_latency_budget_boundary_and_broker_errors():
    """_adaptive_cap edges: the budget boundary is inclusive (est ==
    budget keeps the big gulp; one tenth of a ms over drops to a
    chunk-sized gulp) and a broker error falls back to the full
    batch."""
    bat = Server(num_schedulers=1, seed=1, batch_pipeline=True)
    try:
        worker = bat.workers[0]
        worker.latency_budget_ms = 250.0
        # keeping up (empty broker): estimated last-eval latency for
        # a 64-eval gulp = 8 launches x the 8-wide chunk cost EWMA
        # + 1 * replay EWMA = 8 * 30.625 + 5 = 250.0 exactly
        worker._replay_ewma_ms = 5.0
        worker._launch_ewma = {2: 10.0, 4: 10.0, 8: 30.625}
        assert worker._adaptive_cap() == worker.batch_max  # est == 250
        worker._launch_ewma = {2: 10.0, 4: 10.0, 8: 30.6375}
        assert worker._adaptive_cap() == 8  # est just over budget

        # a broken broker must not kill sizing: full batch fallback
        class _Exploding:
            def ready_count(self, schedulers):
                raise RuntimeError("broker down")

        real = bat.broker
        bat.broker = _Exploding()
        try:
            worker._launch_ewma = {2: 9999.0, 4: 9999.0, 8: 9999.0}
            assert worker._adaptive_cap() == worker.batch_max
        finally:
            bat.broker = real
    finally:
        bat.stop()


def test_adaptive_cap_inputs_exported_as_gauges():
    """Operators can see WHY _adaptive_cap picked a gulp size: the
    launch EWMA per trace bucket and the replay EWMA are /v1/metrics
    gauges (satellite of PR 2)."""
    server = Server(num_schedulers=1, seed=4, batch_pipeline=True)
    server.start()
    try:
        for node in make_nodes(8, seed=1):
            server.register_node(node)
        for job in make_jobs(4, seed=2):
            server.register_job(job)
        assert server.drain_to_idle(30)
        gauges = server.metrics.dump()["gauges"]
        assert "batch_worker.replay_ewma_ms" in gauges
        # chunk buckets export as .e<width>, mesh buckets as .m<width>
        assert any(
            k.startswith("batch_worker.launch_ewma_ms.e")
            or k.startswith("batch_worker.launch_ewma_ms.m")
            for k in gauges
        ), gauges
    finally:
        server.stop()


def _served_wave(nodes, jobs):
    """A wave of prescored evals through one served worker: what it
    committed (ids aside), the rank.fit_* counters and how many
    speculations committed."""
    server = Server(num_schedulers=1, seed=34, batch_pipeline=True)
    server.start()
    try:
        for node in nodes:
            server.register_node(copy.deepcopy(node))
        for job in jobs:
            server.register_job(copy.deepcopy(job))
        assert server.drain_to_idle(30)
        committed = {
            job.id: sorted(
                (
                    a.name, a.node_id, a.metrics.nodes_evaluated,
                    tuple(sorted(a.metrics.scores.items())),
                    tuple(
                        (m.node_id, tuple(sorted(m.scores.items())),
                         m.norm_score)
                        for m in a.metrics.score_meta
                    ),
                    tuple(sorted(
                        (name, tr.cpu, tr.memory_mb)
                        for name, tr in a.allocated_resources.tasks.items()
                    )),
                )
                for a in server.store.allocs_by_job("default", job.id)
                if not a.terminal_status()
            )
            for job in jobs
        }
        worker = server.workers[0]
        assert worker.prescored > 0 and worker.fallbacks == 0
        counters = server.metrics.dump()["counters"]
        return {
            "committed": committed,
            "fast": counters["rank.fit_fast"],
            "full": counters["rank.fit_full"],
            "speculated": worker.replay_speculative,
        }
    finally:
        server.stop()


def test_prescored_wave_commits_the_walks_answers_from_the_aggregate(
    monkeypatch,
):
    """The winner's exact check reads the store's live aggregate on the
    served path (`rank.fit_fast`, no `rank.fit_full`), and commits what
    the same wave commits over snapshots that hide the aggregate and so
    walk the node's allocations: the same nodes, scores, nodes
    evaluated and task resources.  One job a datacenter: the wave's
    speculations read no node another member writes, so what each
    records does not depend on the order they ran in."""
    from nomad_tpu.state.store import StateSnapshot

    nodes, jobs = [], []
    for dc in range(6):
        for node in make_nodes(3, seed=34 + dc):
            node.datacenter = f"dc{dc}"
            node.computed_class = compute_node_class(node)
            nodes.append(node)
        job = make_jobs(1, seed=40 + dc)[0]
        job.id = f"fit-wave-{dc}"
        job.datacenters = [f"dc{dc}"]
        job.task_groups[0].count = 2 + dc % 3
        jobs.append(job)
    placed = sum(j.task_groups[0].count for j in jobs)
    fast = _served_wave(nodes, jobs)
    assert sum(len(v) for v in fast["committed"].values()) == placed
    assert fast["fast"] >= placed and fast["full"] == 0
    assert fast["speculated"] > 0
    # the schedulers' view only: the plan applier reads the store
    monkeypatch.delattr(StateSnapshot, "node_fit_usage")
    walked = _served_wave(nodes, jobs)
    assert walked["fast"] == 0 and walked["full"] >= placed
    assert walked["speculated"] > 0
    assert fast["committed"] == walked["committed"]


def test_deq_ts_is_bounded_and_popped_on_nack():
    """The dequeue-timestamp map must not leak: nacked evals pop their
    stamp, and the map sheds oldest-first past DEQ_TS_MAX even when
    evals vanish without an ack or nack."""
    from nomad_tpu.server.batch_worker import DEQ_TS_MAX
    from nomad_tpu.structs import Evaluation

    server = Server(num_schedulers=1, seed=6, batch_pipeline=True)
    try:
        worker = server.workers[0]
        for i in range(DEQ_TS_MAX + 100):
            worker._note_dequeue(Evaluation(id=f"ev-{i}"))
        assert len(worker._deq_ts) <= DEQ_TS_MAX
        # oldest were shed first
        assert "ev-0" not in worker._deq_ts
        ev = Evaluation(id="nacked")
        worker._note_dequeue(ev)
        worker._nack_quietly(ev, "tok")  # unknown token: still pops
        assert "nacked" not in worker._deq_ts
    finally:
        server.stop()


def test_chained_kernel_carries_phase_scopes_under_its_old_name():
    """The phases of a pick-step are `jax.named_scope`s (score, spread,
    walk, usage_update), so an operation of a device profile can be
    put to a phase from its metadata — and the jit name, by which the
    benchmark finds the kernel (`jit_chained_plan_picks_cols*`) and a
    launch shape's compile (`jit(chained_plan_picks_cols`), is what it
    was."""
    import re

    import numpy as np

    from nomad_tpu.ops.batch import (
        ChainInputs,
        SpreadInputs,
        chained_plan_picks_cols,
        chained_plan_picks_cols_donated,
    )

    assert chained_plan_picks_cols.__name__ == "chained_plan_picks_cols"
    assert (
        chained_plan_picks_cols_donated().__name__
        == "chained_plan_picks_cols_donated"
    )
    C, E, P, T, S, V = 8, 2, 4, 1, 1, 3
    col = lambda v: np.full(C, v)  # noqa: E731
    stacked = ChainInputs(
        feasible=np.ones((E, T, C), bool),
        perm=np.tile(np.arange(C, dtype=np.int32), (E, 1)),
        ask_cpu=np.full((E, P), 100.0),
        ask_mem=np.full((E, P), 256.0),
        ask_disk=np.full((E, P), 300.0),
        desired_count=np.full((E, P), 4, np.int32),
        limit=np.full((E, P), 3, np.int32),
        distinct_hosts=np.zeros(E, bool),
        tg_idx=np.zeros((E, P), np.int32),
    )
    spread = SpreadInputs(
        codes=np.zeros((E, S, C), np.int32),
        desired=np.ones((E, S, V + 1)),
        used0=np.zeros((E, S, V + 1), np.int32),
        proposed0=np.zeros((E, S, V + 1), np.int32),
        cleared0=np.zeros((E, S, V + 1), np.int32),
        weight=np.ones((E, S)),
        active=np.ones((E, S), bool),
        even=np.zeros((E, S), bool),
    )
    text = (
        chained_plan_picks_cols.lower(
            col(4000.0), col(8192.0), col(1e5),
            col(0.0), col(0.0), col(0.0),
            stacked, np.full(E, C, np.int32), P,
            wanted=np.full(E, 4, np.int32), spread=spread,
            return_carry=True,
        )
        .compile()
        .as_text()
    )
    assert text.startswith("HloModule jit_chained_plan_picks_cols")
    ops = set(re.findall(r'op_name="([^"]+)"', text))
    scoped = {
        op for op in ops
        if op.startswith("jit(chained_plan_picks_cols)/")
    }
    for scope in ("score", "spread", "walk", "usage_update"):
        assert any(f"/{scope}/" in op for op in scoped), scope
