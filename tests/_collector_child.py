"""Child process of tests/test_collector.py: the cases that read the
process's cycle collector EXACTLY.  A tier-1 worker process has run
other test files first and may still hold their servers, whose workers
reclaim at their own idle beats and whose policy hold keeps the
collector changed; an interpreter of its own starts with the
interpreter's default collector and holds only the servers started
here.  Prints one JSON line, a key a case.

    python tests/_collector_child.py
"""
import copy
import gc
import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from nomad_tpu import collector, mock  # noqa: E402
from nomad_tpu.structs import compute_node_class  # noqa: E402
from nomad_tpu.server import Server  # noqa: E402

FREEZES, RECLAIMS = collector.COLLECTOR_COUNTERS
WAIT_S = 120.0


def count(name: str) -> int:
    return int(collector.counts()[name])


def state() -> dict:
    return {
        "threshold": list(gc.get_threshold()),
        "callback": collector._freeze_survivors in gc.callbacks,
        "frozen": gc.get_freeze_count(),
    }


def lifecycle():
    """Two servers in one process: the first start installs the
    policy, the last stop removes it, and a second stop (or a server
    never started) changes nothing."""
    found = state()
    first = Server(num_schedulers=1, seed=40, batch_pipeline=False)
    second = Server(num_schedulers=1, seed=41, batch_pipeline=False)
    never = Server(num_schedulers=1, seed=42, batch_pipeline=False)
    first.start()
    one = state()
    second.start()
    second.start()  # a restart of a running server holds once
    two = state()
    f0 = count(FREEZES)
    gc.collect()
    collected = {"freezes": count(FREEZES) - f0, "frozen": gc.get_freeze_count()}
    first.stop()
    after_first = state()
    never.stop()
    second.stop()
    after_last = state()
    second.stop()
    return {
        "found": found,
        "one_server": one,
        "two_servers": two,
        "full_collection": collected,
        "after_first_stop": after_first,
        "after_last_stop": after_last,
        "after_a_second_stop": state(),
    }


def make_nodes(n, seed):
    rng = random.Random(seed)
    nodes = []
    for _ in range(n):
        node = mock.node()
        node.node_resources.cpu = rng.choice([8000, 16000])
        node.node_resources.memory_mb = rng.choice([16384, 32768])
        node.computed_class = compute_node_class(node)
        nodes.append(node)
    return nodes


def make_jobs(n, seed, prefix):
    rng = random.Random(seed)
    jobs = []
    for i in range(n):
        job = mock.job(id=f"{prefix}-{i}")
        job.task_groups[0].count = rng.randint(1, 3)
        job.task_groups[0].tasks[0].resources.cpu = rng.choice([200, 500])
        jobs.append(job)
    return jobs


def wait_for(cond, limit=WAIT_S) -> bool:
    deadline = time.monotonic() + limit
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def held_backlog():
    """A backlog held by Worker.set_pause never reaches the idle beat;
    the first idle beat after a freeze reclaims once, and its own
    re-freeze is no new freeze."""
    srv = Server(num_schedulers=1, seed=43, batch_pipeline=True)
    srv.start()
    try:
        worker = srv.workers[0]
        worker.set_pause(True)
        time.sleep(0.3)  # a dequeue already waiting runs out
        for node in make_nodes(8, 1):
            srv.register_node(node)
        for job in make_jobs(6, 2, "held"):
            srv.register_job(job)
        f0, r0 = count(FREEZES), count(RECLAIMS)
        gc.collect()
        frozen = gc.get_freeze_count()
        time.sleep(0.5)
        held = {
            "freezes": count(FREEZES) - f0,
            "reclaims": count(RECLAIMS) - r0,
            "pending": srv.broker.ready_count(),
        }
        worker.set_pause(False)
        drained = srv.drain_to_idle(WAIT_S)
        came = wait_for(lambda: count(RECLAIMS) > r0)
        time.sleep(0.5)  # five more idle beats
        return {
            "frozen_at_the_freeze": frozen,
            "held": held,
            "drained": drained,
            "reclaimed": came,
            "freezes": count(FREEZES) - f0,
            "reclaims": count(RECLAIMS) - r0,
            "refrozen": gc.get_freeze_count() > 0,
        }
    finally:
        srv.stop()


def served(nodes, jobs, policy: bool):
    """A served run of every job; a full collection after each quarter
    of the registrations.  Without ``policy`` the server holds no
    collector policy: the interpreter's default collector."""
    hold, release = collector.hold, collector.release
    if not policy:
        collector.hold = collector.release = lambda: None
    srv = Server(num_schedulers=1, seed=44, batch_pipeline=True)
    srv.start()
    try:
        for node in nodes:
            srv.register_node(copy.deepcopy(node))
        f0 = count(FREEZES)
        quarter = len(jobs) // 4
        for k in range(0, len(jobs), quarter):
            for job in jobs[k : k + quarter]:
                srv.register_job(copy.deepcopy(job))
            gc.collect()
        drained = srv.drain_to_idle(WAIT_S)
        # the freezes above took evaluations in flight: their cyclic
        # scheduler objects wait frozen for the worker's idle beat
        reclaimed = wait_for(
            lambda: not collector._pending and not collector._lock.locked()
        )
        placed = {
            job.id: sorted(
                (a.name, a.node_id)
                for a in srv.store.allocs_by_job("default", job.id)
                if not a.terminal_status()
            )
            for job in jobs
        }
        doc = {
            "drained": drained,
            "reclaimed": reclaimed,
            "placed": placed,
            "freezes": count(FREEZES) - f0,
            "threshold": gc.get_threshold()[0],
        }
        # the leak check: whatever the policy holds frozen, walked
        frozen = gc.get_freeze_count()
        gc.unfreeze()
        doc["frozen"] = frozen
        doc["garbage"] = gc.collect()
        return doc
    finally:
        srv.stop()
        collector.hold, collector.release = hold, release


def same_placements():
    nodes = make_nodes(60, 3)
    jobs = make_jobs(300, 4, "served")
    default = served(nodes, jobs, policy=False)
    frozen = served(nodes, jobs, policy=True)
    placed = frozen.pop("placed")
    want = default.pop("placed")
    return {
        "default": default,
        "policy": frozen,
        "jobs": len(jobs),
        "placed_jobs": sum(1 for p in placed.values() if p),
        "placements": sum(len(p) for p in placed.values()),
        "differ": sorted(j for j in want if want[j] != placed[j])[:5],
    }


def main() -> None:
    doc = {"default_at_start": state(), "young": collector.YOUNG}
    # first: a lifecycle belongs before this process has held a policy
    doc["lifecycle"] = lifecycle()
    doc["held_backlog"] = held_backlog()
    doc["same_placements"] = same_placements()
    doc["default_at_end"] = state()
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
