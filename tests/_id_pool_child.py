"""Child process of tests/test_id_pool.py: the cases that count the id
pool's draws, refills and reads of ``os.urandom`` EXACTLY.  A tier-1
worker process has run other test files first and may still hold their
daemon threads (servers, clients, sweepers), any of which can draw an id
at any moment; an interpreter of its own, which imports the structs and
the broker and starts nothing, has no such thread.  No case here depends
on which thread runs first: every count is read after the threads that
move it have been joined.  Prints one JSON line, a key a case.

    python tests/_id_pool_child.py
"""
import json
import os
import select
import signal
import sys
import threading
import types
import uuid

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from nomad_tpu.acl import Token  # noqa: E402
from nomad_tpu.server.eval_broker import EvalBroker, job_family  # noqa: E402
from nomad_tpu.structs import (  # noqa: E402
    ID_COUNTERS,
    ID_POOL_SIZE,
    Allocation,
    Deployment,
    Evaluation,
    Node,
    ScalingPolicy,
    id_counts,
    new_id,
)
from nomad_tpu.structs import structs as pool  # noqa: E402

REFILLS, DRAWN = ID_COUNTERS
N = ID_POOL_SIZE
JOIN_S = 60.0


def drawn() -> int:
    return int(id_counts()[DRAWN])


def refills() -> int:
    return int(id_counts()[REFILLS])


class Urandom:
    """Stands where the pool's module looks `os` up: counts the sizes
    asked for, and serves `block` first where one is given."""

    def __init__(self, block=b"", gate=None):
        self.sizes, self.block, self.gate = [], block, gate

    def urandom(self, size):
        if self.gate is not None:
            self.gate.wait(timeout=JOIN_S)
        self.sizes.append(size)
        served, self.block = self.block[:size], self.block[size:]
        return served + os.urandom(size - len(served))

    def __enter__(self):
        pool.os = types.SimpleNamespace(urandom=self.urandom)
        return self

    def __exit__(self, *exc):
        pool.os = os


def run_threads(targets) -> bool:
    """Start, join with a limit; True where every thread came home."""
    workers = [threading.Thread(target=t, daemon=True) for t in targets]
    for t in workers:
        t.start()
    for t in workers:
        t.join(JOIN_S)
    return not any(t.is_alive() for t in workers)


def same_bytes():
    pool._forget_ids()
    block = os.urandom(16 * N)
    with Urandom(block) as seen:
        got = [new_id() for _ in range(N)]
    want = [
        uuid.UUID(bytes=block[i : i + 16], version=4).hex
        for i in range(0, len(block), 16)
    ]
    return {
        "same_ids": sorted(got) == sorted(want),
        "distinct": len(set(want)),
        "reads": seen.sizes,
    }


def racing_threads(threads=16, each=2_500):
    """More threads than cores, a switch every microsecond: a refill
    whose count or whose ids a race could lose would show here."""
    barrier = threading.Barrier(threads)
    out = [[] for _ in range(threads)]

    def draw(k):
        barrier.wait(timeout=JOIN_S)
        out[k] = [new_id() for _ in range(each)]

    before, before_refills = drawn(), refills()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        home = run_threads([lambda k=k: draw(k) for k in range(threads)])
    finally:
        sys.setswitchinterval(interval)
    ids = [x for part in out for x in part]
    return {
        "all_home": home,
        "asked": threads * each,
        "got": len(ids),
        "distinct": len(set(ids)),
        "drawn": drawn() - before,
        "refilled": refills() - before_refills,
        "pooled": len(pool._id_pool),
        "accounted": refills() * N - drawn() == len(pool._id_pool),
    }


def both_refill():
    """Two threads meet inside the read of the OS generator, so both
    are past the empty pop: both refill, and the pool only grows."""
    pool._forget_ids()
    first = [None, None]

    def draw(k):
        first[k] = new_id()

    with Urandom(gate=threading.Barrier(2)) as seen:
        home = run_threads([lambda k=k: draw(k) for k in range(2)])
    after = {
        "all_home": home,
        "reads": seen.sizes,
        "refills": refills(),
        "drawn": drawn(),
        "pooled": len(pool._id_pool),
    }
    with Urandom() as later:
        rest = [new_id() for _ in range(2 * N - 2)]
    after.update(
        reads_for_the_rest=later.sizes,
        pooled_at_the_end=len(pool._id_pool),
        distinct=len(set(first + rest)),
    )
    return after


def read_once_a_pool():
    pool._forget_ids()
    with Urandom() as seen:
        for _ in range(3 * N):
            new_id()
        three_pools = list(seen.sizes)
        pooled = len(pool._id_pool)
        new_id()  # opens the fourth
        del seen.sizes[:]
        for _ in range(N - 1):  # draws the pool can serve
            new_id()
        served = list(seen.sizes)
        new_id()
        fifth = list(seen.sizes)
    return {
        "three_pools": three_pools,
        "pooled_after_three": pooled,
        "reads_for_served_draws": served,
        "read_at_the_empty_pool": fifth,
    }


def lease(call):
    def make():
        broker = EvalBroker(nack_timeout=60.0)
        broker.set_enabled(True)
        try:
            ev = Evaluation(
                id="e" * 32, namespace="default", job_id="fam/dispatch-0",
                type="batch", priority=50,
            )
            broker.enqueue(ev)
            before = drawn()
            token = call(broker, ev)
            took = drawn() - before
            assert uuid.UUID(token).version == 4 and uuid.UUID(token).hex == token
            return took
        finally:
            broker.set_enabled(False)

    return make


def counted(factory):
    def make():
        before = drawn()
        factory()
        return drawn() - before

    return make


MAKERS = {
    "Allocation": counted(Allocation),
    "Evaluation": counted(Evaluation),
    "Node": counted(Node),
    "Deployment": counted(Deployment),
    "ScalingPolicy": counted(ScalingPolicy),
    "acl.Token": counted(Token),
    "Allocation-with-id": counted(lambda: Allocation(id="given")),
    "broker.dequeue": lease(lambda b, ev: b.dequeue(["batch"], timeout=1.0)[1]),
    "broker.drain_family": lease(
        lambda b, ev: b.drain_family(["batch"], job_family(ev), 4)[0][1]
    ),
}


def from_the_pool():
    found = {}
    for name, make in MAKERS.items():
        while len(pool._id_pool) < 4:  # a pool that can serve the draws
            new_id()
        with Urandom() as seen:
            found[name] = {"drawn": make(), "reads": seen.sizes}
    return found


def fork_hook_called():
    new_id()
    before = refills()
    pool._forget_ids()
    emptied = {
        "refills_before": before,
        "pooled": len(pool._id_pool),
        "refills": refills(),
        "drawn": drawn(),
    }
    with Urandom() as seen:
        mine = new_id()
    emptied.update(
        reads=seen.sizes,
        refills_after_a_draw=refills(),
        drawn_after_a_draw=drawn(),
        pooled_after_a_draw=len(pool._id_pool),
        mine_left_the_pool=mine not in pool._id_pool,
    )
    return emptied


def forked_child():
    while len(pool._id_pool) < 8:
        new_id()
    pooled = set(pool._id_pool)
    read_end, write_end = os.pipe()
    pid = os.fork()
    if pid == 0:  # the forked child: report and leave
        try:
            os.close(read_end)
            doc = {
                "pooled": len(pool._id_pool),
                "counts": id_counts(),
                "ids": [new_id() for _ in range(4)],
            }
            os.write(write_end, json.dumps(doc).encode())
        finally:
            os._exit(0)
    os.close(write_end)
    try:
        ready, _, _ = select.select([read_end], [], [], JOIN_S)
        with os.fdopen(read_end, "rb") as fh:
            doc = json.loads(fh.read()) if ready else None
    finally:
        os.kill(pid, signal.SIGKILL)
        os.waitpid(pid, 0)
    if doc is None:
        return {"answered": False}
    return {
        "answered": True,
        "pooled_in_child": doc["pooled"],
        "counts_in_child": doc["counts"],
        "child_ids": len(set(doc["ids"])),
        "shared_with_parent": len(pooled & set(doc["ids"])),
        "parent_pool_kept": set(pool._id_pool) == pooled,
    }


def main() -> None:
    doc = {
        "threads_at_start": threading.active_count(),
        # first: a fork belongs before this process has started a thread
        "forked_child": forked_child() if hasattr(os, "fork") else None,
        "same_bytes": same_bytes(),
        "racing_threads": racing_threads(),
        "both_refill": both_refill(),
        "read_once_a_pool": read_once_a_pool(),
        "from_the_pool": from_the_pool(),
        "fork_hook_called": fork_hook_called(),
    }
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
