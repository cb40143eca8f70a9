"""`structs.new_id` draws uuid4 text from a pool that one read of the OS
generator fills: every id is what `uuid.uuid4().hex` would make of the
same 16 bytes, no id is handed out twice under racing threads,
`os.urandom` is read once a pool and never for a draw the pool can
serve, every struct, token and lease that carries an id draws it from
the pool, a forked child starts with none, and the two counts are
zero-registered and exported without an increment a draw.

The cases that count EXACTLY run once, in an interpreter of their own
(`tests/_id_pool_child.py`): this worker process may still hold other
test files' daemon threads, and any of them may draw an id between two
reads of a count.  What runs here holds with such a thread about.
"""
import json
import os
import re
import subprocess
import sys
import urllib.request
import uuid

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server
from nomad_tpu.structs import ID_COUNTERS, ID_POOL_SIZE, id_counts, new_id
from nomad_tpu.structs import structs as pool
from nomad_tpu.telemetry import Metrics, MetricsHistory

REFILLS, DRAWN = ID_COUNTERS
N = ID_POOL_SIZE
CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_id_pool_child.py")


@pytest.fixture(scope="module")
def counted():
    """What the counting cases read, in a process with no other thread."""
    done = subprocess.run(
        [sys.executable, "-W", "error", CHILD],
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    doc = json.loads(done.stdout.strip().splitlines()[-1])
    assert doc["threads_at_start"] == 1
    return doc


def test_every_id_is_uuid4_text():
    ids = [new_id() for _ in range(3 * N + 7)]  # over several refills
    for x in ids:
        assert re.fullmatch(r"[0-9a-f]{32}", x), x
        parsed = uuid.UUID(x)
        assert parsed.version == 4
        assert parsed.variant == uuid.RFC_4122
        assert parsed.hex == x
    assert len(set(ids)) == len(ids)


def test_an_id_is_what_uuid4_makes_of_the_same_bytes(counted):
    """uuid4 is `UUID(bytes=os.urandom(16), version=4)`: the pool sets
    the same six bits of each 16 bytes and keeps the other 122."""
    assert counted["same_bytes"] == {
        "same_ids": True, "distinct": N, "reads": [16 * N],
    }


@pytest.mark.parametrize("nibble", range(16))
def test_variant_digit_keeps_the_two_random_bits(nibble):
    digit = pool._VARIANT_DIGIT[f"{nibble:x}"]
    assert int(digit, 16) == 0b1000 | (nibble & 0b0011)


def test_racing_threads_draw_distinct_ids_and_the_count_is_exact(counted):
    seen = counted["racing_threads"]
    assert seen["all_home"]
    assert seen["got"] == seen["distinct"] == seen["drawn"] == seen["asked"]
    # no refill's count was lost in the race, and none counted twice
    assert seen["accounted"]
    assert seen["refilled"] * N >= seen["asked"]
    # at most a pool a thread is left over: the racers that met an
    # empty pool together each filled it
    assert 0 <= seen["pooled"] <= 16 * N


def test_two_threads_that_find_the_pool_empty_both_refill(counted):
    """The pool only grows longer: every id of both blocks is served
    once and none is lost."""
    seen = counted["both_refill"]
    assert seen["all_home"]
    assert seen["reads"] == [16 * N] * 2
    assert (seen["refills"], seen["drawn"], seen["pooled"]) == (2, 2, 2 * N - 2)
    assert seen["reads_for_the_rest"] == [] and seen["pooled_at_the_end"] == 0
    assert seen["distinct"] == 2 * N


def test_urandom_is_read_once_a_pool(counted):
    assert counted["read_once_a_pool"] == {
        "three_pools": [16 * N] * 3,
        "pooled_after_three": 0,
        "reads_for_served_draws": [],
        "read_at_the_empty_pool": [16 * N],
    }


@pytest.mark.parametrize(
    "maker,ids",
    [
        ("Allocation", 1),
        ("Evaluation", 1),
        ("Node", 1),
        ("Deployment", 1),
        ("ScalingPolicy", 1),
        ("acl.Token", 2),  # accessor and secret
        ("Allocation-with-id", 0),
        ("broker.dequeue", 1),
        ("broker.drain_family", 1),
    ],
)
def test_it_comes_from_the_pool(maker, ids, counted):
    assert counted["from_the_pool"][maker] == {"drawn": ids, "reads": []}


def test_mock_structs_draw_their_ids_from_the_pool():
    before = int(id_counts()[DRAWN])
    alloc, ev = mock.alloc(), mock.evaluation()
    assert int(id_counts()[DRAWN]) - before >= 2
    for x in (alloc.id, ev.id):
        assert uuid.UUID(x).version == 4 and uuid.UUID(x).hex == x


def test_fork_hook_leaves_an_empty_pool_whose_next_draw_refills(counted):
    seen = counted["fork_hook_called"]
    assert seen["refills_before"] >= 1
    assert (seen["pooled"], seen["refills"], seen["drawn"]) == (0, 0, 0)
    assert seen["reads"] == [16 * N]
    assert (seen["refills_after_a_draw"], seen["drawn_after_a_draw"]) == (1, 1)
    assert seen["pooled_after_a_draw"] == N - 1 and seen["mine_left_the_pool"]


@pytest.mark.skipif(not hasattr(os, "fork"), reason="no fork here")
def test_a_forked_child_shares_no_pooled_id(counted):
    assert counted["forked_child"] == {
        "answered": True,
        "pooled_in_child": 0,
        "counts_in_child": {REFILLS: 0.0, DRAWN: 0.0},
        "child_ids": 4,
        "shared_with_parent": 0,
        "parent_pool_kept": True,  # the parent's pool is its own
    }


def test_counts_are_zero_registered_and_exported():
    from nomad_tpu.api import start_http_server

    srv = Server(num_schedulers=1, seed=37, batch_pipeline=False)
    srv.start()
    http = start_http_server(srv, port=0)
    try:
        base = f"http://127.0.0.1:{http.port}"

        def counters():
            with urllib.request.urlopen(base + "/v1/metrics", timeout=10) as resp:
                return json.loads(resp.read())["counters"]

        first = counters()
        for name in ID_COUNTERS:
            assert name in first and first[name] >= 0, name
        for _ in range(N + 1):
            new_id()
        after = counters()
        assert after[DRAWN] - first[DRAWN] >= N + 1
        assert after[REFILLS] - first[REFILLS] >= 1
        assert srv.metrics.get_counter(DRAWN) >= after[DRAWN]
        with urllib.request.urlopen(
            base + "/v1/metrics?format=prometheus", timeout=10
        ) as resp:
            text = resp.read().decode()
        for name in ID_COUNTERS:
            assert f"# TYPE {name.replace('.', '_')} counter" in text
    finally:
        http.stop()
        srv.stop()


def test_a_registry_reads_live_counters_when_it_is_read():
    metrics = Metrics()
    level = {"n": 0.0}
    metrics.preregister(counters=("live.n",))
    metrics.incr("stored")
    metrics.attach_live_counters(lambda: {"live.n": level["n"]})
    assert metrics.dump()["counters"] == {"live.n": 0.0, "stored": 1.0}
    level["n"] = 5.0
    assert metrics.get_counter("live.n") == 5.0
    assert metrics.get_counter("stored") == 1.0
    assert metrics.dump()["counters"]["live.n"] == 5.0
    assert metrics.dump_lean()["counters"]["live.n"] == 5.0
    assert "live_n 5.0" in metrics.prometheus_text()
    history = MetricsHistory(metrics, interval_s=3600.0, windows=2)
    assert history.snapshot_once()["counters"]["live.n"] == 5.0


def test_a_draw_takes_no_lock_and_makes_no_call_but_the_pop():
    """The draw is a bound `list.pop`: nothing on it can lose the GIL."""
    assert pool._draw_id.__self__ is pool._id_pool
    assert pool._draw_id.__name__ == "pop"
    names = set(new_id.__code__.co_names)
    assert names <= {"_draw_id", "IndexError", "_fresh_ids", "pop", "_id_pool", "extend"}
