"""While a server serves it owns its process's cycle collector
(`nomad_tpu/collector.py`): young collections every `YOUNG` net
allocations, the survivors of every full collection frozen, and a walk
of the whole heap only when a worker finds the broker empty.  The first
`Server.start` installs the policy and the last `Server.stop` gives the
interpreter its default collector back.

The cases that read the collector EXACTLY run once, in an interpreter
of their own (`tests/_collector_child.py`): this worker process may
still hold other test files' servers, which keep the policy installed
and reclaim at their own idle beats.  What runs here holds with such a
server about.
"""
import gc
import json
import os
import subprocess
import sys
import urllib.request

import pytest

from nomad_tpu import collector
from nomad_tpu.server import Server

FREEZES, RECLAIMS = collector.COLLECTOR_COUNTERS
CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_collector_child.py")
DEFAULT = [700, 10, 10]  # the interpreter's thresholds


@pytest.fixture(scope="module")
def child():
    """What the exact cases read, in a process that starts no server
    but its own."""
    done = subprocess.run(
        [sys.executable, CHILD], capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("when", ["default_at_start", "default_at_end"])
def test_with_no_server_started_the_collector_is_the_default(child, when):
    seen = child[when]
    assert seen["threshold"] == DEFAULT
    assert seen["callback"] is False
    if when == "default_at_end":
        assert seen["frozen"] == 0


def test_the_first_start_installs_the_policy(child):
    seen = child["lifecycle"]
    assert seen["found"]["threshold"] == DEFAULT
    for held in ("one_server", "two_servers", "after_first_stop"):
        assert seen[held]["threshold"] == [collector.YOUNG, 10, 10], held
        assert seen[held]["callback"] is True, held


@pytest.mark.parametrize("when", ["after_last_stop", "after_a_second_stop"])
def test_the_last_stop_restores_the_default(child, when):
    """Thresholds back to what the policy found, the callback gone,
    nothing frozen — and a second stop, or the stop of a server never
    started, changes nothing."""
    assert child["lifecycle"][when] == {
        "threshold": DEFAULT, "callback": False, "frozen": 0,
    }


def test_a_full_collection_while_serving_freezes_its_survivors(child):
    seen = child["lifecycle"]["full_collection"]
    assert seen["freezes"] == 1
    assert seen["frozen"] > child["lifecycle"]["two_servers"]["frozen"]


def test_a_full_collection_here_is_counted():
    srv = Server(num_schedulers=1, seed=40, batch_pipeline=False)
    srv.start()
    try:
        before = srv.metrics.get_counter(FREEZES)
        gc.collect()
        assert srv.metrics.get_counter(FREEZES) >= before + 1
        assert gc.get_threshold()[0] == collector.YOUNG
    finally:
        srv.stop()


def test_a_held_backlog_reclaims_nothing(child):
    seen = child["held_backlog"]
    assert seen["frozen_at_the_freeze"] > 0
    assert seen["held"] == {"freezes": 1, "reclaims": 0, "pending": 6}


def test_the_first_idle_beat_after_a_freeze_reclaims_once(child):
    """Five idle beats later still once: the reclaim's own re-freeze is
    no new freeze."""
    seen = child["held_backlog"]
    assert seen["drained"] and seen["reclaimed"]
    assert (seen["freezes"], seen["reclaims"]) == (1, 1)
    assert seen["refrozen"]


@pytest.mark.parametrize(
    "phase,generation", [("start", 2), ("start", 0), ("stop", 0), ("stop", 1)]
)
def test_only_the_end_of_a_full_collection_freezes(phase, generation):
    before = collector.counts()
    collector._freeze_survivors(phase, {"generation": generation})
    assert collector.counts() == before


class CountingGc:
    """The gc module, with its walks counted."""

    def __init__(self):
        self.walks = []

    def __getattr__(self, name):
        return getattr(gc, name)

    def collect(self, *args):
        self.walks.append("collect")
        return gc.collect(*args)

    def unfreeze(self):
        self.walks.append("unfreeze")
        gc.unfreeze()

    def freeze(self):
        self.walks.append("freeze")


class BusyGc(CountingGc):
    """As gc.collect is while another thread collects: it walks nothing."""

    def collect(self, *args):
        self.walks.append("collect")
        return 0


def test_an_idle_beat_with_nothing_frozen_walks_nothing(monkeypatch):
    spy = CountingGc()
    monkeypatch.setattr(collector, "gc", spy)
    monkeypatch.setattr(collector, "_pending", False)
    collector.reclaim_at_idle()
    assert spy.walks == []


def test_an_idle_beat_after_a_freeze_walks_once(monkeypatch):
    spy = CountingGc()
    monkeypatch.setattr(collector, "gc", spy)
    monkeypatch.setattr(collector, "_pending", True)
    collector.reclaim_at_idle()
    collector.reclaim_at_idle()
    assert spy.walks == ["unfreeze", "collect"]
    assert collector._pending is False


def test_an_idle_beat_that_meets_another_collection_tries_again(monkeypatch):
    spy = BusyGc()
    monkeypatch.setattr(collector, "gc", spy)
    monkeypatch.setattr(collector, "_pending", True)
    reclaims = collector.counts()[RECLAIMS]
    collector.reclaim_at_idle()
    assert spy.walks == ["unfreeze", "collect", "freeze"]
    assert collector._pending is True
    assert collector.counts()[RECLAIMS] == reclaims


def test_counters_are_zero_registered_and_exported():
    from nomad_tpu.api import start_http_server

    srv = Server(num_schedulers=1, seed=40, batch_pipeline=False)
    at_construction = srv.metrics.dump()["counters"]
    for name in collector.COLLECTOR_COUNTERS:
        assert name in at_construction and at_construction[name] >= 0, name
    srv.start()
    http = start_http_server(srv, port=0)
    try:
        base = f"http://127.0.0.1:{http.port}"
        with urllib.request.urlopen(base + "/v1/metrics", timeout=10) as resp:
            counters = json.loads(resp.read())["counters"]
        for name in collector.COLLECTOR_COUNTERS:
            assert counters[name] >= 0, name
        with urllib.request.urlopen(
            base + "/v1/metrics?format=prometheus", timeout=10
        ) as resp:
            text = resp.read().decode()
        for name in collector.COLLECTOR_COUNTERS:
            assert f"# TYPE {name.replace('.', '_')} counter" in text
    finally:
        http.stop()
        srv.stop()


def test_a_served_run_places_as_the_default_collector_does(child):
    seen = child["same_placements"]
    assert seen["default"]["drained"] and seen["policy"]["drained"]
    assert seen["placed_jobs"] == seen["jobs"] == 300
    assert seen["placements"] >= 300
    assert seen["differ"] == []
    # the two runs did differ in their collector
    assert seen["default"]["threshold"] == 700 and seen["default"]["freezes"] == 0
    assert seen["policy"]["threshold"] == collector.YOUNG
    assert seen["policy"]["freezes"] >= 4


def test_after_a_served_run_what_is_frozen_is_nearly_all_alive(child):
    """The leak check: once the worker's idle beat has reclaimed, an
    unfreeze and a full collection find cyclic garbage under 1% of
    what was frozen."""
    seen = child["same_placements"]["policy"]
    assert seen["reclaimed"]
    assert seen["frozen"] > 10_000
    assert seen["garbage"] < 0.01 * seen["frozen"]


def test_the_young_threshold_is_one_constant_and_no_knob():
    assert collector.YOUNG == 10_000
    with open(collector.__file__, encoding="utf-8") as fh:
        source = fh.read()
    assert "environ" not in source and "getenv" not in source
