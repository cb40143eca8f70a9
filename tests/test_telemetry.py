"""Telemetry store tests: summary edge cases (max init, empty
snapshots), prometheus name-collision dedupe, exemplar plumbing, the
scrape endpoint format, and accessor behavior under concurrent
writers."""
import json
import threading
import urllib.request

from nomad_tpu.telemetry import Metrics, _Summary


# -- _Summary edges ---------------------------------------------------


def test_summary_max_tracks_all_negative_streams():
    """max started at 0.0, so an all-negative sample stream reported
    max=0.0 — a value that never occurred.  It must mirror min's
    sentinel idiom (-inf) and report the true maximum."""
    s = _Summary()
    for v in (-5.0, -2.5, -9.0):
        s.add(v)
    snap = s.snapshot()
    assert snap["max"] == -2.5
    assert snap["min"] == -9.0


def test_summary_empty_snapshot_guards_min_and_max():
    snap = _Summary().snapshot()
    assert snap["count"] == 0
    assert snap["min"] == 0.0
    assert snap["max"] == 0.0
    assert snap["exemplars"] == []


def test_summary_exemplars_link_p99_entries_to_traces():
    """The slow-tail ring entries surface their trace ids, slowest
    first, so a bad p99 links straight to /v1/traces/<id>."""
    s = _Summary()
    for i in range(100):
        s.add(float(i), exemplar=f"ev-{i}")
    s.add(500.0)  # slowest sample has NO exemplar: must be skipped
    snap = s.snapshot()
    ids = [e["trace_id"] for e in snap["exemplars"]]
    assert ids, snap
    assert ids[0] == "ev-99"
    assert all(e["value"] >= snap["p99"] for e in snap["exemplars"])
    assert len(ids) <= _Summary.EXEMPLARS


# -- prometheus_text --------------------------------------------------


def test_prometheus_text_dedupes_colliding_names():
    """esc() maps both '.' and '-' to '_': two distinct store names
    can collide into one scrape series, which Prometheus rejects.
    The first (sorted) name wins; the loser is skipped with a
    comment, never emitted twice."""
    m = Metrics()
    m.incr("replay.serial_fallbacks", 3)
    m.incr("replay-serial.fallbacks", 7)
    text = m.prometheus_text()
    sample_lines = [
        line
        for line in text.splitlines()
        if line.startswith("replay_serial_fallbacks ")
    ]
    assert len(sample_lines) == 1, text
    type_lines = [
        line
        for line in text.splitlines()
        if line.startswith("# TYPE replay_serial_fallbacks ")
    ]
    assert len(type_lines) == 1, text
    assert "# collision:" in text


def test_prometheus_text_dedupes_across_metric_kinds():
    """A gauge and a summary that escape to the same name must not
    both emit (TYPE redefinition breaks the scrape)."""
    m = Metrics()
    m.set_gauge("batch.launch", 1.0)
    m.add_sample("batch-launch", 2.0)
    text = m.prometheus_text()
    assert (
        sum(
            1
            for line in text.splitlines()
            if line.startswith("# TYPE batch_launch ")
        )
        == 1
    ), text


def test_prometheus_text_unique_names_all_emit():
    m = Metrics()
    m.incr("a.counter")
    m.set_gauge("a.gauge", 2.0)
    m.add_sample("a.sample", 3.0)
    text = m.prometheus_text()
    assert "# TYPE a_counter counter" in text
    assert "# TYPE a_gauge gauge" in text
    assert "# TYPE a_sample summary" in text
    assert "# collision:" not in text


# -- /v1/metrics?format=prometheus endpoint ---------------------------


def test_metrics_prometheus_endpoint_content_type_and_quantiles():
    from nomad_tpu.api import start_http_server
    from nomad_tpu.server import Server

    server = Server(num_schedulers=1, seed=1, batch_pipeline=False)
    server.start()
    http = start_http_server(server, port=0)
    try:
        server.metrics.incr("test.counter", 2)
        for v in (1.0, 2.0, 3.0):
            server.metrics.add_sample("test.sample", v)
        url = (
            f"http://127.0.0.1:{http.port}/v1/metrics"
            "?format=prometheus"
        )
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            assert (
                resp.headers["Content-Type"]
                == "text/plain; version=0.0.4"
            )
            text = resp.read().decode()
        assert "# TYPE test_counter counter" in text
        assert "test_counter 2" in text
        assert "# TYPE test_sample summary" in text
        assert "test_sample_count 3" in text
        for q in ("0.5", "0.9", "0.99"):
            assert f'test_sample{{quantile="{q}"}}' in text, text
        # the JSON dump still works and carries exemplars per summary
        with urllib.request.urlopen(
            f"http://127.0.0.1:{http.port}/v1/metrics", timeout=10
        ) as resp:
            dump = json.loads(resp.read())
        assert "exemplars" in dump["samples"]["test.sample"]
    finally:
        http.stop()
        server.stop()


# -- accessors under concurrent writers -------------------------------


def test_get_counter_and_gauge_under_concurrent_writers():
    """get_counter/get_gauge race real writers: no exceptions, counter
    reads are monotonic, and the final values are exact."""
    m = Metrics()
    n_threads, n_incr = 4, 2000
    errors = []
    stop = threading.Event()

    def writer(i):
        for k in range(n_incr):
            m.incr("c.shared")
            m.set_gauge("g.shared", float(k))
            m.set_gauge(f"g.mine.{i}", float(k))

    def reader():
        last = 0.0
        while not stop.is_set():
            v = m.get_counter("c.shared")
            if v < last:
                errors.append(f"counter went backwards: {v} < {last}")
                return
            last = v
            g = m.get_gauge("g.shared")
            if g is not None and not (0.0 <= g < n_incr):
                errors.append(f"gauge out of range: {g}")
                return

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = [
        threading.Thread(target=writer, args=(i,))
        for i in range(n_threads)
    ]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors, errors
    assert m.get_counter("c.shared") == n_threads * n_incr
    assert m.get_gauge("g.shared") == float(n_incr - 1)
    assert m.get_gauge("g.never_set") is None
    assert m.get_counter("c.never_bumped") == 0.0


def test_add_samples_adds_several_summaries_under_one_exemplar():
    """The flight recorder's fold adds a trace's whole layer split in
    one call: every name gets its sample, tagged with the eval."""
    from nomad_tpu.telemetry import Metrics

    m = Metrics()
    m.preregister(samples=("trace.life",))
    m.add_samples(
        [("trace.life", 12.5), ("trace.self.broker", 10.0)],
        exemplar="ev-1",
    )
    m.add_samples(iter([("trace.life", 7.5)]))
    dump = m.dump()["samples"]
    assert dump["trace.life"]["count"] == 2
    assert dump["trace.life"]["sum"] == 20.0
    assert dump["trace.self.broker"]["count"] == 1
    assert dump["trace.life"]["exemplars"] == [
        {"value": 12.5, "trace_id": "ev-1"}
    ]
    assert "trace_self_broker_sum 10.0" in m.prometheus_text()
