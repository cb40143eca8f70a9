"""Cluster-scope observability tests: cross-server trace segment
export/absorb, redelivery-supersedes across servers (a late segment
from a dead follower lands in the settled old-generation trace, never
the redelivered attempt), explicit ``revoked``/``shed`` outcomes for
traces that used to dangle, the metric time-series history ring, the
3-server fan-out trace-stitching soak, and the leader fan-in HTTP
surface with partial-result (unreachable peer) marking."""
import json
import pickle
import time
import urllib.error
import urllib.request

from types import SimpleNamespace

from nomad_tpu import mock
from nomad_tpu.server.cluster import TestCluster
from nomad_tpu.server.eval_broker import EvalBroker
from nomad_tpu.server.overload import MODE_SHEDDING, OverloadController
from nomad_tpu.structs import Evaluation, new_id
from nomad_tpu.telemetry import Metrics, MetricsHistory
from nomad_tpu.trace import TRACE, Tracer

SCHEDS = ["service", "batch", "system", "_core"]


def wait_until(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timeout waiting for {msg}")


def _nodes(n, prefix="obs-node"):
    return [mock.node(id=f"{prefix}-{i:03d}") for i in range(n)]


def _jobs(n, prefix="obs-job"):
    out = []
    for i in range(n):
        job = mock.job(id=f"{prefix}-{i:04d}")
        job.task_groups[0].count = 1
        for tg in job.task_groups:
            for task in tg.tasks:
                task.resources.cpu = 50
                task.resources.memory_mb = 32
        out.append(job)
    return out


def _evals(n, family="obsfam"):
    return [
        Evaluation(
            id=new_id(),
            namespace="default",
            job_id=f"{family}/dispatch-{i:03d}",
            type="batch",
            priority=50,
        )
        for i in range(n)
    ]


def _assert_well_nested(trace):
    """Every span's parent exists, starts no later than it and, on
    the same thread, encloses it (small epsilon for float math); no
    orphan (never-closed) spans.  A span another thread recorded names
    its cause as parent, and may run after it: `replay.speculate`
    replays the rows its `batch_worker.fetch` brought."""
    assert trace["orphans"] == 0, trace
    by_id = {s["id"]: s for s in trace["spans"]}
    eps = 1e-3  # ms
    for span in trace["spans"]:
        assert span["dur_ms"] is not None, span
        parent = span["parent"]
        if parent is None:
            continue
        assert parent in by_id, span
        p = by_id[parent]
        assert span["off_ms"] >= p["off_ms"] - eps, (span, p)
        if span["thread"] != p["thread"]:
            continue
        assert (
            span["off_ms"] + span["dur_ms"]
            <= p["off_ms"] + p["dur_ms"] + eps
        ), (span, p)


def _lanes(trace):
    """Distinct server_id values across a trace's spans (None = the
    server that owns the trace)."""
    return {
        (s.get("attrs") or {}).get("server_id")
        for s in trace["spans"]
    }


# -- segment export / absorb (two tracers = two "processes") ----------


def test_segment_export_absorb_stitches_remote_spans():
    """The leader's trace and a follower's segment live in different
    tracers (different processes in a real deployment); the shipped
    segment re-anchors onto the leader's clock, carries the follower's
    server_id on every span, and the ship marker itself is visible."""
    leader = Tracer(ring=8)
    follower = Tracer(ring=8)
    leader.begin("ev-seg", queue="service")
    ctx = leader.export_context("ev-seg")
    assert ctx is not None and "#" in ctx["trace_id"]

    follower.begin_segment("ev-seg", ctx)
    with follower.span("ev-seg", "batch_worker.simulate"):
        with follower.span("ev-seg", "batch_worker.assemble", members=2):
            pass
    follower.annotate("ev-seg", outcome="speculative")
    seg = follower.export_segment("ev-seg", "srv-b", close=True)
    assert seg is not None
    assert seg["trace_id"] == ctx["trace_id"]
    assert seg["server_id"] == "srv-b"
    assert follower.open_segments() == 0

    absorbed = leader.absorb_segment(seg)
    assert absorbed >= 3  # simulate + assemble + ship marker
    leader.finish("ev-seg", "ack")
    trace = leader.get("ev-seg")
    assert trace["complete"]
    # the follower's richer outcome annotation traveled in the
    # segment and was consumed by the ack
    assert trace["outcome"] == "speculative"
    _assert_well_nested(trace)
    by_name = {s["name"]: s for s in trace["spans"]}
    assert by_name["batch_worker.simulate"]["attrs"]["server_id"] == (
        "srv-b"
    )
    assert "fanout.remote_span_ship" in by_name
    # intra-batch parent links survive the sid remap
    assert by_name["batch_worker.assemble"]["parent"] == (
        by_name["batch_worker.simulate"]["id"]
    )


def test_killed_follower_late_segment_lands_in_superseded_trace():
    """Redelivery supersedes ACROSS servers: a segment straggling in
    from a dead follower carries the old generation's trace id and
    must land in that settled trace — never interleave into the
    redelivered attempt's trace."""
    leader = Tracer(ring=8)
    dead = Tracer(ring=8)
    leader.begin("ev-kill")
    old_ctx = leader.export_context("ev-kill")
    dead.begin_segment("ev-kill", old_ctx)
    with dead.span("ev-kill", "batch_worker.simulate"):
        pass
    # follower dies mid-lease; the leader reclaims and redelivers,
    # which begins a NEW generation and settles the old one
    leader.begin("ev-kill")
    leader.finish("ev-kill", "ack")
    new_trace = leader.get("ev-kill")
    assert new_trace["outcome"] == "ack"

    # the dead follower's segment finally arrives (stale token path
    # absorbs the segment before rejecting the settle)
    seg = dead.export_segment("ev-kill", "dead-f", close=True)
    assert leader.absorb_segment(seg) >= 1

    traces = {
        t["trace_id"]: t
        for t in leader.recent(limit=10, full=True)
        if t["eval_id"] == "ev-kill"
    }
    assert len(traces) == 2
    old = traces[old_ctx["trace_id"]]
    new = leader.get("ev-kill")
    assert old["outcome"] == "superseded"
    old_names = {s["name"] for s in old["spans"]}
    new_names = {s["name"] for s in new["spans"]}
    assert "batch_worker.simulate" in old_names
    assert "batch_worker.simulate" not in new_names
    assert "dead-f" not in _lanes(new)


def test_local_redelivery_evicts_stale_segment():
    """If the lease is reclaimed and redelivered to THIS server, the
    next recording call drops the stale segment ('superseded') instead
    of swallowing the new attempt's spans."""
    t = Tracer(ring=8)
    t.begin("ev-loc")
    ctx = t.export_context("ev-loc")
    t.begin_segment("ev-loc", ctx)
    assert t.open_segments() == 1
    t.begin("ev-loc")  # redelivered locally: new trace id
    with t.span("ev-loc", "batch_worker.sequential"):
        pass
    assert t.open_segments() == 0
    t.finish("ev-loc", "ack")
    trace = t.get("ev-loc")
    assert {s["name"] for s in trace["spans"]} == {
        "broker.dequeue",
        "batch_worker.sequential",
    }


# -- explicit outcomes for formerly-dangling traces -------------------


def test_broker_flush_finishes_unacked_traces_revoked():
    """A leadership revoke flushes the broker; every unacked
    delivery's trace settles with an explicit `revoked` outcome
    instead of dangling 'in flight' forever."""
    TRACE.clear()
    broker = EvalBroker(nack_timeout=60.0)
    broker.set_enabled(True)
    evs = _evals(3)
    broker.enqueue_all(evs)
    leases = broker.dequeue_remote(
        ["batch"], timeout=1.0, max_n=3, peer="server-9"
    )
    assert len(leases) == 3
    for ev, _tok in leases:
        assert TRACE.get(ev.id)["complete"] is False
    broker.set_enabled(False)  # revoke -> flush
    for ev, _tok in leases:
        trace = TRACE.get(ev.id)
        assert trace["complete"], trace
        assert trace["outcome"] == "revoked"
    TRACE.clear()


def test_overload_close_incident_finishes_shed_trace():
    """Server shutdown mid-incident settles the incident trace with
    an explicit `shed` outcome and the shed-count annotation."""
    TRACE.clear()
    ctl = OverloadController(SimpleNamespace(metrics=Metrics()))
    with ctl._lock:
        ctl._transition_locked(MODE_SHEDDING, 999.0, 45.0)
    incident = ctl._incident_id
    assert incident is not None
    assert TRACE.get(incident)["complete"] is False
    ctl.close_incident()
    assert ctl._incident_id is None
    trace = TRACE.get(incident)
    assert trace["complete"]
    assert trace["outcome"] == "shed"
    assert "shed_total" in trace["attrs"]
    ctl.close_incident()  # idempotent
    TRACE.clear()


# -- metric time-series history ---------------------------------------


def test_metrics_history_ring_bounded_with_percentiles():
    m = Metrics()
    m.preregister(
        counters=("obs.history_snapshots",),
        gauges=("obs.history_windows",),
    )
    hist = MetricsHistory(m, windows=4, interval_s=60.0)
    for round_no in range(6):
        m.incr("test.ticks")
        for v in range(10):
            m.add_sample("test.lat_ms", float(v + round_no))
        hist.snapshot_once()
    out = hist.to_dict()
    assert out["enabled"] is True
    assert out["max_windows"] == 4
    assert len(out["windows"]) == 4  # ring bounded
    window = out["windows"][-1]
    assert window["counters"]["test.ticks"] == 6.0
    sample = window["samples"]["test.lat_ms"]
    assert set(sample) == {"count", "p50", "p99"}
    assert m.get_gauge("obs.history_windows") == 4.0
    assert m.get_counter("obs.history_snapshots") == 6.0
    series = hist.series("test.lat_ms")
    assert len(series) == 4
    assert all("p99" in point for point in series)
    counter_series = hist.series("test.ticks")
    assert [p["value"] for p in counter_series] == [3.0, 4.0, 5.0, 6.0]
    assert hist.series("nope") == []


def test_metrics_history_thread_snapshots(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_OBS_HISTORY_N", "8")
    m = Metrics()
    hist = MetricsHistory(m, interval_s=0.05)
    hist.start()
    try:
        wait_until(
            lambda: len(hist.to_dict()["windows"]) >= 2,
            timeout=10.0,
            msg="history snapshots",
        )
    finally:
        hist.stop()
    assert hist.to_dict()["max_windows"] == 8


def test_metrics_history_disabled_knob(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_OBS_HISTORY", "0")
    hist = MetricsHistory(Metrics())
    hist.start()
    hist.stop()
    out = hist.to_dict()
    assert out["enabled"] is False
    assert out["windows"] == []


# -- 3-server fan-out trace-stitching soak ----------------------------


def test_fanout_trace_stitching_soak(monkeypatch):
    """Every completed eval in a 3-server fan-out run carries a
    well-nested dequeue->commit trace on the leader; follower-planned
    evals stitch spans from >= 2 distinct servers into ONE waterfall;
    zero orphan spans and zero dangling segments after drain."""
    monkeypatch.setenv("NOMAD_TPU_FANOUT", "1")
    TRACE.clear()
    n_jobs = 12
    cluster = TestCluster(3, heartbeat_ttl=300.0)
    cluster.start()
    try:
        leader = cluster.wait_for_leader(timeout=30.0)
        for node in _nodes(12):
            leader.register_node(node)
        evs = []
        for i, job in enumerate(_jobs(n_jobs)):
            evs.append(cluster.servers[i % 3].register_job(job))
        wait_until(
            lambda: cluster.wait_for_leader(timeout=30.0)
            .drain_to_idle(timeout=1.0),
            timeout=90.0,
            msg="fan-out drain",
        )
        leader = cluster.wait_for_leader(timeout=30.0)
        shipped = sum(
            s.metrics.get_counter("fanout.segments_shipped")
            for s in cluster.servers
        )
        assert shipped > 0, "no trace segments ever shipped"
        assert leader.metrics.get_counter("cluster.segments_absorbed") > 0

        stitched = 0
        completed = 0
        for ev in evs:
            trace = TRACE.get(ev.id)
            assert trace is not None, ev.id
            if not trace["complete"]:
                continue
            completed += 1
            _assert_well_nested(trace)
            names = [s["name"] for s in trace["spans"]]
            assert names[0] == "broker.dequeue", names
            lanes = _lanes(trace)
            if len(lanes) >= 2:
                stitched += 1
                assert "fanout.remote_span_ship" in names
                assert "store.commit" in names
        assert completed == n_jobs, (completed, n_jobs)
        assert stitched > 0, "no stitched cross-server trace"
        # zero orphan segments: every follower buffer was shipped on
        # settle or evicted by redelivery
        wait_until(
            lambda: TRACE.open_segments() == 0,
            timeout=10.0,
            msg="segments drained",
        )
    finally:
        cluster.stop()
        TRACE.clear()


def test_fanout_follower_kill_redelivery_supersedes_over_rpc(
    monkeypatch,
):
    """The integration shape of redelivery-supersedes: a follower
    leases over the real RPC surface, records into its segment, dies;
    the leader reclaims + redelivers (new trace generation); the dead
    follower's straggler settle RPC still ships its segment, which
    lands in the OLD generation's trace."""
    TRACE.clear()
    cluster = TestCluster(
        3, heartbeat_ttl=300.0, nack_timeout=0.5, num_schedulers=0
    )
    cluster.start()
    try:
        leader = cluster.wait_for_leader(timeout=30.0)
        follower = cluster.followers()[0]
        other = cluster.followers()[1]
        leader.broker.enqueue_all(_evals(2, family="kill"))
        resp = cluster.transport.rpc(
            follower.addr,
            leader.addr,
            "broker_dequeue",
            {
                "schedulers": SCHEDS,
                "timeout": 1.0,
                "n": 2,
                "server": follower.addr,
            },
        )
        leases = pickle.loads(resp["leases"])
        assert len(leases) == 2
        ctxs = resp.get("trace_ctx") or {}
        ev, token = leases[0]
        old_ctx = ctxs[ev.id]
        # the "follower" records pipeline spans into its segment
        TRACE.begin_segment(ev.id, old_ctx)
        with TRACE.span(ev.id, "batch_worker.simulate"):
            pass
        # follower dies: never settles; leader reclaims at the nack
        # timeout and redelivers to another server
        wait_until(
            lambda: leader.broker.remote_unacked_count() == 0,
            timeout=10.0,
            msg="lease reclamation",
        )
        resp2 = cluster.transport.rpc(
            other.addr,
            leader.addr,
            "broker_dequeue",
            {
                "schedulers": SCHEDS,
                "timeout": 1.0,
                "n": 2,
                "server": other.addr,
            },
        )
        redelivered = {
            e.id: ctx_tok
            for e, ctx_tok in pickle.loads(resp2["leases"])
        }
        assert ev.id in redelivered
        new_ctx = (resp2.get("trace_ctx") or {})[ev.id]
        assert new_ctx["trace_id"] != old_ctx["trace_id"]
        # the dead follower's straggler settle finally arrives with
        # the OLD token: the segment is absorbed (old generation),
        # the ack itself is rejected
        seg = TRACE.export_segment(ev.id, follower.addr, close=True)
        assert seg is not None
        try:
            cluster.transport.rpc(
                follower.addr,
                leader.addr,
                "broker_ack",
                {"eval_id": ev.id, "token": token, "segment": seg},
            )
        except Exception:
            pass  # stale-token rejection is expected
        assert TRACE.open_segments() == 0
        traces = {
            t["trace_id"]: t
            for t in TRACE.recent(limit=16, full=True)
            if t["eval_id"] == ev.id
        }
        old = traces.get(old_ctx["trace_id"])
        assert old is not None
        # the sweeper nacks the reclaimed lease (settling the old
        # generation) before the redelivery begins the new one
        assert old["outcome"] in ("nack", "superseded")
        assert "batch_worker.simulate" in {
            s["name"] for s in old["spans"]
        }
        new = TRACE.get(ev.id)
        assert new["trace_id"] == new_ctx["trace_id"]
        assert "batch_worker.simulate" not in {
            s["name"] for s in new["spans"]
        }
    finally:
        cluster.stop()
        TRACE.clear()


# -- leader fan-in HTTP surface ---------------------------------------


def _get_json(base, path):
    with urllib.request.urlopen(base + path, timeout=10) as resp:
        return json.loads(resp.read())


def test_cluster_http_endpoints(monkeypatch):
    """/v1/cluster/* fan the query out to every peer and merge;
    unreachable peers are marked per-server instead of failing the
    whole query; /v1/metrics/history serves the ring."""
    from nomad_tpu.api import start_http_server

    monkeypatch.setenv("NOMAD_TPU_FANOUT", "1")
    monkeypatch.setenv("NOMAD_TPU_OBS_FANIN_TIMEOUT_S", "2.0")
    TRACE.clear()
    cluster = TestCluster(3, heartbeat_ttl=300.0)
    cluster.start()
    http = None
    try:
        leader = cluster.wait_for_leader(timeout=30.0)
        for node in _nodes(8, prefix="ch-node"):
            leader.register_node(node)
        evs = []
        for i, job in enumerate(_jobs(6, prefix="ch-job")):
            evs.append(cluster.servers[i % 3].register_job(job))
        wait_until(
            lambda: cluster.wait_for_leader(timeout=30.0)
            .drain_to_idle(timeout=1.0),
            timeout=90.0,
            msg="drain",
        )
        leader = cluster.wait_for_leader(timeout=30.0)
        http = start_http_server(leader, port=0)
        base = f"http://127.0.0.1:{http.port}"

        listing = _get_json(base, "/v1/cluster/traces?limit=64")
        assert listing["unreachable"] == 0
        assert set(listing["servers"].values()) == {"ok"}
        assert len(listing["servers"]) == 3
        listed = {t["eval_id"] for t in listing["traces"]}
        for ev in evs:
            assert ev.id in listed
        # the merged listing is deduplicated by trace id
        assert len(listed) == len(listing["traces"])
        assert all(t.get("server") for t in listing["traces"])

        detail = _get_json(base, f"/v1/cluster/traces/{evs[0].id}")
        assert detail["complete"]
        assert detail["server"]
        assert set(detail["servers"].values()) == {"ok"}
        assert any(
            s["name"] == "store.commit" for s in detail["spans"]
        )
        try:
            urllib.request.urlopen(
                base + "/v1/cluster/traces/nope", timeout=10
            )
            assert False, "expected 404"
        except urllib.error.HTTPError as exc:
            assert exc.code == 404

        # metric history: snapshot deterministically, then read back
        leader.metrics_history.snapshot_once()
        leader.metrics_history.snapshot_once()
        hist = _get_json(base, "/v1/metrics/history")
        assert hist["enabled"] is True
        assert len(hist["windows"]) >= 2
        assert "batch_worker.eval_latency_ms" in (
            hist["windows"][-1]["samples"]
        )
        series = _get_json(
            base,
            "/v1/metrics/history?name=batch_worker.eval_latency_ms",
        )
        assert series["name"] == "batch_worker.eval_latency_ms"
        assert all("p99" in p for p in series["series"])

        merged = _get_json(base, "/v1/cluster/metrics")
        assert merged["unreachable"] == 0
        assert len(merged["servers"]) == 3
        for data in merged["servers"].values():
            assert "counters" in data
        hist_all = _get_json(base, "/v1/cluster/metrics/history")
        assert len(hist_all["servers"]) == 3

        # partial results: a peer that cannot be reached is MARKED,
        # not silently dropped and not fatal
        down = cluster.followers()[0].addr
        cluster.transport.set_down(down)
        try:
            merged = _get_json(base, "/v1/cluster/metrics")
            assert merged["unreachable"] == 1
            assert merged["servers"][down] == {"unreachable": True}
            listing = _get_json(base, "/v1/cluster/traces?limit=8")
            assert listing["servers"][down] == "unreachable"
        finally:
            cluster.transport.set_down(down, down=False)
        assert (
            leader.metrics.get_counter("cluster.fanin_unreachable")
            >= 2.0
        )
        assert (
            leader.metrics.get_counter("cluster.fanin_queries") > 0
        )
    finally:
        if http is not None:
            http.stop()
        cluster.stop()
        TRACE.clear()
