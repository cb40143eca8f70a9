"""Eval flight recorder tests: tracer unit behavior, the /v1/traces
HTTP surface, the terminal waterfall renderer, and the acceptance
soak — >= 64 evals through the batch pipeline with parallel replay on,
every completed eval carrying a complete well-nested trace
(dequeue -> commit), forced conflicts recording the tripped fence and
the serial re-replay, and tracing overhead staying within budget on a
config2-like run."""
import copy
import json
import random
import time
import urllib.request

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server
from nomad_tpu.structs import compute_node_class
from nomad_tpu.trace import MAX_SPANS, SPAN_NAMES, TRACE, Tracer


def make_nodes(n, seed=0, dcs=1, big=False):
    rng = random.Random(seed)
    nodes = []
    for i in range(n):
        node = mock.node()
        if big:
            # roomy nodes: soak streams must place every alloc (the
            # dequeue->commit assertion needs a committed plan)
            node.node_resources.cpu = rng.choice([16000, 32000])
            node.node_resources.memory_mb = rng.choice([32768, 65536])
        else:
            node.node_resources.cpu = rng.choice([4000, 8000])
            node.node_resources.memory_mb = rng.choice([8192, 16384])
        if dcs > 1:
            node.datacenter = f"dc{i % dcs}"
        node.computed_class = compute_node_class(node)
        nodes.append(node)
    return nodes


# -- tracer unit behavior ---------------------------------------------


def test_tracer_records_nested_spans_and_outcome():
    t = Tracer(ring=8)
    t.begin("ev-1", queue="service")
    with t.span("ev-1", "outer"):
        with t.span("ev-1", "inner", detail="x"):
            t.event("ev-1", "mark", n=3)
    t.annotate("ev-1", outcome="speculative")
    t.finish("ev-1", "ack")
    trace = t.get("ev-1")
    assert trace["complete"]
    assert trace["outcome"] == "speculative"
    assert trace["orphans"] == 0
    by_name = {s["name"]: s for s in trace["spans"]}
    assert by_name["broker.dequeue"]["parent"] is None
    assert by_name["outer"]["parent"] is None
    assert by_name["inner"]["parent"] == by_name["outer"]["id"]
    assert by_name["mark"]["parent"] == by_name["inner"]["id"]
    assert by_name["mark"]["dur_ms"] == 0.0
    assert by_name["inner"]["attrs"] == {"detail": "x"}


def test_tracer_nack_and_supersede_override_annotated_outcome():
    """Only a successful ack consumes the annotated outcome: a nack
    or a redelivery supersede describes an attempt that did not
    stick."""
    t = Tracer(ring=8)
    t.begin("ev-n")
    t.annotate("ev-n", outcome="sequential")
    t.finish("ev-n", "nack")
    assert t.get("ev-n")["outcome"] == "nack"

    t.begin("ev-s")
    t.annotate("ev-s", outcome="sequential")
    t.begin("ev-s")  # redelivery supersedes the running attempt
    t.finish("ev-s", "ack")
    outcomes = sorted(
        tr["outcome"]
        for tr in t.recent(limit=10)
        if tr["eval_id"] == "ev-s"
    )
    assert outcomes == ["ack", "superseded"]


def test_tracer_drops_superseded_generations_stale_spans():
    """After a redelivery, the old attempt's in-flight writes resolve
    (by eval id) to the NEW trace; intervals that began before the
    new trace did are the old generation's and must not pollute it
    with negative offsets."""
    t = Tracer(ring=8)
    t.begin("ev-g")
    stale_start = time.monotonic()
    time.sleep(0.002)
    t.begin("ev-g")  # redelivery
    t.add_span("ev-g", "batch_worker.sequential", stale_start, 0.001)
    t.finish("ev-g", "ack")
    trace = t.get("ev-g")
    assert all(s["off_ms"] >= 0.0 for s in trace["spans"]), trace
    assert trace["dropped"] == 1
    assert [s["name"] for s in trace["spans"]] == ["broker.dequeue"]


def test_tracer_ring_is_bounded_and_span_cap_counts_drops():
    t = Tracer(ring=4)
    for i in range(10):
        t.begin(f"ev-{i}")
        t.finish(f"ev-{i}", "ack")
    assert len(t.recent(limit=100)) == 4
    assert t.get("ev-0") is None  # evicted
    assert t.get("ev-9") is not None
    t.begin("ev-big")
    for i in range(MAX_SPANS + 50):
        t.event("ev-big", "mark")
    t.finish("ev-big", "ack")
    trace = t.get("ev-big")
    assert len(trace["spans"]) == MAX_SPANS
    assert trace["dropped"] == 51  # 50 + the broker.dequeue slot

    # redelivery: a second begin supersedes the first trace
    t2 = Tracer(ring=8)
    t2.begin("ev-r")
    t2.begin("ev-r")
    t2.finish("ev-r", "ack")
    superseded = [
        tr
        for tr in t2.recent(limit=10)
        if tr["eval_id"] == "ev-r" and tr["outcome"] == "superseded"
    ]
    assert len(superseded) == 1


def test_tracer_disabled_is_a_noop():
    t = Tracer(ring=8)
    t.set_enabled(False)
    t.begin("ev-off")
    with t.span("ev-off", "outer"):
        t.event("ev-off", "mark")
    t.finish("ev-off", "ack")
    assert t.get("ev-off") is None
    assert t.recent() == []


def test_tracer_recent_filters_slow_and_outcome():
    t = Tracer(ring=16)
    t.begin("ev-fast")
    t.finish("ev-fast", "ack")
    t.begin("ev-slow")
    t.add_span("ev-slow", "work", time.monotonic(), 1.0)  # 1000ms
    t.annotate("ev-slow", outcome="sequential")
    t.finish("ev-slow", "ack")
    slow = t.recent(slow_ms=500.0, limit=10)
    assert [x["eval_id"] for x in slow] == ["ev-slow"]
    seq = t.recent(outcome="sequential", limit=10)
    assert [x["eval_id"] for x in seq] == ["ev-slow"]
    assert t.recent(outcome="nack", limit=10) == []


def test_span_names_in_this_repo_are_registered():
    """Names recorded by the live pipeline must come from the
    documented registry (the lint checks call sites; this checks the
    other direction on a real trace)."""
    t = Tracer(ring=4)
    t.begin("ev-reg")
    t.finish("ev-reg", "ack")
    for span in t.get("ev-reg")["spans"]:
        assert span["name"] in SPAN_NAMES


# -- waterfall renderer -----------------------------------------------


def test_trace_report_renders_waterfall():
    import os
    import sys

    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools",
        ),
    )
    try:
        import trace_report
    finally:
        sys.path.pop(0)

    t = Tracer(ring=4)
    t.begin("ev-rpt", queue="service")
    with t.span("ev-rpt", "batch_worker.replay", mode="serial"):
        t.event("ev-rpt", "store.commit", index=7)
    t.annotate("ev-rpt", outcome="prescored")
    t.finish("ev-rpt", "ack")
    text = trace_report.render(t.get("ev-rpt"))
    lines = text.splitlines()
    assert "outcome=prescored" in lines[0]
    assert any("batch_worker.replay" in line for line in lines)
    # the nested commit mark is indented under its parent span
    commit = next(line for line in lines if "store.commit" in line)
    assert "  store.commit" in commit
    assert "index=7" in commit
    # listing mode renders summaries without spans
    listing = trace_report.render(t.recent(limit=4))
    assert "ev-rpt" in listing


# -- /v1/traces HTTP surface ------------------------------------------


def _get_json(base, path):
    with urllib.request.urlopen(base + path, timeout=10) as resp:
        return json.loads(resp.read())


def test_traces_http_endpoints():
    from nomad_tpu.api import start_http_server

    server = Server(num_schedulers=1, seed=21, batch_pipeline=True)
    server.start()
    http = start_http_server(server, port=0)
    base = f"http://127.0.0.1:{http.port}"
    try:
        for node in make_nodes(6, seed=1):
            server.register_node(node)
        evs = []
        for i in range(4):
            job = mock.job(id=f"http-trace-{i}")
            job.task_groups[0].count = 2
            evs.append(server.register_job(job))
        assert server.drain_to_idle(30)

        listing = _get_json(base, "/v1/traces?limit=200")
        listed_ids = {t["eval_id"] for t in listing}
        for ev in evs:
            assert ev.id in listed_ids
        # summaries carry no span bodies; ?full=1 does
        entry = next(t for t in listing if t["eval_id"] == evs[0].id)
        assert isinstance(entry["spans"], int)
        full = _get_json(base, "/v1/traces?limit=200&full=1")
        entry = next(t for t in full if t["eval_id"] == evs[0].id)
        assert isinstance(entry["spans"], list)

        detail = _get_json(base, f"/v1/traces/{evs[0].id}")
        names = [s["name"] for s in detail["spans"]]
        assert "broker.dequeue" in names
        assert "store.commit" in names
        assert detail["complete"]
        # the listing's full trace id (eval#gen) resolves too
        by_tid = _get_json(
            base, f"/v1/traces/{detail['trace_id']}"
        )
        assert by_tid["trace_id"] == detail["trace_id"]

        # filters
        assert _get_json(
            base, "/v1/traces?slow_ms=9000000"
        ) == []
        outcome = detail["outcome"]
        filtered = _get_json(base, f"/v1/traces?outcome={outcome}")
        assert all(t["outcome"] == outcome for t in filtered)
        assert any(t["eval_id"] == evs[0].id for t in filtered)

        # unknown id -> 404
        try:
            urllib.request.urlopen(
                base + "/v1/traces/nope", timeout=10
            )
            assert False, "expected 404"
        except urllib.error.HTTPError as exc:
            assert exc.code == 404

        # metrics exemplars: slow batch_worker samples name the eval
        dump = _get_json(base, "/v1/metrics")
        replay = dump["samples"].get("batch_worker.replay")
        if replay is not None:
            assert any(
                e["trace_id"] in listed_ids
                for e in replay["exemplars"]
            ), replay
    finally:
        http.stop()
        server.stop()


# -- acceptance soak --------------------------------------------------


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


def test_soak_64_evals_all_traced_end_to_end():
    """>= 64 evals through the batch pipeline with parallel replay on:
    every completed eval has a complete, well-nested trace spanning
    dequeue -> state commit."""
    server = Server(num_schedulers=1, seed=77, batch_pipeline=True)
    assert server.workers[0].parallel_replay
    server.start()
    try:
        for node in make_nodes(16, seed=9, dcs=4, big=True):
            server.register_node(node)
        evs = []
        for i in range(64):
            job = mock.job(id=f"soak-{i}")
            if i % 3 == 2:
                job.type = "batch"
            job.task_groups[0].count = 2
            job.task_groups[0].tasks[0].resources.cpu = 200
            evs.append(server.register_job(job))
        assert server.drain_to_idle(120)

        # every job placed: exhaustion would legitimately skip the
        # plan commit and void the dequeue->commit assertion below
        for i in range(64):
            placed = [
                a
                for a in server.store.allocs_by_job(
                    "default", f"soak-{i}"
                )
                if not a.terminal_status()
            ]
            assert len(placed) == 2, f"soak-{i} placed {len(placed)}"

        speculated = 0
        for ev in evs:
            trace = TRACE.get(ev.id)
            assert trace is not None, f"no trace for {ev.id}"
            assert trace["complete"], trace
            assert trace["outcome"] not in (None, "nack"), trace
            assert trace["dropped"] == 0
            names = [s["name"] for s in trace["spans"]]
            # dequeue -> commit: the trace covers the whole lifecycle
            assert names[0] == "broker.dequeue", names
            assert "store.commit" in names, (trace["outcome"], names)
            # every eval enters the pipeline through a gulp OR a
            # mid-chain admission (continuous micro-batching)
            assert (
                "batch_worker.gulp" in names
                or "batch_worker.admit" in names
            ), names
            # a timed scheduling stage is present on every path
            assert (
                "batch_worker.replay" in names
                or "replay.commit" in names
                or "batch_worker.sequential" in names
            ), names
            _assert_well_nested(trace)
            if "replay.speculate" in names:
                speculated += 1
                spec = next(
                    s
                    for s in trace["spans"]
                    if s["name"] == "replay.speculate"
                )
                # straggler attribution: the pool thread is recorded
                assert spec["thread"].startswith("replay-spec"), spec
        # the wave path must actually have engaged for the soak to
        # mean anything
        assert speculated > 0
        assert server.workers[0].replay_speculative > 0
    finally:
        server.stop()


def test_forced_conflict_trace_records_fence_and_serial_replay(
    monkeypatch,
):
    """Strict mode on a tiny contended cluster forces conflicts: the
    discarded speculation's trace must record WHICH fence tripped and
    the serial re-replay that followed."""
    monkeypatch.setenv("NOMAD_TPU_REPLAY_STRICT", "1")
    server = Server(num_schedulers=1, seed=42, batch_pipeline=True)
    assert server.workers[0].replay_strict
    server.start()
    try:
        for node in make_nodes(6, seed=5):
            server.register_node(node)
        evs = []
        for i in range(10):
            job = mock.job(id=f"tconflict-{i}")
            job.task_groups[0].count = random.Random(i).randint(2, 3)
            job.task_groups[0].tasks[0].resources.cpu = 300
            evs.append(server.register_job(job))
        assert server.drain_to_idle(60)
        worker = server.workers[0]
        assert worker.replay_conflicts > 0

        conflicted = []
        for ev in evs:
            trace = TRACE.get(ev.id)
            if trace is None:
                continue
            for span in trace["spans"]:
                if span["name"] == "replay.conflict":
                    conflicted.append((trace, span))
        assert conflicted, "no trace recorded a replay.conflict"
        for trace, conflict in conflicted:
            # the tripped fence is named ...
            assert conflict["attrs"].get("fence") in {
                "strict_node",
                "plan_node",
                "job_ledger",
                "job_version",
                "scheduler_config",
                "deployment",
                "readiness",
            }, conflict
            names = [s["name"] for s in trace["spans"]]
            # ... the demotion is marked with its reason ...
            fallback = next(
                s
                for s in trace["spans"]
                if s["name"] == "replay.serial_fallback"
            )
            assert fallback["attrs"]["reason"] == "conflict"
            # ... and the serial re-replay actually ran
            assert (
                "batch_worker.replay" in names
                or "batch_worker.sequential" in names
            ), names
    finally:
        server.stop()


def test_trace_overhead_under_budget_on_config2_like_run():
    """Always-on tracing must cost < 5% wall time on a config2-like
    batch stream.  Interleaved on/off runs, min-of-2 per mode (min
    filters scheduler noise); a small absolute allowance covers timer
    jitter at this miniature scale.  A per-op microbench additionally
    bounds the recorder's primitive cost so the wall-clock contract
    isn't carried by noise alone."""
    # microbench: span open+close and event append, amortized
    t = Tracer(ring=8)
    t.begin("ev-micro")
    n_ops = 20_000
    t0 = time.perf_counter()
    for _ in range(n_ops // 2):
        with t.span("ev-micro", "batch_worker.replay"):
            pass
        t.event("ev-micro", "store.commit", index=1)
    per_op_us = (time.perf_counter() - t0) / n_ops * 1e6
    # ~25 trace ops per eval at ~10ms/eval -> well under 1% even at
    # 20us/op; a regression past this bound would threaten the 5%
    assert per_op_us < 50.0, f"{per_op_us:.1f}us per trace op"

    def run_once(enabled, rep):
        TRACE.set_enabled(enabled)
        server = Server(
            num_schedulers=1, seed=1000 + rep, batch_pipeline=True
        )
        server.start()
        try:
            for node in make_nodes(24, seed=3):
                server.register_node(node)
            jobs = []
            for i in range(24):
                job = mock.job(id=f"ovh-{rep}-{int(enabled)}-{i}")
                job.type = "batch"
                job.task_groups[0].count = 10
                job.task_groups[0].tasks[0].resources.cpu = 100
                jobs.append(job)
            t0 = time.monotonic()
            for job in jobs:
                server.register_job(job)
            assert server.drain_to_idle(120)
            return time.monotonic() - t0
        finally:
            server.stop()

    times = {True: [], False: []}
    try:
        for rep in range(2):
            for enabled in (True, False):
                times[enabled].append(run_once(enabled, rep))
    finally:
        TRACE.set_enabled(True)
    t_on, t_off = min(times[True]), min(times[False])
    overhead_pct = (t_on - t_off) / t_off * 100.0
    # the 5% contract, with a 0.2s absolute allowance: at this
    # miniature scale a sub-0.2s delta is scheduler jitter, not
    # recorder cost (the microbench above pins the per-op cost)
    assert t_on <= t_off * 1.05 + 0.2, (
        f"tracing overhead {overhead_pct:.1f}% "
        f"(on={t_on:.2f}s off={t_off:.2f}s)"
    )


# -- one causal tree, folded by layer (PR 27) -------------------------


def _on_thread(name, fn):
    """Run ``fn`` on a thread of its own (a span's recording thread is
    part of the record)."""
    import threading

    th = threading.Thread(target=fn, name=name)
    th.start()
    th.join()


def _built_trace():
    """An eval's life laid out by hand, 1 s before now: ingress and
    broker wait, two overlapping sibling stages, two chunk-wide stages
    with members=8, a cross-thread speculation caused by the fetch, a
    commit whose plan crosses to the applier's threads and back."""
    t = Tracer(ring=8)
    b = time.monotonic() - 1.0
    t.begin("ev-f", t0=b, queue="service")
    t.add_span("ev-f", "ingress.register", b, 0.001, thread="http")
    t.add_span("ev-f", "broker.wait", b + 0.001, 0.100)
    # overlapping siblings: the later start owns the overlap
    t.add_span("ev-f", "batch_worker.simulate", b + 0.102, 0.004)
    t.add_span("ev-f", "batch_worker.admit", b + 0.104, 0.005)
    t.add_span(
        "ev-f", "batch_worker.assemble", b + 0.110, 0.008,
        members=8, cpu_ms=4.0,
    )
    fetch = t.add_span(
        "ev-f", "batch_worker.fetch", b + 0.120, 0.016,
        members=8, cpu_ms=0.5,
    )
    # the pool thread replays the rows AFTER the fetch that caused it
    _on_thread(
        "replay-spec_0",
        lambda: t.add_span(
            "ev-f", "replay.speculate", b + 0.140, 0.010,
            cause=fetch, cpu_ms=9.0,
        ),
    )
    t.add_span("ev-f", "replay.commit_wait", b + 0.145, 0.015)
    commit = t.add_span("ev-f", "replay.commit", b + 0.160, 0.020)

    def verifier():
        t.add_span(
            "ev-f", "plan.queue_wait", b + 0.161, 0.001, cause=commit
        )
        t.add_span(
            "ev-f", "plan.evaluate", b + 0.162, 0.003, cause=commit,
            cpu_ms=2.5,
        )

    def committer():
        t.add_span(
            "ev-f", "plan.stage_wait", b + 0.165, 0.001, cause=commit
        )
        apply = t.add_span(
            "ev-f", "plan.apply", b + 0.166, 0.004, cause=commit,
            cpu_ms=3.0,
        )
        t.add_span(
            "ev-f", "store.commit", b + 0.167, 0.002, cause=apply,
            cpu_ms=1.5,
        )

    _on_thread("plan-verifier", verifier)
    _on_thread("plan-applier", committer)
    t.add_span(
        "ev-f", "plan.respond_wait", b + 0.170, 0.001, cause=commit
    )
    return t, fetch, commit


def test_fold_partitions_a_built_trace_by_layer():
    from nomad_tpu.trace import LAYERS

    t, fetch, commit = _built_trace()
    trace = t.finish("ev-f", "ack")
    assert trace is not None
    fold = trace.fold()
    assert fold is not None
    ms = fold.self_ms
    assert list(ms) == list(LAYERS)
    approx = lambda v: __import__("pytest").approx(v, abs=1e-6)
    assert ms["ingress"] == approx(1.0)
    assert ms["broker"] == approx(100.0)
    # simulate 2 (admit, the later start, owns the overlap) + admit 5
    # + assemble 8/8 + fetch 16/8 + the commit's own 20 - 10
    assert ms["bw_host"] == approx(2.0 + 5.0 + 1.0 + 2.0 + 10.0)
    # the cross-thread child runs outside its cause's interval and
    # still counts once; it is deeper than the wait it overlaps
    assert ms["replay_pool"] == approx(10.0)
    assert ms["plan_handoff"] == approx(3.0)
    assert ms["plan_applier"] == approx(3.0 + 2.0)
    assert ms["store"] == approx(2.0)
    # commit_wait's remainder, the chunk-mates' 7/8, the root's own
    covered = 1 + 100 + 7 + 8 + 16 + 20 + 20
    assert ms["pipeline_wait"] == approx(
        10.0 + 7.0 + 14.0 + (fold.life_ms - covered)
    )
    # the eight sum to the life within 1 us
    assert abs(sum(ms.values()) - fold.life_ms) < 1e-3
    # CPU: assemble's share, the pool's, the applier's less the
    # store's (same thread); the fetch waits on the device by design
    assert fold.cpu_ms["bw_host"] == approx(0.5)
    assert fold.cpu_ms["replay_pool"] == approx(9.0)
    assert fold.cpu_ms["plan_applier"] == approx(2.5 + 3.0 - 1.5)
    assert fold.cpu_ms["store"] == approx(1.5)
    assert fold.cpu_wall_ms == approx(1.0 + 10.0 + 3.0 + 2.0 + 2.0)
    # the served form carries the split and each span's self time
    doc = t.get("ev-f")
    assert doc["layers_ms"] == fold.self_ms
    by_id = {s["id"]: s for s in doc["spans"]}
    assert by_id[commit]["self_ms"] == approx(10.0)
    assert by_id[fetch]["self_ms"] == approx(16.0)
    spec = next(
        s for s in doc["spans"] if s["name"] == "replay.speculate"
    )
    assert spec["parent"] == fetch
    assert spec["thread"] == "replay-spec_0"
    assert spec["cpu_ms"] == 9.0


def test_fold_reaches_telemetry_and_an_open_span_counts_unfolded():
    from nomad_tpu.telemetry import Metrics
    from nomad_tpu.trace import FOLD_SAMPLES

    metrics = Metrics()
    t, _fetch, _commit = _built_trace()
    # the spans the finishing thread holds open enclose the finish
    # itself (broker.ack inside replay.commit): ended there, no orphan
    with t.span("ev-f", "batch_worker.replay"):
        with t.span("ev-f", "broker.ack"):
            settled = t.finish("ev-f", "ack")
    t.publish(settled, metrics)
    assert settled.orphans == 0
    assert metrics.get_counter("trace.folded") == 1.0
    assert metrics.get_counter("trace.unfolded") == 0.0
    dump = metrics.dump()["samples"]
    assert set(FOLD_SAMPLES) <= set(dump)
    assert all(dump[name]["count"] == 1 for name in FOLD_SAMPLES)
    total = sum(
        dump[n]["sum"] for n in FOLD_SAMPLES
        if n.startswith("trace.self.")
    )
    assert abs(total - dump["trace.life"]["sum"]) < 1e-3
    assert dump["trace.life"]["exemplars"][0]["trace_id"] == "ev-f"

    # a span ANOTHER thread still holds open: the trace cannot be
    # folded, and the meter says so
    t.begin("ev-open")
    opened = []
    _on_thread(
        "replay-spec_1",
        lambda: opened.append(
            t.span("ev-open", "replay.speculate").__enter__()
        ),
    )
    settled = t.finish("ev-open", "ack")
    assert settled.orphans == 1
    assert settled.fold() is None
    settled.folds = True  # (one ack in FOLD_SAMPLE is the fold's)
    t.publish(settled, metrics)
    # an acked eval whose trace left the ring is unfolded too
    t.publish(t.finish("ev-never-traced", "ack"), metrics)
    assert metrics.get_counter("trace.folded") == 1.0
    assert metrics.get_counter("trace.unfolded") == 2.0
    # the recorder off: nothing is counted at all
    t.set_enabled(False)
    t.publish(None, metrics)
    assert metrics.get_counter("trace.unfolded") == 2.0


def test_redelivered_eval_starts_its_own_broker_wait():
    """A nack re-enqueues the eval; the generation the next dequeue
    begins is dated back to THAT enqueue, and carries its own
    `broker.wait` (the first generation keeps its own)."""
    from nomad_tpu.server.eval_broker import EvalBroker

    TRACE.clear()
    broker = EvalBroker(nack_timeout=60.0)
    broker.set_enabled(True)
    try:
        ev = mock.evaluation()
        broker.enqueue(ev)
        time.sleep(0.02)
        got, token = broker.dequeue([ev.type], timeout=1.0)
        assert got is ev
        first = TRACE.get(ev.id)
        time.sleep(0.02)
        broker.nack(ev.id, token)
        time.sleep(0.03)
        got, token = broker.dequeue([ev.type], timeout=1.0)
        assert got is ev
        second = TRACE.get(ev.id)
        assert second["trace_id"] != first["trace_id"]
        assert TRACE.get(first["trace_id"])["outcome"] == "nack"
        for doc, waited in ((first, 20.0), (second, 30.0)):
            wait = next(
                s for s in doc["spans"] if s["name"] == "broker.wait"
            )
            # the trace begins where the wait does: at the (re-)enqueue
            assert wait["off_ms"] == 0.0 and wait["parent"] is None
            assert wait["dur_ms"] >= waited
            assert wait["attrs"]["queue"] == ev.type
            dequeue = next(
                s for s in doc["spans"]
                if s["name"] == "broker.dequeue"
            )
            # the wait ends at the dequeue mark (recorded first)
            assert abs(dequeue["off_ms"] - wait["dur_ms"]) < 1.0
        # the second wait does not reach back into the first delivery
        assert second["start"] > first["start"] + 0.04
        broker.ack(ev.id, token)
        assert TRACE.get(ev.id)["outcome"] == "ack"
    finally:
        broker.set_enabled(False)
        TRACE.clear()


@pytest.mark.parametrize("path", ["direct", "pipeline"])
def test_served_evals_fold_into_one_causal_tree(path, monkeypatch):
    """20 registrations over HTTP on a small batch-pipeline server:
    every acked eval is folded, its trace begins at the handler, the
    plan's spans hang under the submitter's span (not beside it), and
    `store.commit` is a child of `plan.apply`.  The one submitter of
    such a server finds the applier idle, so its plans are verified
    and committed on its own thread with no hand-off to wait for
    (``direct``); where the applier is busy the plan crosses to the
    pipeline's threads and back, and its three waits are spans too
    (``pipeline``, held busy here by refusing every claim)."""
    from nomad_tpu.api import start_http_server
    from nomad_tpu.api.codec import job_to_dict
    from nomad_tpu.trace import FOLD_SAMPLE, LAYERS

    TRACE.clear()
    server = Server(num_schedulers=1, seed=27, batch_pipeline=True)
    if path == "pipeline":
        monkeypatch.setattr(
            server.applier, "_claim_direct", lambda: None
        )
    server.start()
    http = start_http_server(server, port=0)
    base = f"http://127.0.0.1:{http.port}"
    try:
        for node in make_nodes(16, seed=9, dcs=4, big=True):
            server.register_node(node)
        eval_ids = []
        for i in range(20):
            job = mock.job(id=f"tree-{i}")
            job.task_groups[0].count = 2
            job.task_groups[0].tasks[0].resources.cpu = 200
            req = urllib.request.Request(
                base + "/v1/jobs",
                data=json.dumps({"Job": job_to_dict(job)}).encode(),
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                eval_ids.append(json.loads(resp.read())["EvalID"])
        assert server.drain_to_idle(120)

        # one ack in FOLD_SAMPLE is folded into the telemetry (every
        # eval's trace folds on demand, below); none of those fails
        dump = _get_json(base, "/v1/metrics")
        folded = dump["counters"]["trace.folded"]
        assert 20 // FOLD_SAMPLE <= folded <= 20 // FOLD_SAMPLE + 1
        assert dump["counters"]["trace.unfolded"] == 0.0
        samples = dump["samples"]
        assert samples["trace.life"]["count"] == folded
        split = sum(samples[f"trace.self.{k}"]["sum"] for k in LAYERS)
        assert abs(split - samples["trace.life"]["sum"]) < folded * 1e-3
        assert samples["trace.self.broker"]["sum"] > 0.0
        assert samples["trace.self.ingress"]["sum"] > 0.0
        assert samples["trace.cpu_wall"]["count"] <= folded
        # one sample a folded trace for every layer, the hand-off's
        # too where there was none to wait for: it then reads 0
        handoff = samples["trace.self.plan_handoff"]
        assert handoff["count"] == folded
        took = "plan.direct" if path == "direct" else "plan.queued"
        assert dump["counters"][took] >= 20.0
        assert (
            dump["counters"]["plan.direct"]
            + dump["counters"]["plan.queued"]
            == dump["counters"][took]
        )
        waits = ("plan.queue_wait", "plan.stage_wait", "plan.respond_wait")
        if path == "direct":
            assert handoff["sum"] == 0.0
        else:
            assert handoff["sum"] > 0.0

        submitters = {
            "replay.commit", "batch_worker.replay",
            "batch_worker.sequential", "worker.invoke_scheduler",
        }
        for eval_id in eval_ids:
            doc = _get_json(base, f"/v1/traces/{eval_id}")
            assert doc["complete"] and doc["orphans"] == 0, doc
            spans = doc["spans"]
            by_id = {s["id"]: s for s in spans}
            names = [s["name"] for s in spans]
            # the life begins at the handler, then the broker's wait,
            # both before the first pipeline stage
            ingress = next(
                s for s in spans if s["name"] == "ingress.register"
            )
            wait = next(s for s in spans if s["name"] == "broker.wait")
            assert ingress["off_ms"] == 0.0
            assert ingress["parent"] is None and wait["parent"] is None
            assert not ingress["thread"].startswith("worker")
            first_stage = min(
                s["off_ms"] for s in spans
                if s["name"].startswith(("batch_worker.", "replay."))
            )
            assert wait["off_ms"] + wait["dur_ms"] <= first_stage + 1e-3
            # the plan's life under the span that submitted it
            plan_spans = ("plan.evaluate", "plan.apply") + (
                waits if path == "pipeline" else ()
            )
            for name in plan_spans:
                span = next(s for s in spans if s["name"] == name)
                assert span["parent"] is not None, (name, names)
                assert by_id[span["parent"]]["name"] in submitters, (
                    name, by_id[span["parent"]]["name"],
                )
            evaluate = next(
                s for s in spans if s["name"] == "plan.evaluate"
            )
            apply = next(s for s in spans if s["name"] == "plan.apply")
            submitter = by_id[evaluate["parent"]]
            assert apply["parent"] == evaluate["parent"]
            if path == "direct":
                # no thread crossed, so no wait was recorded and the
                # hand-off layer holds none of this eval's life
                assert not set(waits) & set(names), names
                assert evaluate["thread"] == submitter["thread"]
                assert apply["thread"] == submitter["thread"]
                assert evaluate["attrs"]["overlay"] is False
                assert "full" in evaluate["attrs"]
                assert doc["layers_ms"]["plan_handoff"] == 0.0
            else:
                assert evaluate["thread"] == "plan-verifier"
                assert apply["thread"] == "plan-applier"
                assert submitter["thread"] != "plan-verifier"
                assert doc["layers_ms"]["plan_handoff"] > 0.0
            assert doc["layers_ms"]["plan_applier"] > 0.0
            commit = next(
                s for s in spans if s["name"] == "store.commit"
            )
            assert by_id[commit["parent"]]["name"] == "plan.apply"
            assert commit["dur_ms"] > 0.0
            # the broker's own ack is the eval's last span, under the
            # span that acked
            ack = next(s for s in spans if s["name"] == "broker.ack")
            assert by_id[ack["parent"]]["name"] in submitters
            assert abs(
                ack["off_ms"] + ack["dur_ms"] - doc["life_ms"]
            ) < 0.5
            # the served split partitions this eval's life
            assert abs(
                sum(doc["layers_ms"].values()) - doc["life_ms"]
            ) < 1e-3
            _assert_well_nested(doc)
    finally:
        http.stop()
        server.stop()
        TRACE.clear()


def test_one_trace_in_cpu_sample_reads_the_thread_cpu_clock():
    """The thread CPU clock is a system call (microseconds on a
    sandboxed host), so one trace in CPU_SAMPLE pays for it: its open
    spans carry `cpu_ms`, a caller that times a span by hand gets a
    clock reading for it, and every other trace gets neither — the
    fold then leaves those spans out of BOTH sides of the off-CPU
    share."""
    from nomad_tpu.trace import CPU_SAMPLE, cpu_ms_since

    t = Tracer(ring=2 * CPU_SAMPLE)
    sampled = []
    for i in range(2 * CPU_SAMPLE):
        eval_id = f"ev-c{i}"
        t.begin(eval_id)
        clock = t.cpu_clock(eval_id)
        with t.span(eval_id, "replay.commit"):
            sum(range(2000))
        t.add_span(
            eval_id, "plan.evaluate", time.monotonic(), 0.0,
            cpu_ms=cpu_ms_since(clock),
        )
        fold = t.finish(eval_id, "ack").fold()
        spans = {s["name"]: s for s in t.get(eval_id)["spans"]}
        if clock is not None:
            sampled.append(i)
            assert spans["replay.commit"]["cpu_ms"] is not None
            assert spans["plan.evaluate"]["cpu_ms"] >= 0.0
            assert fold.cpu_wall_ms > 0.0
            assert len(list(fold.samples())) == 14
        else:
            assert spans["replay.commit"]["cpu_ms"] is None
            assert spans["plan.evaluate"]["cpu_ms"] is None
            assert fold.cpu_wall_ms == 0.0
            # the life and the eight self times, no CPU side
            assert len(list(fold.samples())) == 9
    assert sampled == [0, CPU_SAMPLE]
    assert cpu_ms_since(None) is None


def test_one_ack_in_fold_sample_reaches_the_telemetry():
    """Every trace folds on demand; the ack folds one in FOLD_SAMPLE
    into the telemetry, so the `trace.*` series are means over the
    folded evals and the recorder stays cheap on the commit chain."""
    from nomad_tpu.telemetry import Metrics
    from nomad_tpu.trace import CPU_SAMPLE, FOLD_SAMPLE

    assert CPU_SAMPLE % FOLD_SAMPLE == 0  # a CPU-sampled trace folds
    t = Tracer(ring=4 * FOLD_SAMPLE)
    metrics = Metrics()
    for i in range(3 * FOLD_SAMPLE):
        eval_id = f"ev-s{i}"
        t.begin(eval_id)
        with t.span(eval_id, "replay.commit"):
            pass
        settled = t.finish(eval_id, "ack")
        assert settled.folds == (i % FOLD_SAMPLE == 0)
        t.publish(settled, metrics)
        # any finished trace still says where its life went
        assert "layers_ms" in t.get(eval_id)
    assert metrics.get_counter("trace.folded") == 3.0
    assert metrics.get_counter("trace.unfolded") == 0.0
    assert metrics.dump()["samples"]["trace.life"]["count"] == 3
