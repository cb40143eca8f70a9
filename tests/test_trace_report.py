"""Smoke tests for tools/trace_report.py — the terminal waterfall
renderer over eval flight-recorder traces (previously the only tool
with zero coverage).  Exercises rendering over a synthetic trace
ring: nesting depth, open spans, bars, attrs, list/summary modes and
the file/stdin loaders."""
import io
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import trace_report  # noqa: E402


def _trace(trace_id="eval-1#1", outcome="prescored"):
    """A synthetic completed trace shaped like /v1/traces/<id>:
    root span, two children (one nested two deep), one open span."""
    return {
        "trace_id": trace_id,
        "outcome": outcome,
        "duration_ms": 12.5,
        "dropped": 0,
        "attrs": {"queue": "service"},
        "spans": [
            {
                "id": 1, "parent": None,
                "name": "broker.dequeue",
                "off_ms": 0.0, "dur_ms": 0.05,
                "attrs": {"queue": "service"},
            },
            {
                "id": 2, "parent": 1,
                "name": "batch_worker.simulate",
                "off_ms": 0.2, "dur_ms": 6.0,
                "thread": "worker-0",
            },
            {
                "id": 3, "parent": 2,
                "name": "batch_worker.launch",
                "off_ms": 1.0, "dur_ms": 4.0,
            },
            {
                "id": 4, "parent": 1,
                "name": "batch_worker.replay",
                "off_ms": 7.0, "dur_ms": None,  # still open
            },
        ],
    }


def test_render_trace_waterfall_shape():
    text = trace_report.render_trace(_trace())
    lines = text.splitlines()
    # header: id, outcome, duration, span count
    assert "trace eval-1#1" in lines[0]
    assert "outcome=prescored" in lines[0]
    assert "12.50ms" in lines[0]
    assert "spans=4" in lines[0]
    # trace attrs on the second header line
    assert "queue=service" in lines[1]
    body = "\n".join(lines[2:])
    assert "broker.dequeue" in body
    assert "batch_worker.simulate" in body
    # depth indentation: the nested launch span is indented two
    # levels (its parent simulate is one level under the root)
    launch_row = next(
        ln for ln in lines if "batch_worker.launch" in ln
    )
    assert "    batch_worker.launch" in launch_row
    # open span renders OPEN instead of a duration
    replay_row = next(
        ln for ln in lines if "batch_worker.replay" in ln
    )
    assert "OPEN" in replay_row
    # proportional bars appear for measured spans
    assert "=" * 4 in body
    # per-span thread attribution surfaces
    assert "thread=worker-0" in body


def test_render_trace_in_flight_header():
    trace = _trace()
    trace["duration_ms"] = None
    text = trace_report.render_trace(trace)
    assert "(in flight)" in text.splitlines()[0]


def test_render_orphans_and_drops_flagged():
    trace = _trace()
    trace["dropped"] = 3
    trace["orphans"] = 2
    header = trace_report.render_trace(trace).splitlines()[0]
    assert "dropped=3" in header
    assert "ORPHANS=2" in header


def test_render_list_full_and_summary_modes():
    full = _trace("eval-a#1")
    summary = {
        "trace_id": "eval-b#1",
        "outcome": "sequential",
        "duration_ms": 3.25,
        "spans": 7,
    }
    text = trace_report.render([full, summary])
    parts = text.split("\n\n")
    assert len(parts) == 2
    assert "broker.dequeue" in parts[0]
    # summaries point at the per-eval endpoint for the waterfall
    assert "eval-b#1" in parts[1]
    assert "fetch /v1/traces/<eval_id>" in parts[1]
    assert "spans=7" in parts[1]


def test_render_empty_spans_ring():
    """A trace whose ring overflowed to nothing still renders a
    header (no div-by-zero on the bar scale, no max() on empty)."""
    text = trace_report.render_trace(
        {
            "trace_id": "eval-empty#1",
            "outcome": "prescored",
            "duration_ms": 0.0,
            "spans": [],
        }
    )
    assert "spans=0" in text


def test_load_from_file_and_stdin(tmp_path, monkeypatch):
    payload = _trace("eval-file#1")
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(payload))
    assert trace_report._load(str(p)) == payload
    monkeypatch.setattr(
        sys, "stdin", io.StringIO(json.dumps(payload))
    )
    assert trace_report._load("-") == payload


def test_main_renders_file(tmp_path, capsys):
    p = tmp_path / "ring.json"
    p.write_text(json.dumps([_trace("eval-ring#1")]))
    rc = trace_report.main(["trace_report.py", str(p)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "trace eval-ring#1" in out
    assert "batch_worker.simulate" in out


def test_main_usage_error(capsys):
    assert trace_report.main(["trace_report.py"]) == 2
    assert (
        trace_report.main(["trace_report.py", "--help"]) == 2
    )
    err = capsys.readouterr().err
    assert "waterfall" in err


def test_cross_thread_child_renders_under_its_cause_with_self_time():
    """The waterfall is the trace's one tree, depth first: a span
    another thread recorded sits under the span that caused it even
    where its offset falls among other spans, and a folded trace
    prints each span's self time and the layer split."""
    trace = {
        "trace_id": "eval-t#3",
        "outcome": "speculative",
        "duration_ms": 30.0,
        "life_ms": 30.0,
        "layers_ms": {
            "broker": 10.0, "pipeline_wait": 12.5, "bw_host": 2.5,
            "replay_pool": 3.0, "plan_handoff": 0.0,
            "plan_applier": 2.0,
        },
        "spans": [
            {"id": 0, "parent": None, "name": "broker.wait",
             "off_ms": 0.0, "dur_ms": 10.0, "self_ms": 10.0},
            {"id": 1, "parent": None, "name": "batch_worker.fetch",
             "off_ms": 11.0, "dur_ms": 2.0, "self_ms": 2.0,
             "thread": "worker", "attrs": {"members": 8}},
            # starts AFTER the commit below, caused by the fetch above
            {"id": 4, "parent": 1, "name": "replay.speculate",
             "off_ms": 16.0, "dur_ms": 3.0, "self_ms": 3.0,
             "cpu_ms": 1.25, "thread": "replay-spec_0"},
            {"id": 2, "parent": None, "name": "replay.commit",
             "off_ms": 14.0, "dur_ms": 2.5, "self_ms": 0.5,
             "thread": "worker"},
            {"id": 3, "parent": 2, "name": "plan.evaluate",
             "off_ms": 14.2, "dur_ms": 2.0, "self_ms": 2.0,
             "thread": "plan-verifier"},
        ],
    }
    lines = trace_report.render_trace(trace).splitlines()
    assert lines[1].startswith("  layers: broker=10.00ms")
    assert "plan_handoff" not in lines[1]  # empty layers are left out
    rows = [ln for ln in lines if "ms  " in ln and "layers:" not in ln]
    order = [
        next(n for n in (
            "broker.wait", "batch_worker.fetch", "replay.speculate",
            "replay.commit", "plan.evaluate",
        ) if n in ln)
        for ln in rows
    ]
    assert order == [
        "broker.wait", "batch_worker.fetch", "replay.speculate",
        "replay.commit", "plan.evaluate",
    ]
    spec = next(ln for ln in rows if "replay.speculate" in ln)
    assert "  replay.speculate" in spec and "self=3.00ms" in spec
    assert "cpu_ms=1.25" in spec and "thread=replay-spec_0" in spec
    evaluate = next(ln for ln in rows if "plan.evaluate" in ln)
    assert "  plan.evaluate" in evaluate
    commit = next(ln for ln in rows if "replay.commit" in ln)
    assert "2.50ms" in commit and "self=0.50ms" in commit
    # a trace that was never folded has no self-time column at all
    assert "self=" not in trace_report.render_trace(_trace())
