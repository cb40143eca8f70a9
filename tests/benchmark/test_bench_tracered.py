"""The reduction from a trace to busy/idle time, module time and idle
gaps: exact on a hand-made trace, and held to its recorded readings on a
small piece of a real v5e trace kept beside the benchmark."""
import json
import os

import pytest

from benchmark import tracered
from benchmark.manifest import repo_root

MS = 10**6


def _trace():
    ops = [
        ["%while.1 = (s32[]) while(...)", 10 * MS, 30 * MS],  # 10..40
        ["%fusion.2 = f32[8] fusion(...)", 15 * MS, 5 * MS],  # nested
        ["%fusion.3", 38 * MS, 12 * MS],                      # 38..50 overlaps
        ["%copy.4", 70 * MS, 10 * MS],                        # 70..80
        ["%late.5", 120 * MS, 10 * MS],                       # outside the window
    ]
    modules = [
        ["jit_chained_plan_picks_cols(123)", 10 * MS, 40 * MS],
        ["jit_patch_rows(9)", 70 * MS, 10 * MS],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            [tracered.MARK_OPEN, 0, 0], [tracered.MARK_CLOSE, 100 * MS, 0],
        ]}]},
    ]}


def test_busy_is_the_union_of_operation_intervals_inside_the_window():
    reduced = tracered.reduce_trace(_trace())
    assert reduced["planes"] == 1
    assert reduced["window_s"] == pytest.approx(0.100)
    assert reduced["busy_s"] == pytest.approx(0.050)  # 10..50 and 70..80
    count, seconds = tracered.module_seconds(reduced, "jit_chained_plan_picks_cols")
    assert (count, seconds) == (1, pytest.approx(0.040))
    # an event that straddles the window's edge counts only what is inside
    cut = tracered.reduce_trace(_trace(), window=(0, 30 * MS))
    assert tracered.module_seconds(cut, "jit_chained")[1] == pytest.approx(0.020)
    assert cut["busy_s"] == pytest.approx(0.020)
    assert tracered.module_seconds(reduced, "jit_nothing") == (0, 0.0)
    names = [n for n, _s in tracered.top(reduced["ops"])]
    assert names[0] == "%while.1" and "%late.5" not in names


def test_idle_gaps_go_to_the_host_span_that_covers_most_of_each():
    spans = [
        ("replay.speculate", 0, 9 * MS),            # most of gap 0..10
        ("batch_worker.launch", 8 * MS, 10 * MS),
        ("replay.commit", 52 * MS, 68 * MS),        # most of gap 50..70
        ("batch_worker.fetch", 50 * MS, 55 * MS),
    ]
    reduced = tracered.reduce_trace(_trace(), spans)
    gaps = reduced["idle_gaps"]
    assert gaps["replay.speculate"] == pytest.approx(0.010)
    assert gaps["replay.commit"] == pytest.approx(0.020)
    assert gaps["unattributed"] == pytest.approx(0.020)  # 80..100
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"]
    )


def test_a_trace_with_no_device_plane_reads_nothing():
    host_only = {"planes": [_trace()["planes"][1]]}
    reduced = tracered.reduce_trace(host_only)
    assert reduced["planes"] == 0 and reduced["busy_s"] == 0.0
    assert tracered.window_of({"planes": []}) is None


def test_short_names_drop_the_hlo_text():
    assert tracered.short_name("%while.69 = (s32[]{:T(128)}, f32[16384]) while") == "%while.69"
    assert tracered.short_name("jit_patch_rows(1)") == "jit_patch_rows(1)"


def test_excerpt_keeps_the_piece_and_moves_the_markers():
    piece = tracered.excerpt(_trace(), 5 * MS, 40 * MS)  # 5..45
    reduced = tracered.reduce_trace(piece)
    assert reduced["window_s"] == pytest.approx(0.040)
    # while 10..40 and fusion.3 38..50 clipped at 45
    assert reduced["busy_s"] == pytest.approx(0.035)


RECORDED = os.path.join(repo_root(), "benchmark", "testdata", "small_trace.json")


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded trace")
def test_the_small_recorded_v5e_trace_reduces_to_its_recorded_readings():
    with open(RECORDED, encoding="utf-8") as fh:
        doc = json.load(fh)
    reduced = tracered.reduce_trace(doc["trace"])
    want = doc["readings"]
    assert reduced["planes"] == 1
    assert reduced["window_s"] == pytest.approx(want["window_s"])
    assert reduced["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    count, seconds = tracered.module_seconds(reduced, "jit_chained_plan_picks_cols")
    assert count == want["kernel_launches"]
    assert seconds == pytest.approx(want["kernel_s"], rel=1e-9)
    # a module's operations run inside it, and nothing else ran here:
    # the kernel modules' time, cut at the window's edge, is the busy time
    assert seconds <= reduced["busy_s"] <= reduced["window_s"]
    assert seconds == pytest.approx(reduced["busy_s"], rel=1e-3)
