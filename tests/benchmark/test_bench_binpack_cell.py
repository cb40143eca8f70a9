"""``binpack-10k-f64.deploy``: the north-star fleet as a cell.  What
the manifest says of it, its configuration beside the float32 one it is
a copy of, its traffic file, the limit walk's reader (no entry lists it
yet: the entry is the next ``benchmark`` issue's, as data), and the
cell rehearsed on the CPU at a cut fleet."""
import argparse
import copy
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.manifest import Manifest, check_last_line, repo_root
from benchmark.reference import visit_limit

CELL = "binpack-10k-f64.deploy"
SPREAD = "spread-5k-f64.deploy"
PR26_ELEVEN = [
    "shed_share_pct", "bw_assemble_ms_per_eval", "bw_replay_ms_per_eval",
    "evals_per_launch", "host_path_share_pct", "plan_apply_ms_per_eval",
    "chain_kernel_ms_per_eval", "device_idle_share_pct",
    "compiles_in_window", "gc_pause_share_pct", "longest_gap_ms",
]
WALK = "walk_pulls_per_pick.deploy"
WALK_ENTRY = {
    "name": WALK, "unit": "nodes", "better": "lower",
    "source": "program_counter", "layer": "kernels",
    "moves": "placements_per_s", "workloads": [CELL],
}


@pytest.fixture(scope="module")
def manifest():
    m = Manifest()
    m.check()
    return m


@pytest.fixture(scope="module")
def with_the_walk(tmp_path_factory):
    """The shipped manifest with the walk's entry appended, over the
    shipped files: what a data-only addition of the entry looks like."""
    root = tmp_path_factory.mktemp("with_the_walk")
    doc = copy.deepcopy(Manifest().doc)
    doc["per_layer"].append(WALK_ENTRY)
    (root / "BENCHMARK.json").write_text(json.dumps(doc), encoding="utf-8")
    for path in doc["paths"]:
        os.makedirs((root / path).parent, exist_ok=True)
        os.symlink(os.path.join(repo_root(), path), root / path)
    m = Manifest(str(root))
    m.check()
    return m


def test_the_manifest_holds_the_cell_and_it_reports_placements(manifest):
    assert {SPREAD, CELL} <= {w["name"] for w in manifest.doc["workloads"]}
    cell = manifest.workload(CELL)
    assert cell["chips"] == 1 and cell["config"] == "binpack-10k-f64"
    assert cell["traffic"] == "deploy-128-unprobed"
    assert len(cell["why"]) <= 200
    for name in (SPREAD, CELL):
        e2e = [m["name"] for m in manifest.metrics_of("end_to_end", name)]
        assert sorted(e2e) == ["placements_per_s", "setup_s"]
    (entry,) = [
        c for c in manifest.doc["configs"] if c["name"] == "binpack-10k-f64"
    ]
    assert entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/binpack-10k-f64.json"
    assert len(entry["source"]) <= 200


@pytest.mark.parametrize("base", PR26_ELEVEN)
def test_the_cell_reports_this_layer_metric(manifest, base):
    listed = {m["name"]: m for m in manifest.metrics_of("per_layer", CELL)}
    m = listed[base + ".deploy"]
    assert m["moves"] == "placements_per_s"
    assert {SPREAD, CELL} <= set(m["workloads"])


def test_the_cell_leaves_the_whole_fleet_byte_count_to_the_spread_cell(
    manifest,
):
    names = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    # the whole-fleet byte count is not this cell's: a pick walks
    assert "chain_kernel_roofline_pct.deploy" not in names
    spread = {m["name"] for m in manifest.metrics_of("per_layer", SPREAD)}
    assert "chain_kernel_roofline_pct.deploy" in spread
    # every metric of this cell is one the spread cell reports too
    assert names <= spread


def test_the_configuration_is_binpack_10k_in_all_but_name_and_precision(manifest):
    f32 = manifest.config("binpack-10k")
    f64 = manifest.config("binpack-10k-f64")
    assert f64["name"] == "binpack-10k-f64" and f64["jax_enable_x64"] is True
    assert not f32.get("jax_enable_x64")
    assert f64["fleet"] == f32["fleet"] and f64["job"] == f32["job"]
    assert f64["fleet"]["nodes"] == 10000
    assert f64["fleet"]["resident_allocs"] == 100000
    assert f64["job"]["task_groups"][0]["count"] == 10
    assert f64["job"]["spreads"] == [] and f64["job"]["affinities"] == []
    differ = {k for k in set(f32) | set(f64) if f32.get(k) != f64.get(k)}
    assert differ == {"name", "assumed", "guarantees", "jax_enable_x64"}
    for key in ("placement", "durability", "exactly_once"):
        assert f64["guarantees"][key] == f32["guarantees"][key]
    spread = manifest.config("spread-5k-f64")
    assert f64["guarantees"]["precision"] == spread["guarantees"]["precision"]
    assert set(f64["assumed"]) - set(f32["assumed"]) == {"precision"}
    assert f64["reduced"] == [] and f64["source"] == f32["source"]


def test_the_traffic_is_the_closed_loop_of_128_without_a_probe_ramp(manifest):
    traffic = manifest.traffic("deploy-128-unprobed")
    who = traffic.pop("who")
    assert traffic == {
        "loop": "closed", "in_flight": 128, "senders": 128,
        "warmup_evals": 400, "mix": [{"share": 1, "count": None}],
    }
    assert "north-star" in who and "probe_ramp" not in traffic
    probed = manifest.traffic("deploy-128")
    for key in ("loop", "in_flight", "senders", "warmup_evals", "mix"):
        assert traffic[key] == probed[key]


def _obs(counters, trace=None):
    return {
        "window_s": 51.0, "evals": 4400, "attempted": 4400, "refused": 0,
        "counters": counters, "samples": {}, "trace": trace,
        "device_kind": "TPU v5 lite", "arena_rows": 16384,
        "picks_per_eval": 10.0, "column_bytes": 8,
    }


TRACE = {
    "modules": {"jit_chained_plan_picks_cols(123)": (32, 1.024)},
    "launch_evals": 256.0,
}


@pytest.mark.parametrize(
    "counters",
    [{}, {"batch_worker.prescored": 4400.0},
     {"batch_worker.walk_pulls": 0.0, "batch_worker.walk_picks": 0.0}],
    ids=["empty", "parent", "zero_registered"],
)
def test_the_walk_reader_is_silent_without_its_source(manifest, counters):
    read = manifest.layer_reader(WALK)
    assert read(_obs(counters)) is None
    assert read(_obs(counters, TRACE)) is None


def test_the_walk_reader_reads_the_counters(manifest):
    counters = {"batch_worker.walk_pulls": 4675000.0,
                "batch_worker.walk_picks": 44000.0}
    assert manifest.layer_reader(WALK)(_obs(counters)) == pytest.approx(106.25)


def test_the_walk_entry_is_a_data_only_addition(manifest, with_the_walk):
    """No entry of the shipped manifest lists the reader (PERF.md
    section 7: no place in ``per_layer`` is both the end of the list
    and before its pinned last entry); appended, it passes the schema
    and is the cell's alone."""
    assert WALK not in {m["name"] for m in manifest.doc["per_layer"]}
    listed = with_the_walk.metrics_of("per_layer", CELL)
    assert WALK_ENTRY in listed
    assert WALK_ENTRY not in with_the_walk.metrics_of("per_layer", SPREAD)


def test_a_rehearsal_of_the_cell_at_a_cut_fleet_is_correct_and_counts_the_walk(
    manifest, with_the_walk,
):
    args = argparse.Namespace(
        workload=CELL, seed=2**31 + 31, seconds=3.0, trace=1,
        allow_cpu=True, rehearsal_scale=0.03, out="",
    )
    code, result = bench_run.run(args, with_the_walk)
    assert code == 0 and result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    names = set(result["metrics"])
    assert WALK in names and "host_path_share_pct.deploy" in names
    # 300 nodes: 9 scored a pick, and no pick pulls the ring twice round
    pulls = result["metrics"][WALK]["value"]
    assert visit_limit(300) <= pulls <= 300
    assert result["metrics"]["host_path_share_pct.deploy"]["value"] == 0.0
    # the CPU has no device plane: shares of the device stay silent
    assert "chain_kernel_roofline_pct.deploy" not in names
    check_last_line(json.dumps(result), with_the_walk, CELL, trace=True)
    # the shipped manifest's line is this one without the walk's metric
    del result["metrics"][WALK]
    check_last_line(json.dumps(result), manifest, CELL, trace=True)
    # the same run through the command's own entry prints rehearsal_*
    # keys only; that wrapper is held by test_bench_rehearsal
