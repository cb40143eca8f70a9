"""``binpack-10k.deploy``: the north-star fleet at float32, the dtype the
chip runs natively.  What the manifest says of it (by containment: a
later cell or entry breaks nothing here), its configuration, the reader
of the picks the pair score's ``lo`` half decided (no entry lists it
yet: the entry is the next ``benchmark`` issue's, as data), and the
cell rehearsed on the CPU at a cut fleet in a process of its own, so
that ``jax_enable_x64`` is off there as the configuration states.
"""
import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import peaks
from benchmark.manifest import Manifest, check_last_line, repo_root

CELL = "binpack-10k.deploy"
F64_CELL = "binpack-10k-f64.deploy"
CONFIG = "binpack-10k-f32"
CONFIG_FILE = "benchmark/configs/binpack-10k.json"
F64_ELEVEN = [
    "shed_share_pct", "bw_assemble_ms_per_eval", "bw_replay_ms_per_eval",
    "evals_per_launch", "host_path_share_pct", "plan_apply_ms_per_eval",
    "chain_kernel_ms_per_eval", "device_idle_share_pct",
    "compiles_in_window", "gc_pause_share_pct", "longest_gap_ms",
]
PAIR = "pair_decided_per_mpick.deploy"
PAIR_ENTRY = {
    "name": PAIR, "unit": "picks/Mpick", "better": "lower",
    "source": "program_counter", "layer": "kernels",
    "moves": "placements_per_s", "workloads": [CELL],
}
PAIR_COUNTER = "batch_worker.pair_decided_picks"


@pytest.fixture(scope="module")
def manifest():
    m = Manifest()
    m.check()
    return m


@pytest.fixture(scope="module")
def with_the_pair(tmp_path_factory):
    """The shipped manifest with the reader's entry appended, over the
    shipped files: what a data-only addition of the entry looks like."""
    root = tmp_path_factory.mktemp("with_the_pair")
    doc = copy.deepcopy(Manifest().doc)
    doc["per_layer"].append(PAIR_ENTRY)
    (root / "BENCHMARK.json").write_text(json.dumps(doc), encoding="utf-8")
    for path in doc["paths"]:
        os.makedirs((root / path).parent, exist_ok=True)
        os.symlink(os.path.join(repo_root(), path), root / path)
    m = Manifest(str(root))
    m.check()
    return m


def test_the_manifest_holds_the_cell_and_its_configuration(manifest):
    cell = manifest.workload(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == manifest.workload(F64_CELL)["traffic"]
    assert cell["traffic"] == "deploy-128-unprobed"
    assert "float32" in cell["why"] and len(cell["why"]) <= 200
    e2e = [m["name"] for m in manifest.metrics_of("end_to_end", CELL)]
    assert sorted(e2e) == ["placements_per_s", "setup_s"]
    (entry,) = [c for c in manifest.doc["configs"] if c["name"] == CONFIG]
    assert entry["file"] == CONFIG_FILE and entry["reduced"] == []
    assert "float32 on the chip" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # no other configuration runs this file
    assert [c["name"] for c in manifest.doc["configs"]
            if c["file"] == CONFIG_FILE] == [CONFIG]


@pytest.mark.parametrize("base", F64_ELEVEN + ["chain_kernel_roofline_pct"])
def test_the_cell_reports_this_layer_metric(manifest, base):
    listed = {m["name"]: m for m in manifest.metrics_of("per_layer", CELL)}
    m = listed[base + ".deploy"]
    assert m["moves"] == "placements_per_s" and CELL in m["workloads"]
    if base != "chain_kernel_roofline_pct":
        assert F64_CELL in m["workloads"]


def test_the_configuration_states_float32_and_cuts_nothing(manifest):
    cfg = manifest.config(CONFIG)
    assert cfg == manifest.config("binpack-10k")  # the file, by either way
    assert cfg["name"] == "binpack-10k" and not cfg.get("jax_enable_x64")
    assert "float32 on the chip" in cfg["guarantees"]["precision"]
    assert "bit-identical" in cfg["guarantees"]["placement"]
    assert cfg["reduced"] == []
    assert cfg["fleet"]["nodes"] == 10000
    assert cfg["fleet"]["resident_allocs"] == 100000
    f64 = manifest.config("binpack-10k-f64")
    assert f64["fleet"] == cfg["fleet"] and f64["job"] == cfg["job"]


def _obs(counters, column_bytes=4, trace=None):
    return {
        "window_s": 51.0, "evals": 8800, "attempted": 8800, "refused": 0,
        "counters": counters, "samples": {}, "trace": trace,
        "device_kind": "TPU v5 lite", "arena_rows": 16384,
        "picks_per_eval": 10.0, "column_bytes": column_bytes,
    }


@pytest.mark.parametrize(
    "counters",
    [{}, {"batch_worker.walk_pulls": 9.4e6, "batch_worker.walk_picks": 88000.0},
     {PAIR_COUNTER: 0.0, "batch_worker.walk_picks": 0.0}],
    ids=["empty", "parent", "no_pick_in_the_window"],
)
def test_the_pair_reader_is_silent_without_its_source(manifest, counters):
    assert manifest.layer_reader(PAIR)(_obs(counters)) is None


@pytest.mark.parametrize(
    "decided, picks, expected",
    [(0.0, 88000.0, 0.0), (2.0, 88000.0, 22.727272727), (88.0, 88000.0, 1000.0)],
    ids=["float64_or_none_met", "two_a_window", "one_in_a_thousand"],
)
def test_the_pair_reader_reads_the_counters(manifest, decided, picks, expected):
    counters = {PAIR_COUNTER: decided, "batch_worker.walk_picks": picks,
                "batch_worker.walk_pulls": 107.0 * picks}
    assert manifest.layer_reader(PAIR)(_obs(counters)) == pytest.approx(expected)


def test_the_pair_entry_is_a_data_only_addition(manifest, with_the_pair):
    assert PAIR not in {m["name"] for m in manifest.doc["per_layer"]}
    assert PAIR_ENTRY in with_the_pair.metrics_of("per_layer", CELL)
    assert PAIR_ENTRY not in with_the_pair.metrics_of("per_layer", F64_CELL)


def test_the_roofline_counts_the_columns_at_four_bytes(manifest):
    """The vectorised kernel reads the whole arena a pick on this fleet
    too: 16,384 rows x 6 columns x 4 bytes = 393 KB a pick-step, 3.93 MB
    an evaluation of 10, half the float64 cell's."""
    assert peaks.chain_kernel_bytes(1, 10, 16384, 4) == 3932280
    assert peaks.chain_kernel_bytes(1, 10, 16384, 8) == 2 * 3932280
    trace = {
        "modules": {"jit_chained_plan_picks_cols(123)": (32, 0.640)},
        "launch_evals": 256.0,
    }
    read = manifest.layer_reader("chain_kernel_roofline_pct.deploy")
    f32, f64 = read(_obs({}, 4, trace)), read(_obs({}, 8, trace))
    # 2.5 ms of kernel an evaluation: 4.8 us of the chip's bandwidth
    assert f32 == pytest.approx(100.0 * 3932280 / 819.0e9 / 2.5e-3)
    assert f64 == pytest.approx(2 * f32) and 0.05 < f32 < 1.0


def test_a_rehearsal_of_the_cell_at_a_cut_fleet_runs_float32_and_is_correct(
    with_the_pair,
):
    """The command's own entry in a child (the session holds x64 on):
    1,000 nodes, the closed loop of 128, a 3 s window.  The run states
    x64 off, every evaluation goes through the chained kernel, and the
    plain float64 reference finds every placement its own."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo_root()
    done = subprocess.run(
        [sys.executable, os.path.join(with_the_pair.root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 35), "--seconds", "3",
         "--trace", "1", "--allow-cpu", "--rehearsal-scale", "0.1"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=with_the_pair.root,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    assert " x64=False " in done.stderr
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "correct" not in line
    assert line["rehearsal_correct"] is True, line["rehearsal_checks"]
    assert line["rehearsal_failed"] == 0 and line["rehearsal_attempted"] > 0
    checks = line["rehearsal_checks"]
    assert checks["mismatched_placements"]["value"] == 0
    assert checks["lost_or_duplicate"]["value"] == 0
    metrics = line["rehearsal_metrics"]
    assert metrics["host_path_share_pct.deploy"]["value"] == 0.0
    # the counter is there at float32, and rare: under one pick in a
    # thousand even on a fleet this small
    assert 0.0 <= metrics[PAIR]["value"] < 1000.0
    # the CPU has no device plane: shares of the device stay silent
    assert "chain_kernel_roofline_pct.deploy" not in metrics
    result = {k[len("rehearsal_"):]: v for k, v in line.items()
              if k.startswith("rehearsal_")}
    check_last_line(json.dumps(result), with_the_pair, CELL, trace=True)
