"""``spread-5k.deploy``: the spread-and-affinity fleet at float32, the
dtype the chip runs natively.  What the manifest says of it (by
containment: a later cell or entry breaks nothing here), its
configuration beside the float64 one, the reader of the picks the pair
score's ``lo`` half decided shown on this cell's log (no entry lists it
yet: the entry is the next ``benchmark`` issue's, as data), the cell
rehearsed on the CPU at a cut fleet in a process of its own, so that
``jax_enable_x64`` is off there as the configuration states, and its
control: the plain reference in bfloat16 put in the program's place.
"""
import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import correct, peaks
from benchmark.control import served_by
from benchmark.manifest import Manifest, check_last_line, repo_root

CELL = "spread-5k.deploy"
F64_CELL = "spread-5k-f64.deploy"
F32_SIBLING = "binpack-10k.deploy"
CONFIG = "spread-5k-f32"
CONFIG_FILE = "benchmark/configs/spread-5k.json"
ELEVEN = [
    "shed_share_pct", "bw_assemble_ms_per_eval", "bw_replay_ms_per_eval",
    "evals_per_launch", "host_path_share_pct", "plan_apply_ms_per_eval",
    "chain_kernel_ms_per_eval", "device_idle_share_pct",
    "compiles_in_window", "gc_pause_share_pct", "longest_gap_ms",
]
PAIR = "pair_decided_per_mpick.deploy"
PAIR_ENTRY = {
    "name": PAIR, "unit": "picks/Mpick", "better": "lower",
    "source": "program_counter", "layer": "kernels",
    "moves": "placements_per_s", "workloads": [F32_SIBLING, CELL],
}
PAIR_COUNTER = "batch_worker.pair_decided_picks"
# the rehearsal's cut: 500 nodes.  A node of this fleet has on average
# (8,000 + 16,000 + 32,000) / 3 - 100 reserved - 10 resident allocations
# of 267 = 15,900 cpu free, 53 of the job's 300-cpu placements (memory
# leaves room for 83), so the cut fleet holds about 26,000 placements,
# 4,400 jobs of six; the run sends the ramp's probes (count 1 each),
# 400 warm-up jobs, 128 in flight and a 3 s window's — under 1,500
# six-placement jobs on any host that has run it (ROADMAP M12)
REHEARSAL_SCALE = 0.1
REHEARSAL_HOLDS_JOBS = 4400


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
    # the float64 spread cell's traffic, probe ramp and all: at 128 in
    # flight a cold spread shape sends whole gulps down the host path,
    # 24 s an evaluation on this fleet, where a ramp's pinned probes
    # meet it one at a time and cheaply (PERF.md section 6, PR 38)
    assert cell["traffic"] == manifest.workload(F64_CELL)["traffic"]
    assert cell["traffic"] == "deploy-128"
    assert manifest.traffic(cell["traffic"])["probe_ramp"]
    assert "float32" in cell["why"] and len(cell["why"]) <= 200
    assert "probe ramp" in cell["why"] and "argmax" in cell["why"]
    e2e = [m["name"] for m in manifest.metrics_of("end_to_end", CELL)]
    assert sorted(e2e) == ["placements_per_s", "setup_s"]
    (entry,) = [c for c in manifest.doc["configs"] if c["name"] == CONFIG]
    assert entry["file"] == CONFIG_FILE and entry["reduced"] == []
    (f64,) = [c for c in manifest.doc["configs"] if c["name"] == "spread-5k-f64"]
    assert entry["source"] == f64["source"] + "; float32 on the chip"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # no other configuration runs this file, and one cell runs the entry
    assert [c["name"] for c in manifest.doc["configs"]
            if c["file"] == CONFIG_FILE] == [CONFIG]
    assert [w["name"] for w in manifest.doc["workloads"]
            if w["config"] == CONFIG] == [CELL]


@pytest.mark.parametrize("base", ELEVEN + ["chain_kernel_roofline_pct"])
def test_the_cell_reports_this_layer_metric(manifest, base):
    listed = {m["name"]: m for m in manifest.metrics_of("per_layer", CELL)}
    m = listed[base + ".deploy"]
    assert m["moves"] == "placements_per_s"
    assert CELL in m["workloads"] and F64_CELL in m["workloads"]
    assert F32_SIBLING in m["workloads"]


def test_the_cell_is_listed_under_nothing_else(manifest):
    """The span metrics and the counters of PR 27 and PR 29 stay the
    float64 spread cell's until a ``benchmark`` issue loosens their
    pins (ROADMAP M10 (a))."""
    listed = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert listed == {b + ".deploy" for b in ELEVEN} | {
        "chain_kernel_roofline_pct.deploy"
    }


def test_the_configuration_states_float32_and_cuts_nothing(manifest):
    cfg = manifest.config(CONFIG)
    assert cfg == manifest.config("spread-5k")  # the file, by either way
    assert cfg["name"] == "spread-5k" and not cfg.get("jax_enable_x64")
    assert "float32 on the chip" in cfg["guarantees"]["precision"]
    assert "bit-identical" in cfg["guarantees"]["placement"]
    assert cfg["reduced"] == []
    assert cfg["fleet"]["nodes"] == 5000
    assert cfg["fleet"]["resident_allocs"] == 50000
    f64 = manifest.config("spread-5k-f64")
    assert f64["jax_enable_x64"] is True
    assert f64["fleet"] == cfg["fleet"] and f64["job"] == cfg["job"]
    # the job that makes the cell: a percent spread whose desired counts
    # are 3, 1.8 and 1.2 of six, and an affinity
    (spread,) = cfg["job"]["spreads"]
    assert [(t["value"], t["percent"]) for t in spread["targets"]] == [
        ("dc1", 50), ("dc2", 30),
    ]
    assert cfg["job"]["task_groups"][0]["count"] == 6
    assert [a["rtarget"] for a in cfg["job"]["affinities"]] == ["dc2"]


def _obs(counters, column_bytes=4, trace=None):
    return {
        "window_s": 51.0, "evals": 11000, "attempted": 11000, "refused": 0,
        "counters": counters, "samples": {}, "trace": trace,
        "device_kind": "TPU v5 lite", "arena_rows": 8192,
        "picks_per_eval": 6.0, "column_bytes": column_bytes,
    }


def test_the_pair_reader_reads_this_cells_counters(manifest):
    """``pair_decided_per_mpick.deploy`` reads what a run of this cell
    keeps under ``--out`` (``window.counters`` of its log): the
    whole-fleet walk's picks beside the picks ``lo`` decided.  The
    rehearsal below reads a real run's."""
    counters = {
        PAIR_COUNTER: 33.0, "batch_worker.walk_picks": 66000.0,
        "batch_worker.walk_pulls": 66000.0 * 5000,
    }
    read = manifest.layer_reader(PAIR)
    assert read(_obs(counters)) == pytest.approx(500.0)
    assert manifest.layer_reader("walk_pulls_per_pick.deploy")(
        _obs(counters)
    ) == pytest.approx(5000.0)
    # the parent's log has no such counter: the reader says nothing
    del counters[PAIR_COUNTER]
    assert read(_obs(counters)) is None


def test_the_pair_entry_is_a_data_only_addition(manifest, with_the_pair):
    assert PAIR not in {m["name"] for m in manifest.doc["per_layer"]}
    assert PAIR_ENTRY in with_the_pair.metrics_of("per_layer", CELL)
    assert PAIR_ENTRY not in with_the_pair.metrics_of("per_layer", F64_CELL)


def test_the_roofline_counts_the_columns_at_four_bytes(manifest):
    """Six picks an evaluation, each reading six columns of the 8,192
    arena rows and writing three entries: 1.18 MB at 4 bytes an entry,
    half the float64 cell's."""
    assert peaks.chain_kernel_bytes(1, 6, 8192, 4) == 6 * (6 * 8192 + 3) * 4
    assert peaks.chain_kernel_bytes(1, 6, 8192, 4) == 1179720
    assert peaks.chain_kernel_bytes(1, 6, 8192, 8) == 2 * 1179720
    trace = {
        "modules": {"jit_chained_plan_picks_cols(123)": (32, 0.512)},
        "launch_evals": 256.0,
    }
    read = manifest.layer_reader("chain_kernel_roofline_pct.deploy")
    f32, f64 = read(_obs({}, 4, trace)), read(_obs({}, 8, trace))
    # 2.0 ms of kernel an evaluation: 1.44 us of the chip's bandwidth
    assert f32 == pytest.approx(100.0 * 1179720 / 819.0e9 / 2.0e-3)
    assert f64 == pytest.approx(2 * f32) and 0.01 < f32 < 1.0


def test_a_rehearsal_of_the_cell_at_a_cut_fleet_runs_float32_and_is_correct(
    with_the_pair,
):
    """The command's own entry in a child (the session holds x64 on):
    500 nodes, the probe ramp, the closed loop of 128, a 3 s window.  The
    run states x64 off, every evaluation goes through the chained
    kernel with the whole fleet scored a pick, the plain float64
    reference finds every placement its own, and the picks ``lo``
    decided are counted."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo_root()
    done = subprocess.run(
        [sys.executable, os.path.join(with_the_pair.root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 38), "--seconds", "3",
         "--trace", "1", "--allow-cpu",
         "--rehearsal-scale", str(REHEARSAL_SCALE)],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=with_the_pair.root,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    assert " x64=False " in done.stderr
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "correct" not in line
    # the cut fleet does not fill: it holds over twice what was sent,
    # were every job compared (the ramp's count-1 probes among them) a
    # job of six
    sent = line["rehearsal_checks"]["jobs_compared"]["value"]
    assert 400 < sent < REHEARSAL_HOLDS_JOBS / 2, sent
    assert line["rehearsal_correct"] is True, line["rehearsal_checks"]
    assert line["rehearsal_failed"] == 0 and line["rehearsal_attempted"] > 0
    checks = line["rehearsal_checks"]
    assert checks["mismatched_placements"]["value"] == 0
    assert checks["lost_or_duplicate"]["value"] == 0
    metrics = line["rehearsal_metrics"]
    assert metrics["host_path_share_pct.deploy"]["value"] == 0.0
    # counted at float32 on the whole-fleet walk: 500 candidates a
    # pick, so `lo` decides some, and still far under one in a hundred
    assert 0.0 <= metrics[PAIR]["value"] < 10000.0
    # the CPU has no device plane: shares of the device stay silent
    assert "chain_kernel_roofline_pct.deploy" not in metrics
    result = {k[len("rehearsal_"):]: v for k, v in line.items()
              if k.startswith("rehearsal_")}
    check_last_line(json.dumps(result), with_the_pair, CELL, trace=True)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_the_control_in_bfloat16_comes_out_not_correct_on_this_configuration(
    manifest, seed
):
    """The cell's limits are exact, so its control is the plain
    reference in the nearest precision below float32 put in the
    program's place: not correct, by mismatched placements with gaps
    that are no tie."""
    config = manifest.config(CONFIG)
    config["fleet"]["nodes"] = 1000
    config["fleet"]["resident_allocs"] = 10000
    traffic = {"loop": "closed", "in_flight": 1}
    world, served = served_by(config, traffic, seed, 60, "bfloat16")
    numbers = correct.compare(world, seed, served)
    numbers.update(unfinished_acked=0, readback_mismatches=0)
    assert numbers["mismatched_placements"] >= 2
    assert numbers["widest_score_gap"] > 3e-3
    assert not correct.verdict(numbers, 60)
