"""The harness end to end on the CPU at a tiny size: a sound run is
correct, a run with the timed path broken underneath is not, and no
line a rehearsal prints can be read as a chip result."""
import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark.manifest import Manifest, check_last_line, repo_root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The shipped cell cut to a test's size, beside a binpack cell and
    an open-loop cell with a latency metric and a reader of their own:
    new files, new entries, no edit to a file that is there."""
    root = tmp_path_factory.mktemp("tiny")
    src = repo_root()
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(src, "benchmark", sub), root / "benchmark" / sub)
    doc = json.load(open(os.path.join(src, "BENCHMARK.json"), encoding="utf-8"))
    for name, nodes in (("binpack-10k", 300), ("spread-5k-f64", 120)):
        cfg = json.load(
            open(os.path.join(src, f"benchmark/configs/{name}.json"), encoding="utf-8")
        )
        cfg["fleet"]["nodes"] = nodes
        cfg["fleet"]["resident_allocs"] = nodes * 10
        (root / "benchmark" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (root / "benchmark" / "traffic" / "deploy-8.json").write_text(json.dumps(
        {"loop": "closed", "in_flight": 8, "warmup_evals": 20, "senders": 8,
         "probe_ramp": [[1, 2], [3, 6]]}))
    (root / "benchmark" / "traffic" / "paced-25.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 25, "warmup_s": 1, "senders": 4,
         "probe_ramp": [[2, 4]]}))
    (root / "benchmark" / "layers" / "eval_p95_ms.paced.py").write_text(
        "from benchmark import metrics\n\n\n"
        "def read(obs):\n"
        "    lat = obs['latency_ms']\n"
        "    return metrics.percentile(lat, 95.0) if lat else None\n"
    )
    doc["configs"].append(
        {"name": "binpack-10k", "source": "test", "reduced": [],
         "file": "benchmark/configs/binpack-10k.json", "why": "test"}
    )
    doc["workloads"] = [
        dict(doc["workloads"][0], traffic="deploy-8"),
        {"name": "binpack-10k.deploy", "config": "binpack-10k",
         "traffic": "deploy-8", "chips": 1, "why": "test"},
        {"name": "spread-5k-f64.paced", "config": "spread-5k-f64",
         "traffic": "paced-25", "chips": 1, "why": "test"},
    ]
    deploy = ["spread-5k-f64.deploy", "binpack-10k.deploy"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(deploy)
    doc["end_to_end"].append(
        {"name": "eval_p50_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["spread-5k-f64.paced"]}
    )
    doc["per_layer"].append(
        {"name": "eval_p95_ms.paced", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "load generator",
         "moves": "eval_p50_ms", "workloads": ["spread-5k-f64.paced"]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    manifest = Manifest(str(root))
    manifest.check()
    return manifest


def _args(workload, seed, seconds=2.0, trace=0):
    return argparse.Namespace(
        workload=workload, seed=seed, seconds=seconds, trace=trace,
        allow_cpu=True, rehearsal_scale=1.0, out="",
    )


def test_a_sound_closed_loop_run_is_correct(tiny):
    code, result = bench_run.run(_args("binpack-10k.deploy", 2**31 + 7), tiny)
    assert code == 0
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["placements_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    checks = result["checks"]
    assert list(result)[-1] == "checks"
    assert checks["mismatched_placements"] == {"value": 0, "limit": 0, "sense": "<="}
    assert checks["widest_score_gap"]["value"] == 0.0
    assert checks["jobs_compared"]["value"] >= checks["jobs_compared"]["limit"] > 0
    check_last_line(json.dumps(result), tiny, "binpack-10k.deploy", trace=False)


def test_a_sound_traced_run_reads_the_whole_window_and_stays_silent_on_the_device(tiny):
    code, result = bench_run.run(
        _args("spread-5k-f64.deploy", 4321, seconds=3.0, trace=1), tiny
    )
    assert code == 0 and result["correct"] is True, result["checks"]
    names = set(result["metrics"])
    assert {"bw_replay_ms_per_eval.deploy", "plan_apply_ms_per_eval.deploy",
            "evals_per_launch.deploy", "gc_pause_share_pct.deploy"} <= names
    # the CPU has no device plane: shares of the device stay silent, never 0
    assert "chain_kernel_roofline_pct.deploy" not in names
    assert "device_idle_share_pct.deploy" not in names
    assert all(n.endswith(".deploy") for n in names)
    check_last_line(json.dumps(result), tiny, "spread-5k-f64.deploy", trace=True)


def test_an_open_loop_cell_is_added_as_data_and_times_from_the_due_instant(tiny):
    code, result = bench_run.run(
        _args("spread-5k-f64.paced", 12345, seconds=2.0, trace=1), tiny
    )
    assert code == 0 and result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"eval_p95_ms.paced"}
    assert 0 < result["metrics"]["eval_p95_ms.paced"]["value"] < 5000
    assert 40 <= result["attempted"] <= 50  # 25 a second for 2 seconds


def test_a_launch_shape_that_compiles_inside_the_window_is_no_measurement(
    tiny, monkeypatch
):
    real = bench_run.CompileClock.launch_shapes

    def one_more(self, t0=0.0, t1=float("inf")):
        found = real(self, t0, t1)
        if t1 != float("inf"):  # the window's own question
            found = found + [(t0 + 0.5, 1.2, bench_run.LAUNCH_JIT + ")")]
        return found

    monkeypatch.setattr(bench_run.CompileClock, "launch_shapes", one_more)
    code, result = bench_run.run(_args("binpack-10k.deploy", 5), tiny)
    assert code == 3 and result is None


def test_an_answer_altered_where_it_is_produced_comes_out_not_correct(tiny, monkeypatch):
    from nomad_tpu.state.store import StateStore

    real = StateStore.upsert_plan_results
    seen = {"plans": 0}

    def altered(self, result, *args, **kwargs):
        seen["plans"] += 1
        if seen["plans"] == 12:
            nodes = [n.id for n in self.iter_nodes()]
            for allocs in result.node_allocation.values():
                for alloc in allocs:
                    alloc.node_id = next(n for n in nodes if n != alloc.node_id)
                    break
                break
        return real(self, result, *args, **kwargs)

    monkeypatch.setattr(StateStore, "upsert_plan_results", altered)
    code, result = bench_run.run(_args("binpack-10k.deploy", 99), tiny)
    assert code == 0 and seen["plans"] > 12
    assert result["correct"] is False
    # the program goes on from a state its own mirror no longer matches,
    # so later answers differ too: at least the altered one
    assert result["checks"]["mismatched_placements"]["value"] >= 1


def test_a_rehearsal_line_cannot_be_read_as_a_chip_result(monkeypatch, capsys):
    canned = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
              "device": {"platform": "cpu"}, "checks": {}}
    monkeypatch.setattr(bench_run, "run", lambda args, manifest: (0, dict(canned)))
    rc = bench_run.main(["--workload", "spread-5k-f64.deploy", "--seed", "1",
                         "--seconds", "1", "--allow-cpu"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] is True
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key not in line and "rehearsal_" + key in line


def test_without_the_switch_no_tpu_is_a_failure(monkeypatch):
    from benchmark import system

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(system.NoChip):
        system.resolve_device(1, allow_cpu=False)


def test_rehearsal_scale_needs_the_switch():
    rc = bench_run.main(["--workload", "spread-5k-f64.deploy", "--seed", "1",
                         "--seconds", "1", "--rehearsal-scale", "0.1"])
    assert rc == 2


def test_without_the_program_the_command_fails_and_prints_no_result(tmp_path):
    src = repo_root()
    shutil.copy(os.path.join(src, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(
        os.path.join(src, "benchmark"), tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "spread-5k-f64.deploy",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
