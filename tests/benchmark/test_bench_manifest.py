"""BENCHMARK.json, the loader that finds files by name, and the schema
of a run's last line."""
import json
import os
import shutil

import pytest

from benchmark.manifest import (
    NAME_RE,
    UNIT_RE,
    Manifest,
    ManifestError,
    check_last_line,
    repo_root,
)


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_manifest_passes_its_own_schema(manifest):
    manifest.check()


def test_names_and_units_use_the_contract_characters(manifest):
    doc = manifest.doc
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in doc[section]:
            assert NAME_RE.match(entry["name"]), entry["name"]
    for w in doc["workloads"]:
        assert NAME_RE.match(w["config"]) and NAME_RE.match(w["traffic"])
    for section in ("end_to_end", "per_layer"):
        for m in doc[section]:
            assert UNIT_RE.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    assert not NAME_RE.match("has space") and not NAME_RE.match("a/b")
    assert not UNIT_RE.match("tokens per second") and UNIT_RE.match("placements/s")


def test_every_cell_is_one_chip_and_every_file_is_under_paths(manifest):
    for w in manifest.doc["workloads"]:
        assert w["chips"] == 1
    for c in manifest.doc["configs"]:
        assert c["file"].split("/")[0] in manifest.paths
        cfg = manifest.config(c["name"])
        for key in ("source", "reduced", "assumed", "guarantees", "fleet", "job"):
            assert key in cfg, (c["name"], key)
        assert cfg["reduced"] == c["reduced"]


def test_per_layer_metrics_are_reported_only_where_their_end_to_end_is(manifest):
    e2e = {m["name"]: m for m in manifest.doc["end_to_end"]}
    for m in manifest.doc["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved["workloads"], (m["name"], cell)
        suffix = m["name"].rsplit(".", 1)[1]
        assert all(cell.endswith("." + suffix) for cell in m["workloads"])


def test_offered_load_is_a_number_in_the_traffic_file(manifest):
    for w in manifest.doc["workloads"]:
        traffic = manifest.traffic(w["traffic"])
        if traffic["loop"] == "open":
            assert float(traffic["rate_per_s"]) > 0
        else:
            assert int(traffic["in_flight"]) > 0
        # set-up is traffic and nothing else: a ramp of closed loops
        for in_flight, evals in traffic.get("probe_ramp", ()):
            assert int(in_flight) > 0 and int(evals) > 0


def test_the_yardstick_reaches_into_no_private_part_of_the_program():
    """``system.py`` is the one module that imports the program; it and
    the harness use its public surface only, so a later PR that changes
    the worker's inside cannot break set-up."""
    import re

    root = os.path.join(repo_root(), "benchmark")
    for name in ("system.py", "run.py", "loadgen.py"):
        text = open(os.path.join(root, name), encoding="utf-8").read()
        for word in re.findall(r"\b(?:worker|server|store|TRACE|workers\[0\])\._[a-z]\w*", text):
            raise AssertionError((name, word))
    text = open(os.path.join(root, "system.py"), encoding="utf-8").read()
    assert "set_pause" not in text and "latency_budget" not in text


def test_a_configuration_that_no_cell_uses_yet_is_found_by_its_file(manifest):
    # the float32 configurations of the cells PERF.md keeps for later
    for name in ("binpack-10k", "spread-5k"):
        cfg = manifest.config(name)
        assert cfg["name"] == name and not cfg.get("jax_enable_x64")
    assert manifest.config("spread-5k-f64")["jax_enable_x64"] is True


def test_no_collector_tuning_anywhere_in_the_benchmark():
    root = os.path.join(repo_root(), "benchmark")
    banned = ("gc." + "freeze", "gc." + "disable", "gc." + "set_threshold")
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(dirpath, f), encoding="utf-8").read()
                for word in banned:
                    assert word not in text, (f, word)


def _grown_root(tmp_path):
    """A copy of the manifest's data with one configuration, one traffic
    mix, one per-layer metric and one cell ADDED: new files in a new
    directory, new entries, no edit to a file that was there."""
    root = tmp_path / "repo"
    root.mkdir()
    src = repo_root()
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(
            os.path.join(src, "benchmark", sub), root / "benchmark" / sub
        )
    doc = json.load(open(os.path.join(src, "BENCHMARK.json"), encoding="utf-8"))
    extra = root / "benchmark_more"
    for sub in ("configs", "traffic", "layers"):
        (extra / sub).mkdir(parents=True)
    cfg = json.load(
        open(os.path.join(src, "benchmark/configs/binpack-10k.json"), encoding="utf-8")
    )
    cfg["name"] = "binpack-1k"
    cfg["fleet"]["nodes"] = 1000
    cfg["reduced"] = ["nodes"]
    (extra / "configs" / "binpack-1k.json").write_text(json.dumps(cfg))
    (extra / "traffic" / "deploy-8.json").write_text(
        json.dumps({"loop": "closed", "in_flight": 8, "warmup_evals": 10,
                    "senders": 8, "mix": [{"share": 3, "count": 2},
                                          {"share": 1, "count": 7}]})
    )
    (extra / "layers" / "launches_per_s.deploy.py").write_text(
        "def read(obs):\n"
        "    n = obs['samples'].get('batch_worker.launch', {}).get('count')\n"
        "    return None if not n else n / obs['window_s']\n"
    )
    doc["paths"].append("benchmark_more")
    doc["configs"].append(
        {"name": "binpack-1k", "source": "test", "reduced": ["nodes"],
         "file": "benchmark_more/configs/binpack-1k.json", "why": "test"}
    )
    doc["workloads"].append(
        {"name": "binpack-1k.deploy", "config": "binpack-1k",
         "traffic": "deploy-8", "chips": 1, "why": "test"}
    )
    for m in doc["end_to_end"]:
        if m["name"] == "placements_per_s":
            m["workloads"].append("binpack-1k.deploy")
    doc["per_layer"].append(
        {"name": "launches_per_s.deploy", "unit": "launches/s",
         "better": "higher", "source": "program_counter",
         "layer": "batch worker batching", "moves": "placements_per_s",
         "workloads": ["binpack-1k.deploy"]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(root)


def test_a_cell_a_config_a_mix_and_a_layer_metric_are_added_as_files(tmp_path):
    grown = Manifest(_grown_root(tmp_path))
    grown.check()
    assert grown.config("binpack-1k")["fleet"]["nodes"] == 1000
    assert grown.traffic("deploy-8")["in_flight"] == 8
    names = [m["name"] for m in grown.metrics_of("per_layer", "binpack-1k.deploy")]
    assert names == ["launches_per_s.deploy"]
    read = grown.layer_reader("launches_per_s.deploy")
    obs = {"samples": {"batch_worker.launch": {"count": 30}}, "window_s": 10.0}
    assert read(obs) == 3.0
    assert read({"samples": {}, "window_s": 10.0}) is None
    e2e = [m["name"] for m in grown.metrics_of("end_to_end", "binpack-1k.deploy")]
    assert sorted(e2e) == ["placements_per_s", "setup_s"]


def test_every_shipped_layer_reader_loads_and_is_silent_without_a_source(manifest):
    empty = {
        "window_s": 10.0, "evals": 0, "attempted": 0, "refused": 0,
        "latency_ms": [], "longest_gap_s": None,
        "counters": {}, "samples": {}, "gen": {"late_ms": []},
        "gc": {"pause_s": 0.0}, "compiles": 0, "trace": None,
        "device_kind": "TPU v5 lite", "arena_rows": 16384, "picks_per_eval": 10,
        "column_bytes": 8,
    }
    silent = 0
    for m in manifest.doc["per_layer"]:
        value = manifest.layer_reader(m["name"])(dict(empty))
        base = m["name"].rsplit(".", 1)[0]
        if base in ("gc_pause_share_pct", "compiles_in_window"):
            assert value == 0.0
        else:
            assert value is None, m["name"]
            silent += 1
    assert silent >= 8


def test_unknown_names_are_refused(manifest):
    with pytest.raises(ManifestError):
        manifest.workload("no-such.cell")
    with pytest.raises(ManifestError):
        manifest.layer_reader("no_such_metric.deploy")


def _line(**over):
    base = {
        "correct": True, "attempted": 400, "failed": 0,
        "metrics": {
            "placements_per_s": {"value": 912.25, "unit": "placements/s"},
            "setup_s": {"value": 31.5, "unit": "s"},
        },
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 123},
    }
    base.update(over)
    return json.dumps(base)


def test_last_line_schema_end_to_end(manifest):
    obj = check_last_line(_line(), manifest, "spread-5k-f64.deploy", trace=False)
    assert obj["metrics"]["setup_s"]["unit"] == "s"
    with pytest.raises(ManifestError):
        check_last_line(_line(metrics={}), manifest, "spread-5k-f64.deploy", False)
    bad = json.loads(_line())
    del bad["device"]
    with pytest.raises(ManifestError):
        check_last_line(json.dumps(bad), manifest, "spread-5k-f64.deploy", False)
    with pytest.raises(ManifestError):  # a metric that is not this cell's
        check_last_line(
            _line(metrics={"eval_p50_ms": {"value": 1.0, "unit": "ms"}}),
            manifest, "spread-5k-f64.deploy", False,
        )


def test_last_line_schema_traced(manifest):
    traced = json.loads(_line(metrics={
        "evals_per_launch.deploy": {"value": 7.8, "unit": "evals"}}))
    with pytest.raises(ManifestError):  # busy_s / window_s missing
        check_last_line(json.dumps(traced), manifest, "spread-5k-f64.deploy", True)
    traced["device"].update(busy_s=1.5, window_s=10.0)
    check_last_line(json.dumps(traced), manifest, "spread-5k-f64.deploy", True)
