"""The flight recorder's self-time readers (``benchmark/layers/_spans.py``
and the eleven files on it): silent without a source, and the right
number on a ``/v1/metrics`` delta as ``run.delta`` builds it."""
import pytest

from benchmark import run
from benchmark.manifest import Manifest

# two /v1/metrics reads at a window's edges, as metrics_snapshot keeps
# them: of the acks between the two, 500 were the fold's to take and
# 498 of them folded
BEFORE = {
    "counters": {
        "trace.folded": 400.0, "trace.unfolded": 0.0,
        "batch_worker.device_unfed_ms": 1000.0,
    },
    "samples": {
        "trace.life": {"count": 400, "sum_ms": 400000.0},
        "trace.self.ingress": {"count": 400, "sum_ms": 80.0},
        "trace.self.broker": {"count": 400, "sum_ms": 300000.0},
        "trace.self.pipeline_wait": {"count": 400, "sum_ms": 96000.0},
        "trace.self.bw_host": {"count": 400, "sum_ms": 1200.0},
        "trace.self.replay_pool": {"count": 400, "sum_ms": 1000.0},
        "trace.self.plan_handoff": {"count": 400, "sum_ms": 600.0},
        "trace.self.plan_applier": {"count": 400, "sum_ms": 720.0},
        "trace.self.store": {"count": 400, "sum_ms": 400.0},
        "trace.cpu.bw_host": {"count": 400, "sum_ms": 700.0},
        "trace.cpu.replay_pool": {"count": 400, "sum_ms": 600.0},
        "trace.cpu.plan_applier": {"count": 400, "sum_ms": 500.0},
        "trace.cpu.store": {"count": 400, "sum_ms": 300.0},
        "trace.cpu_wall": {"count": 400, "sum_ms": 3000.0},
    },
}
AFTER = {
    "counters": {
        "trace.folded": 898.0, "trace.unfolded": 2.0,
        "batch_worker.device_unfed_ms": 3500.0,
    },
    "samples": {
        "trace.life": {"count": 898, "sum_ms": 975000.0},
        "trace.self.ingress": {"count": 898, "sum_ms": 180.0},
        "trace.self.broker": {"count": 898, "sum_ms": 800000.0},
        "trace.self.pipeline_wait": {"count": 898, "sum_ms": 166000.0},
        "trace.self.bw_host": {"count": 898, "sum_ms": 2700.0},
        "trace.self.replay_pool": {"count": 898, "sum_ms": 2250.0},
        "trace.self.plan_handoff": {"count": 898, "sum_ms": 1350.0},
        "trace.self.plan_applier": {"count": 898, "sum_ms": 1620.0},
        "trace.self.store": {"count": 898, "sum_ms": 900.0},
        "trace.cpu.bw_host": {"count": 898, "sum_ms": 1600.0},
        "trace.cpu.replay_pool": {"count": 898, "sum_ms": 1400.0},
        "trace.cpu.plan_applier": {"count": 898, "sum_ms": 1100.0},
        "trace.cpu.store": {"count": 898, "sum_ms": 650.0},
        "trace.cpu_wall": {"count": 898, "sum_ms": 7000.0},
    },
}
# 498 traces folded in a 10 s window (the program folds a share of its
# acks: the generator saw 4,000 completions); cpu 900+800+600+350 of
# 4000 ms wall
EXPECTED = {
    "ingress_register_ms_per_eval.deploy": 100.0 / 498,
    "broker_wait_ms_per_eval.deploy": 500000.0 / 498,
    "pipeline_wait_ms_per_eval.deploy": 70000.0 / 498,
    "bw_host_self_ms_per_eval.deploy": 1500.0 / 498,
    "replay_speculate_ms_per_eval.deploy": 1250.0 / 498,
    "plan_handoff_ms_per_eval.deploy": 750.0 / 498,
    "plan_apply_self_ms_per_eval.deploy": 900.0 / 498,
    "store_commit_ms_per_eval.deploy": 500.0 / 498,
    "host_offcpu_share_pct.deploy": 100.0 * (1 - 2650.0 / 4000.0),
    "trace_unfolded_pct.deploy": 100.0 * 2 / 500,
    "device_unfed_share_pct.deploy": 100.0 * 2500.0 / 10000.0,
}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def _obs(window):
    return {
        "window_s": 10.0, "evals": 4000, "attempted": 4000, "refused": 0,
        "counters": window["counters"], "samples": window["samples"],
    }


def test_the_manifest_lists_the_eleven_on_the_deploy_cell(manifest):
    listed = {m["name"]: m for m in manifest.doc["per_layer"]}
    for name in EXPECTED:
        m = listed[name]
        assert m["workloads"] == ["spread-5k-f64.deploy"]
        assert m["moves"] == "placements_per_s" and m["better"] == "lower"
        assert m["unit"] == ("%" if name.endswith("_pct.deploy") else "ms")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_span_reader_is_silent_without_a_source(manifest, name):
    read = manifest.layer_reader(name)
    assert read(_obs({"counters": {}, "samples": {}})) is None
    # the parent's program, or NOMAD_TPU_TRACE=0: zero-registered or
    # absent series hold nothing to read
    zero = {
        "counters": {"trace.folded": 0.0, "trace.unfolded": 0.0},
        "samples": {
            k: {"count": 0, "sum_ms": 0.0} for k in AFTER["samples"]
        },
    }
    assert read(_obs(zero)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_span_reader_reads_a_recorded_metrics_delta(manifest, name):
    read = manifest.layer_reader(name)
    value = read(_obs(run.delta(AFTER, BEFORE)))
    assert value == pytest.approx(EXPECTED[name])


def test_the_eight_self_times_sum_to_the_life_over_the_same_evals(manifest):
    obs = _obs(run.delta(AFTER, BEFORE))
    split = sum(
        manifest.layer_reader(name)(obs)
        for name in EXPECTED if name.endswith("_ms_per_eval.deploy")
    )
    life = obs["samples"]["trace.life"]
    life = life["sum_ms"] / life["count"]
    assert split == pytest.approx(life)
