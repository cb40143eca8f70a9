"""The plain reference against the program's sequential scheduler at a
small size, and its control: the same reference in a lower precision,
put in the program's place.  In bfloat16 it comes out as not correct; in
IEEE float32 it places as float64 does (what moves placements on the
chip is the TPU's float32 ``10^x``, not a rounding: PERF.md section 2),
so the float32 control of the float64 cell is the program itself with
``jax_enable_x64`` off, read on the chip."""
import pytest

from benchmark import correct
from benchmark.manifest import Manifest
from benchmark.reference import JobSpec, RefCluster, visit_limit
from benchmark.stream import JobStream, ProbeStream
from benchmark.world import make_world

SEEDS = (3, 1000000007, 2**31 + 11)


def _small(config_name, nodes, allocs):
    config = Manifest().config(config_name)
    config["fleet"]["nodes"] = nodes
    config["fleet"]["resident_allocs"] = allocs
    return config


def _oracle_answers(config, seed, n_jobs, probes=0):
    """The program's sequential scheduler (no batch pipeline, no JAX)
    over the same world and stream; ``probes`` pinned shape probes
    come first."""
    from benchmark import system
    from nomad_tpu.api.codec import job_from_dict
    from nomad_tpu.server import Server

    world = make_world(config, seed)
    stream = JobStream(config, {"loop": "closed", "in_flight": 1}, seed)
    server = Server(num_schedulers=1, heartbeat_ttl=1e9, seed=seed,
                    batch_pipeline=False)
    system.load_world(server.store, world)
    server.start()
    try:
        pinned = ProbeStream(
            stream, [system.node_name(3 * k + 1) for k in range(max(1, probes))]
        )
        payloads = [pinned.payload(k) for k in range(probes)] + [
            stream.payload(i) for i in range(n_jobs)
        ]
        for p in payloads:
            server.register_job(job_from_dict(p))
            assert server.drain_to_idle(timeout=120)
        served = [
            system.job_answers(server.store, p["id"]) + (p,) for p in payloads
        ]
    finally:
        server.stop()
    return world, [(idx, p, placed) for idx, placed, p in served]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize(
    "config_name,nodes,allocs,jobs",
    [("binpack-10k", 400, 4000, 12), ("spread-5k", 150, 1500, 6)],
)
def test_reference_agrees_with_the_sequential_scheduler(
    config_name, nodes, allocs, jobs, seed
):
    config = _small(config_name, nodes, allocs)
    world, served = _oracle_answers(config, seed, jobs)
    numbers = correct.compare(world, seed, served)
    assert numbers["jobs_compared"] == jobs
    assert numbers["mismatched_placements"] == 0, numbers["worst"]
    assert numbers["widest_score_gap"] == 0.0
    assert numbers["lost_or_duplicate"] == 0


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config_name", ["binpack-10k", "spread-5k"])
def test_reference_agrees_on_pinned_shape_probes(config_name, seed):
    config = _small(config_name, 150, 1500)
    world, served = _oracle_answers(config, seed, 3, probes=4)
    for k in range(4):  # a pinned probe lands on its node and nowhere else
        assert list(served[k][2].values()) == [world.node_id(3 * k + 1)]
    numbers = correct.compare(world, seed, served)
    assert numbers["jobs_compared"] == 7
    assert numbers["mismatched_placements"] == 0, numbers["worst"]
    assert numbers["lost_or_duplicate"] == 0
    # served on another node than the pinned one: no candidate at all
    index, payload, placed = served[1]
    served[1] = (index, payload, {name: world.node_id(0) for name in placed})
    assert correct.compare(world, seed, served)["widest_score_gap"] == 1.0


def _served_by(config, seed, n_jobs, precision):
    from benchmark.control import served_by

    return served_by(config, {"loop": "closed", "in_flight": 1}, seed, n_jobs, precision)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config_name", ["binpack-10k", "spread-5k-f64"])
def test_control_in_bfloat16_comes_out_not_correct(config_name, seed):
    config = _small(config_name, 1000, 10000)
    jobs = 300 if config_name == "binpack-10k" else 60
    world, served = _served_by(config, seed, jobs, "bfloat16")
    numbers = correct.compare(world, seed, served)
    numbers.update(unfinished_acked=0, readback_mismatches=0)
    # the control's widest gap is no tie: orders of magnitude over 1e-7
    assert numbers["mismatched_placements"] >= 2
    assert numbers["widest_score_gap"] > 3e-3
    assert not correct.verdict(numbers, jobs)
    # and the reference put in the program's place reads 0
    world, served = _served_by(config, seed, jobs, "float64")
    numbers = correct.compare(world, seed, served)
    numbers.update(unfinished_acked=0, readback_mismatches=0)
    assert numbers["mismatched_placements"] == 0 and correct.verdict(numbers, jobs)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_in_ieee_float32_places_as_float64_does(seed):
    """Rounding every score term through float32 moves no placement at
    this size (nor over 4,200 jobs at the cell's own size on three
    seeds, PERF.md): the comparison cannot tell an ideal float32 from
    float64, because their answers are the same."""
    config = _small("spread-5k-f64", 1000, 10000)
    world, served = _served_by(config, seed, 60, "float32")
    numbers = correct.compare(world, seed, served)
    assert numbers["mismatched_placements"] == 0


def test_one_altered_answer_reads_one_not_a_cascade():
    config = _small("binpack-10k", 1000, 10000)
    world, served = _served_by(config, 5, 30, "float64")
    index, payload, placed = served[10]
    name = sorted(placed)[0]
    other = next(
        world.node_id(i) for i in range(world.n_nodes)
        if world.node_id(i) not in placed.values()
    )
    served[10] = (index, payload, dict(placed, **{name: other}))
    numbers = correct.compare(world, 5, served)
    assert numbers["mismatched_placements"] == 1
    assert numbers["worst"][0].startswith(name)
    numbers.update(unfinished_acked=0, readback_mismatches=0)
    assert not correct.verdict(numbers, 30)


def test_the_score_gap_tells_a_tie_from_a_wrong_answer():
    from benchmark.reference import RefCluster

    assert RefCluster._gap(3, 3, {}) == 0.0
    assert RefCluster._gap(3, 4, {3: 0.5, 4: 0.5 - 1e-8}) < 1e-7
    assert RefCluster._gap(3, 4, {3: 0.5, 4: 0.49}) > 1e-2
    assert RefCluster._gap(3, 9, {3: 0.5}) == float("inf")  # not a candidate
    assert RefCluster._gap(3, -1, {3: 0.5}) == float("inf")  # nothing served


def test_lost_and_duplicate_placements_are_counted():
    config = _small("binpack-10k", 500, 5000)
    world, served = _served_by(config, 9, 10, "float64")
    index, payload, placed = served[3]
    lost = dict(placed)
    lost.pop(sorted(lost)[0])
    served[3] = (index, payload, lost)
    served[7] = (None, served[7][1], {})  # never committed
    numbers = correct.compare(world, 9, served)
    assert numbers["lost_or_duplicate"] == 2
    assert numbers["jobs_compared"] == 9


def test_visit_limit_and_job_spec():
    assert visit_limit(10000) == 14 and visit_limit(5000) == 13
    assert visit_limit(3) == 2 and visit_limit(0) == 2
    spec = JobSpec.from_payload(Manifest().config("spread-5k")["job"])
    assert (spec.count, spec.cpu, spec.mem, spec.disk) == (6, 300, 256, 300)
    assert spec.spreads[0][1] == 60 and spec.affinities[0][3] == 35
    assert spec.only_node == -1
    stream = JobStream(Manifest().config("spread-5k"), {}, 1)
    pinned = ProbeStream(stream, ["n17"]).payload(0)
    assert JobSpec.from_payload(pinned).only_node == 17
    bad = Manifest().config("binpack-10k")["job"]
    bad["constraints"] = [{"ltarget": "${attr.arch}", "operand": "=", "rtarget": "arm"}]
    with pytest.raises(ValueError):
        JobSpec.from_payload(bad)
