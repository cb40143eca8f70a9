"""Child process of tests/test_float32_scoring.py: the served batch
pipeline with ``jax_enable_x64`` OFF (the tier-1 session runs x64 on,
and the switch is read when JAX starts, so a case that needs it off
needs a process of its own), over a small fleet and a seeded stream of
one of the benchmark's job shapes.  Prints one JSON line: how the
chained kernel's placements compare with the sequential scheduler's
(no JAX in it) and with ``benchmark/reference.py`` at float64.

    python tests/_float32_parity_child.py <config> <nodes> <jobs> <seed> [planted|weighted]

With ``planted`` the fleet is one near-tie of the configuration's score
lattice (tests/_near_ties.py), the seed's choice of them: half the
nodes a candidate, half its neighbour whose float64 score is a hair
better and which one float32 a score cannot tell from it.  With
``weighted`` the job has a second node affinity (dc3, weight 60 beside
dc2's 35), so that its affinity terms, 35/95 and 60/95, are no float32
and reach the float32 trace as pairs.
"""
import json
import os
import sys

os.environ["JAX_ENABLE_X64"] = "0"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["NOMAD_TPU_SYNC_COMPILE"] = "1"  # a cold shape blocks, no host path
os.environ["NOMAD_TPU_BROKER_WATCHDOG"] = "1"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

import _near_ties as near  # noqa: E402
from benchmark import correct, system  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.stream import JobStream  # noqa: E402
from benchmark.world import make_world  # noqa: E402
from nomad_tpu.api.codec import job_from_dict  # noqa: E402
from nomad_tpu.server import Server  # noqa: E402


def serve(world, payloads, seed, batch_pipeline):
    """Every registration queued before the workers start, so that both
    servers take the evaluations in the same order and the batch worker
    chains them through whole chunks."""
    server = Server(num_schedulers=1, heartbeat_ttl=1e9, seed=seed,
                    batch_pipeline=batch_pipeline)
    system.load_world(server.store, world)
    for p in payloads:
        server.register_job(job_from_dict(p))
    server.start()
    try:
        assert server.drain_to_idle(timeout=300)
        served = [
            system.job_answers(server.store, p["id"]) + (p,) for p in payloads
        ]
        worker = server.workers[0]
        prescored = getattr(worker, "prescored", 0)
        counters = server.metrics.dump()["counters"]
    finally:
        server.stop()
    return [(idx, p, placed) for idx, placed, p in served], prescored, counters


def main(argv):
    name, nodes, jobs, seed = argv[0], int(argv[1]), int(argv[2]), int(argv[3])
    config = Manifest().config(name)
    config["fleet"]["nodes"] = nodes
    config["fleet"]["resident_allocs"] = nodes * 10
    world = make_world(config, seed)
    if argv[4:] == ["weighted"]:
        config["job"]["affinities"].append({
            "ltarget": "${node.datacenter}", "rtarget": "dc3", "operand": "=",
            "weight": 60,
        })
    if argv[4:] == ["planted"]:
        worse, better, _gap = near.near_ties(config["fleet"])
        res = config["job"]["task_groups"][0]["tasks"][0]["resources"]
        ask = (res["cpu"], res["memory_mb"])
        roomy = np.flatnonzero(
            np.all(worse[:, [0, 2]] >= ask, 1) & np.all(better[:, [0, 2]] >= ask, 1)
        )
        k = roomy[np.random.default_rng(seed).integers(len(roomy))]
        world = near.plant(world, config["fleet"], worse[k], better[k], ask)
    stream = JobStream(config, {"loop": "closed", "in_flight": 1}, seed)
    payloads = [stream.payload(i) for i in range(jobs)]
    kernel, prescored, counters = serve(world, payloads, seed, True)
    sequential, _n, _c = serve(world, payloads, seed, False)
    numbers = correct.compare(world, seed, kernel)
    print(json.dumps({
        "x64": bool(jax.config.jax_enable_x64),
        "jobs": jobs,
        "prescored": prescored,
        "placements": sum(len(s[2]) for s in kernel),
        "differ_from_sequential": sum(
            a[2] != b[2] for a, b in zip(kernel, sequential)
        ),
        "mismatched_placements": numbers["mismatched_placements"],
        "lost_or_duplicate": numbers["lost_or_duplicate"],
        "jobs_compared": numbers["jobs_compared"],
        "walk_pulls": counters.get("batch_worker.walk_pulls"),
        "walk_picks": counters.get("batch_worker.walk_picks"),
        "pair_decided_picks": counters.get("batch_worker.pair_decided_picks"),
    }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(0)  # daemon threads may sit inside XLA calls
