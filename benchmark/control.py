#!/usr/bin/env python3
"""The control of ``correct``: the plain reference computed in the
precision below the one the cell's configuration states (float32 for
float64, bfloat16 for float32) put in the program's place, at a cell's
own size, judged by the same comparison.  It has to come out as not
correct.  Not part of a benchmark run.  (On the chip the program itself
with ``jax_enable_x64`` off is the float32 control: PERF.md section 2.)

    python3 benchmark/control.py --workload <cell> --jobs <n> --seeds a,b,c
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import correct  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference import JobSpec, RefCluster  # noqa: E402
from benchmark.stream import JobStream  # noqa: E402
from benchmark.world import make_world  # noqa: E402


def served_by(config: dict, traffic: dict, seed: int, n_jobs: int, precision: str):
    """(world, served) with the reference at ``precision`` in the
    program's place, evaluations committed in stream order."""
    world = make_world(config, seed)
    stream = JobStream(config, traffic, seed)
    cluster = RefCluster(world, seed, precision)
    served = []
    for i in range(n_jobs):
        payload = stream.payload(i)
        spec = JobSpec.from_payload(payload)
        picks, _gaps = cluster.place(spec)
        placed = {
            name: world.node_id(n)
            for name, n in zip(correct.expected_names(spec), picks)
            if n >= 0
        }
        served.append((i + 1, payload, placed))
    return world, served


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--jobs", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", default="")
    args = ap.parse_args(argv)
    manifest = Manifest()
    cell = manifest.workload(args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    precision = args.precision or (
        "float32" if config.get("jax_enable_x64") else "bfloat16"
    )
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        world, served = served_by(config, traffic, seed, args.jobs, precision)
        numbers = correct.compare(world, seed, served)
        numbers.update(unfinished_acked=0, readback_mismatches=0)
        print(json.dumps({
            "workload": cell["name"], "seed": seed, "precision": precision,
            "jobs": args.jobs, "widest_score_gap": numbers["widest_score_gap"],
            "mismatched_placements": numbers["mismatched_placements"],
            "lost_or_duplicate": numbers["lost_or_duplicate"],
            "correct": correct.verdict(numbers, args.jobs),
            "seconds": round(time.monotonic() - t, 2),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
