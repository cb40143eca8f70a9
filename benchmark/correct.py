"""The comparison that decides ``correct``.

Every registration the run sent (shape probes, warm-up, window and tail)
is judged once the window has closed and the server has drained,
evaluations taken in the order they committed and every placement
against the state the served history implies (after a placement is
judged the reference commits the SERVED node, so one altered answer
reads once, not as a cascade):

* ``unfinished_acked``: acknowledged registrations (HTTP 200 with an
  EvalID) whose evaluation did not end ``complete`` — limit 0;
* ``lost_or_duplicate``: jobs whose live allocations are not exactly
  ``<job>.<group>[0..count-1]``, one each — limit 0;
* ``mismatched_placements``: placements whose served node is not the
  reference's choice — limit 0, as the configuration states it: every
  placement bit-identical to the sequential scheduler's;
* ``readback_mismatches``: of a sample of jobs drawn from the seed (the
  last one in it), those whose ``GET /v1/job/<id>/allocations`` differs
  from the state store's view — limit 0;
* ``jobs_compared``: at least the completions the window counted.

``widest_score_gap`` (how far a mismatched placement's served node
scores below the reference's best, relative, float64; 1.0 where the
served node is no candidate at all) is printed beside them and not held
to a limit: it tells a tie that a lower precision broke (1e-7) from an
answer that is plainly wrong (1e-2 and more).
"""
from __future__ import annotations

import math

from .reference import JobSpec, RefCluster

# every limit is exact: the configuration states the guarantees
LIMITS = {
    "unfinished_acked": 0,
    "lost_or_duplicate": 0,
    "mismatched_placements": 0,
    "readback_mismatches": 0,
}


def expected_names(spec: JobSpec) -> list:
    return [f"{spec.job_id}.{spec.group}[{k}]" for k in range(spec.count)]


def compare(world, server_seed: int, served: list, precision: str = "float64"):
    """``served``: (commit index or None, payload, {alloc name: node id})
    per acknowledged registration.  Returns the numbers compared."""
    node_index = {world.node_id(i): i for i in range(world.n_nodes)}
    ref = RefCluster(world, server_seed, precision)
    lost = sum(1 for s in served if s[0] is None)
    committed = sorted((s for s in served if s[0] is not None), key=lambda s: s[0])
    mismatched = 0
    widest = 0.0
    worst: list = []
    for _index, payload, placed in committed:
        spec = JobSpec.from_payload(payload)
        names = expected_names(spec)
        if sorted(placed) != names or any(
            v not in node_index for v in placed.values()
        ):
            lost += 1
        took = [node_index.get(placed.get(name), -1) for name in names]
        picks, gaps = ref.place(spec, served=took)
        for k, gap in enumerate(gaps):
            if took[k] == picks[k]:
                continue
            mismatched += 1
            gap = 1.0 if math.isinf(gap) else gap
            widest = max(widest, gap)
            worst.append(
                (gap, f"{names[k]}: served {placed.get(names[k])} "
                      f"reference {world.node_id(picks[k]) if picks[k] >= 0 else None} "
                      f"gap {gap:.3e}")
            )
    worst.sort(key=lambda w: -w[0])
    return {
        "lost_or_duplicate": lost,
        "widest_score_gap": widest,
        "mismatched_placements": mismatched,
        "jobs_compared": len(committed),
        "worst": [w[1] for w in worst[:5]],
    }


def verdict(numbers: dict, floor_jobs: int) -> bool:
    ok = all(numbers.get(k, 1) <= limit for k, limit in LIMITS.items())
    return ok and numbers.get("jobs_compared", 0) >= max(1, floor_jobs)


def lines(numbers: dict, floor_jobs: int) -> list:
    """Each number compared beside its limit, short and plain."""
    out = [
        f"check {k}={numbers.get(k)} limit<={limit}"
        for k, limit in LIMITS.items()
    ]
    out.append(
        f"check jobs_compared={numbers.get('jobs_compared')} "
        f"limit>={max(1, floor_jobs)}"
    )
    out.append(
        f"info widest_score_gap={numbers.get('widest_score_gap')} "
        f"worst={numbers.get('worst')}"
    )
    return out


def table(numbers: dict, floor_jobs: int) -> dict:
    out = {
        k: {"value": numbers.get(k), "limit": limit, "sense": "<="}
        for k, limit in LIMITS.items()
    }
    out["jobs_compared"] = {
        "value": numbers.get("jobs_compared"),
        "limit": max(1, floor_jobs),
        "sense": ">=",
    }
    out["widest_score_gap"] = {
        "value": numbers.get("widest_score_gap"), "limit": None,
        "sense": "info",
    }
    return out
