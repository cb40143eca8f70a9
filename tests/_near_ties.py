"""Near-ties of a fleet's score lattice, for tests/test_float32_scoring.py
and its child process: candidates whose float64 scores (structs/funcs.py,
sched/rank.py, sched/spread.py) differ while one float32 a score — the
tail the float32 trace had before its scores were pairs, kept HERE as
``plain_float32`` and nowhere in the program — ties them, or even swaps
them; and, for a job with a spread and a node affinity, candidates of
different datacenters that one float32 a TERM (``float32_terms``: the
boost and the affinity as the trace held them before they were pairs)
puts in the wrong order.  numpy only.
"""
import dataclasses

import numpy as np

from nomad_tpu.structs.funcs import pow10_np

F = np.float32


def exponentials(cpu_after, cpu_cap, mem_after, mem_cap):
    """The two fitness exponentials as the definition rounds them: the
    share and the power in float64, then one rounding to float32."""
    return (
        pow10_np(1.0 - cpu_after / np.asarray(cpu_cap, np.float64)).astype(F),
        pow10_np(1.0 - mem_after / np.asarray(mem_cap, np.float64)).astype(F),
    )


def _mean_score(
    dtype, e_cpu, e_mem, collisions, desired_count, penalty,
    affinity=0.0, boost=0.0,
):
    """rank.go's score list and its mean over binpack fitness, job
    anti-affinity, the reschedule penalty, node affinity and the spread
    boost (each of the last two appended where it is not 0), every step
    in ``dtype``."""
    t = dtype
    fit = np.clip(t(20.0) - (e_cpu.astype(t) + e_mem.astype(t)), t(0), t(18))
    total = fit / t(18.0)
    count = np.ones_like(total)
    has = np.asarray(collisions) > 0
    anti = -(np.asarray(collisions).astype(t) + t(1.0)) / t(desired_count)
    total = total + np.where(has, anti, t(0.0))
    count = count + has.astype(t)
    total = total - np.asarray(penalty).astype(t)
    count = count + np.asarray(penalty).astype(t)
    for term in (affinity, boost):
        term = np.asarray(term).astype(t)
        total = total + term
        count = count + (term != 0).astype(t)
    return total / count


def float64_score(
    e_cpu, e_mem, collisions=0, desired_count=10, penalty=False,
    affinity=0.0, boost=0.0,
):
    """The definition: every step after the exponentials in float64."""
    return _mean_score(
        np.float64, e_cpu, e_mem, collisions, desired_count, penalty,
        affinity, boost,
    )


def spread_boost(dtype, desired, used, weight=1.0):
    """sched/spread.py's boost of one stanza, ``((desired - used) /
    desired) * weight`` with ``used`` counting the placement under way
    (spread.go:123): ``desired`` and ``weight`` are the float64 numbers
    the host builds (percent / 100 x count; weight / sum of weights),
    brought to ``dtype`` as a launch narrows them, and every step after
    that in ``dtype``."""
    t = dtype
    d = np.asarray(desired, np.float64).astype(t)
    w = np.asarray(weight, np.float64).astype(t)
    return ((d - (np.asarray(used).astype(t) + t(1.0))) / d) * w


def float32_terms(
    e_cpu, e_mem, collisions, desired_count, affinity, desired, used, weight
):
    """The control for a job with a spread and an affinity: the score
    as the float32 trace held it while those two terms were ONE float32
    each — the affinity narrowed, the boost computed in float32 from
    narrowed operands — and the rest of the score a pair (float64 here,
    which a pair follows to 2^-44)."""
    return float64_score(
        e_cpu, e_mem, collisions, desired_count,
        affinity=np.asarray(affinity, np.float64).astype(F),
        boost=spread_boost(F, desired, used, weight),
    )


def job_terms(config):
    """What a configuration's job gives each datacenter of its fleet,
    as the scheduler computes them in float64: (desired count of the
    spread stanza — percent targets, the rest implicit —, the stanza's
    weight over the sum of weights, the node affinity's matched weight
    over the sum of weights).  One datacenter stanza, datacenter
    affinities: the benchmark's spread job."""
    job = config["job"]
    count = float(job["task_groups"][0]["count"])
    dcs = config["fleet"]["datacenters"]
    (spread,) = job["spreads"]
    targets = {
        t["value"]: (float(t["percent"]) / 100.0) * count
        for t in spread["targets"]
    }
    rest = count - sum(targets.values())
    desired = np.asarray([targets.get(dc, rest) for dc in dcs], np.float64)
    weight = float(spread["weight"]) / float(
        sum(s["weight"] for s in job["spreads"])
    )
    total = sum(abs(float(a["weight"])) for a in job["affinities"])
    affinity = np.asarray([
        sum(float(a["weight"]) for a in job["affinities"] if a["rtarget"] == dc)
        / total
        for dc in dcs
    ], np.float64)
    return desired, weight, affinity


def plain_float32(e_cpu, e_mem, collisions=0, desired_count=10, penalty=False):
    """The float32 trace's tail as it was: one float32 a step."""
    return _mean_score(F, e_cpu, e_mem, collisions, desired_count, penalty)


def lattice(fleet):
    """Every (cpu after, cpu capacity) and (memory after, memory
    capacity) the fleet's shapes reach: capacity less the reservation,
    cpu in hundreds and memory in 128s (the sizes of its allocations
    and asks are multiples of those).  Returns the two axes, each an
    (n, 2) array of whole numbers."""
    def axis(caps, reserved, step):
        rows = []
        for cap in caps:
            after = np.arange(0, cap - reserved + 1, step, dtype=np.int64)
            rows.append(np.stack([after, np.full_like(after, cap - reserved)], 1))
        return np.concatenate(rows)

    return (
        axis(fleet["node_cpu"], fleet["reserved_cpu"], 100),
        axis(fleet["node_memory_mb"], fleet["reserved_memory_mb"], 128),
    )


def lattice_sums(fleet):
    """The distinct float64 sums of the two exponentials over the
    lattice, ascending, each with one (cpu after, cpu capacity, memory
    after, memory capacity) that gives it."""
    cpu, mem = lattice(fleet)
    e_cpu, e_mem = exponentials(cpu[:, 0], cpu[:, 1], mem[:, 0], mem[:, 1])
    sums = e_cpu.astype(np.float64)[:, None] + e_mem.astype(np.float64)[None, :]
    distinct, first = np.unique(sums.ravel(), return_index=True)
    i, j = np.unravel_index(first, sums.shape)
    return distinct, np.concatenate([cpu[i], mem[j]], axis=1)


def near_ties(fleet, collisions=0, desired_count=10):
    """(worse, better, gap): neighbouring candidates of the lattice
    whose float64 scores differ (``gap``, absolute) and whose plain
    float32 scores do not order them so: equal, or the other way round.
    Each candidate is a row (cpu after, cpu capacity, memory after,
    memory capacity, collisions).  With ``collisions`` the neighbours
    are one node without and one with that many allocations of the job
    on it (the mean over one term against the mean over two)."""
    sums, where = lattice_sums(fleet)
    rows = np.concatenate([where, np.zeros((len(where), 1), np.int64)], 1)
    if collisions:
        rows = np.concatenate([rows, rows + [0, 0, 0, 0, collisions]])
    e_cpu, e_mem = exponentials(rows[:, 0], rows[:, 1], rows[:, 2], rows[:, 3])
    want = float64_score(e_cpu, e_mem, rows[:, 4], desired_count)
    plain = plain_float32(e_cpu, e_mem, rows[:, 4], desired_count)
    order = np.argsort(want, kind="stable")
    want, plain, rows = want[order], plain[order], rows[order]
    gap = np.diff(want)
    # a float64 gap under 2^-40 of the score is float64's own rounding
    # of a tie in the reals: outside what the pair is built to order
    found = (
        (want[:-1] > 0) & (gap > want[1:] * 2.0**-40)
        & (plain[:-1] >= plain[1:])
    )
    if collisions:
        found &= rows[:-1, 4] != rows[1:, 4]
    at = np.flatnonzero(found)
    return rows[at], rows[at + 1], gap[at]


def spread_near_ties(config, weight=None, affinity=None):
    """(worse, better, gap) over the score lattice of a fleet whose job
    has a spread and an affinity: two candidates of DIFFERENT
    datacenters, in one state of the job's placements, whose float64
    scores differ by ``gap`` and which ``float32_terms`` does not order
    so.  A candidate is a row (cpu after, cpu capacity, memory after,
    memory capacity, collisions = 0, datacenter, allocations of the job
    in that datacenter so far).  ``weight`` and ``affinity`` put other
    numbers in the job's place (a second stanza's share of the weights,
    say)."""
    desired, w, aff = job_terms(config)
    if weight is not None:
        w = weight
    if affinity is not None:
        aff = np.asarray(affinity, np.float64)
    count = int(config["job"]["task_groups"][0]["count"])
    _sums, where = lattice_sums(config["fleet"])
    n = len(where)
    dc, used = np.divmod(np.arange(len(desired) * count), count)
    rows = np.concatenate([
        np.tile(where, (len(dc), 1)), np.zeros((n * len(dc), 1), np.int64),
        np.repeat(dc, n)[:, None], np.repeat(used, n)[:, None],
    ], axis=1)
    e_cpu, e_mem = exponentials(rows[:, 0], rows[:, 1], rows[:, 2], rows[:, 3])
    want = float64_score(
        e_cpu, e_mem, 0, count, affinity=aff[rows[:, 5]],
        boost=spread_boost(np.float64, desired[rows[:, 5]], rows[:, 6], w),
    )
    control = float32_terms(
        e_cpu, e_mem, 0, count, aff[rows[:, 5]], desired[rows[:, 5]],
        rows[:, 6], w,
    )
    order = np.argsort(want, kind="stable")
    want, control, rows = want[order], control[order], rows[order]
    gap = np.diff(want)
    found = (
        (want[:-1] > 0) & (gap > want[1:] * 2.0**-40)
        & (control[:-1] >= control[1:])
        & (rows[:-1, 5] != rows[1:, 5])
        # one state of the job: what the two datacenters hold together
        # leaves room for the placement under way
        & (rows[:-1, 6] + rows[1:, 6] < count)
    )
    at = np.flatnonzero(found)
    return rows[at], rows[at + 1], gap[at]


def plant(world, fleet, worse, better, ask):
    """``world`` with its nodes alternately the ``worse`` and the
    ``better`` candidate of one near-tie ONCE THE ASK IS ADDED: a
    resident allocation each that leaves the node ``ask`` short of the
    lattice point.  Every pick over fresh nodes is then a near-tie."""
    n = world.n_nodes
    kinds = np.stack([worse, better])[np.arange(n) % 2]
    return dataclasses.replace(
        world,
        node_cpu=kinds[:, 1] + fleet["reserved_cpu"],
        node_mem=kinds[:, 3] + fleet["reserved_memory_mb"],
        alloc_node=np.arange(n, dtype=np.int64),
        alloc_cpu=kinds[:, 0] - ask[0],
        alloc_mem=kinds[:, 2] - ask[1],
    )
