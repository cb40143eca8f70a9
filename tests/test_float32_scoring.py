"""float32 scoring that is the definition's, bit for bit.

The fitness exponential is defined in float64 and rounded once to
float32 (structs/funcs.py ``_pow10``).  With ``jax_enable_x64`` off —
the dtype a chip deploys — the kernels carry the free share and the
exponential as two float32s (ops/twofloat.py) and must land on that
same float32; with it on, the float64 trace is what it has always been.
Everything after the exponentials is defined in float64 as well, so the
float32 trace carries the whole score of a candidate as a pair
(ops/score.py ``ScoreList``) and the walk picks on the pair: two
candidates whose float64 scores differ order as float64 orders them,
where one float32 a score ties them and gives the pick to the earlier.
The tier-1 session runs x64 on, so every case here that needs it off
switches it off for its own scope (``jax.enable_x64(False)``, or a child
process where a served pipeline's threads need it).
"""
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _near_ties as near
from benchmark.manifest import Manifest, repo_root
from nomad_tpu.ops import twofloat
from nomad_tpu.ops.batch import (
    ChainInputs,
    SpreadInputs,
    _rotated_prefix,
    _walk,
    chained_plan_picks_cols,
    split_pulls,
    spread_contribution,
)
from nomad_tpu.ops.score import (
    MAX_SKIP,
    SKIP_THRESHOLD,
    ScoreList,
    _fit_exponentials,
    _pow10,
)
from nomad_tpu.structs.funcs import pow10_np


def _definition(after, cap):
    """float64 free share, float64 power, rounded once to float32."""
    share = 1.0 - after.astype(np.float64) / cap.astype(np.float64)
    return pow10_np(share).astype(np.float32)


def _two_float(after, cap):
    with jax.enable_x64(False):
        got = jax.jit(twofloat.pow10_free)(
            jnp.asarray(after, jnp.float32), jnp.asarray(cap, jnp.float32)
        )
        assert got.dtype == jnp.float32
        return np.asarray(got)


def _every_pair(config_name, resource):
    """Every (used, capacity) a fleet of this configuration can hold for
    one resource: each capacity less its reservation, and every whole
    number of units up to it (a superset of the sums of its alloc and
    ask sizes)."""
    fleet = Manifest().config(config_name)["fleet"]
    caps, reserved = {
        "cpu": (fleet["node_cpu"], fleet["reserved_cpu"]),
        "mem": (fleet["node_memory_mb"], fleet["reserved_memory_mb"]),
    }[resource]
    after, cap = [], []
    for c in caps:
        after.append(np.arange(0, c - reserved + 1, dtype=np.int64))
        cap.append(np.full(c - reserved + 1, c - reserved, dtype=np.int64))
    return np.concatenate(after), np.concatenate(cap)


@pytest.mark.parametrize("resource", ["cpu", "mem"])
@pytest.mark.parametrize("config_name", ["binpack-10k", "spread-5k"])
def test_two_float_is_the_definition_on_every_pair_a_fleet_can_hold(
    config_name, resource
):
    after, cap = _every_pair(config_name, resource)
    assert len(after) > 40000
    got, want = _two_float(after, cap), _definition(after, cap)
    off = np.flatnonzero(got != want)
    assert off.size == 0, (after[off][:5], cap[off][:5])


@pytest.mark.parametrize("seed", [31, 1826525683, 2**31 + 11])
def test_two_float_is_the_definition_on_a_seeded_grid(seed):
    rng = np.random.default_rng(seed)
    cap = rng.integers(1000, 70000, 200000)
    after = (rng.random(200000) * (cap + 1)).astype(np.int64)
    assert np.array_equal(_two_float(after, cap), _definition(after, cap))
    # x over [0, 1] on an even grid: after = k of a capacity of 2^18
    k = np.arange(0, 2**18 + 1, dtype=np.int64)
    cap = np.full_like(k, 2**18)
    assert np.array_equal(_two_float(k, cap), _definition(k, cap))


def test_the_free_share_is_float64s_to_the_pair():
    after = np.arange(0, 31901, dtype=np.int64)
    cap = np.full_like(after, 31900)
    with jax.enable_x64(False):
        hi, lo = jax.jit(twofloat.free_share)(
            jnp.asarray(after, jnp.float32), jnp.asarray(cap, jnp.float32)
        )
    pair = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    want = 1.0 - after / cap.astype(np.float64)
    assert np.max(np.abs(pair - want)) < 2.0**-46
    assert np.array_equal(np.asarray(hi), want.astype(np.float32))


def test_plain_float32_pow_is_not_the_definition():
    """What the two floats are for: float32's own division and pow miss
    the definition on a large share of the same pairs, even under IEEE
    (on the TPU: on nearly all of them, PERF.md)."""
    after, cap = _every_pair("binpack-10k", "cpu")
    with jax.enable_x64(False):
        a = jnp.asarray(after, jnp.float32)
        c = jnp.asarray(cap, jnp.float32)
        plain = np.asarray(jnp.power(jnp.float32(10.0), 1.0 - a / c))
    assert np.mean(plain != _definition(after, cap)) > 0.1


def test_the_float32_trace_scores_with_the_two_floats():
    after = np.asarray([600.0, 8400.0, 31900.0], np.float32)
    cap = np.asarray([7900.0, 15900.0, 31900.0], np.float32)
    mem_after = np.asarray([384.0, 8000.0, 16128.0], np.float32)
    mem_cap = np.asarray([16128.0, 32512.0, 65280.0], np.float32)
    with jax.enable_x64(False):
        hi, lo = jax.jit(_fit_exponentials, static_argnums=4)(
            after, cap, mem_after, mem_cap, jnp.float32
        )
        hi, lo = np.asarray(hi), np.asarray(lo)
        assert hi.dtype == lo.dtype == np.float32
    e_cpu, e_mem = _definition(after, cap), _definition(mem_after, mem_cap)
    # hi is the float32 sum the trace scored with before; with lo the
    # pair is the sum as float64 holds it: exactly
    assert np.array_equal(hi, e_cpu + e_mem)
    assert np.array_equal(
        hi.astype(np.float64) + lo, e_cpu.astype(np.float64) + e_mem
    )


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


def test_at_x64_on_the_lowered_pow10_is_what_it_was():
    x = jnp.linspace(0.0, 1.0, 16, dtype=jnp.float64)
    text = _lowered(lambda v: _pow10(v, jnp.float64), x)
    ops = [
        line.split("=", 1)[1].split()[0]
        for line in text.splitlines()
        if "stablehlo." in line and "=" in line
    ]
    assert ops == [
        "stablehlo.constant", "stablehlo.broadcast_in_dim",
        "stablehlo.power", "stablehlo.convert", "stablehlo.convert",
    ], text
    assert "tensor<16xf32>" in text and "bitcast" not in text


def test_at_x64_on_the_score_terms_lower_to_the_float64_trace_of_before():
    """``_fit_exponentials`` at float64 is the inline code the kernels
    held before it, operation for operation."""
    def before(cpu_after, cpu_cap, mem_after, mem_cap):
        dtype = cpu_after.dtype
        free_cpu = 1.0 - cpu_after / cpu_cap
        free_mem = 1.0 - mem_after / mem_cap
        return (
            jnp.power(jnp.asarray(10.0, dtype), free_cpu)
            .astype(jnp.float32).astype(dtype)
            + jnp.power(jnp.asarray(10.0, dtype), free_mem)
            .astype(jnp.float32).astype(dtype)
        )

    def now(cpu_after, cpu_cap, mem_after, mem_cap):
        return _fit_exponentials(
            cpu_after, cpu_cap, mem_after, mem_cap, cpu_after.dtype
        )

    args = [jnp.arange(1.0, 9.0, dtype=jnp.float64) * k for k in (1, 3, 2, 5)]
    text = _lowered(now, *args)
    assert text.replace("jit_now", "jit_before") == _lowered(before, *args)
    assert "bitcast" not in text and "f32" in text


def _before_step(
    cpu_after, cpu_cap, mem_after, mem_cap, coll, desired, pick, penalty,
    aff, feasible, offset, limit, n_candidates,
):
    """The score block and the walk of a pick-step as ops/batch.py held
    them before a float32 trace's score became a pair, line for line."""
    dtype = cpu_after.dtype
    base = _fit_exponentials(cpu_after, cpu_cap, mem_after, mem_cap, dtype)
    fitness = jnp.clip(20.0 - base, 0.0, 18.0)
    score_sum = fitness / 18.0
    count = jnp.ones_like(score_sum)
    has_coll = coll > 0
    anti = jnp.where(
        has_coll,
        -(coll.astype(dtype) + 1.0) / desired[pick].astype(dtype),
        0.0,
    )
    score_sum = score_sum + anti
    count = count + has_coll.astype(dtype)
    score_sum = score_sum - penalty.astype(dtype)
    count = count + penalty.astype(dtype)
    has_aff = aff != 0.0
    score_sum = score_sum + jnp.where(has_aff, aff, 0.0)
    count = count + has_aff.astype(dtype)
    s_p = score_sum / count
    f_p = feasible

    n = s_p.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_tail = pos >= n_candidates
    in_wrap = pos < offset
    wp = jnp.where(
        is_tail, pos, jnp.mod(pos - offset + n_candidates, n_candidates)
    )

    def rot(b):
        cs = jnp.cumsum(b.astype(jnp.int32))
        total = cs[-1]
        c_off = jnp.where(offset > 0, cs[offset - 1], 0)
        return (
            _rotated_prefix(cs, c_off, total, in_wrap, is_tail), total
        )

    bad = f_p & (s_p <= SKIP_THRESHOLD)
    bad_rank, _ = rot(bad)
    diverted = bad & (bad_rank <= MAX_SKIP)
    nd = f_p & ~diverted
    nd_incl, nd_count = rot(nd)
    div_incl, n_div = rot(diverted)
    div_rank = div_incl - 1
    div_order = jnp.where(
        (n_div == 2) & (nd_count > 0), 1 - div_rank, div_rank
    )
    emit_order = jnp.where(nd, nd_incl - 1, nd_count + div_order)
    emitted = f_p & (emit_order < limit)

    neg_inf = jnp.asarray(-jnp.inf, dtype=s_p.dtype)
    masked = jnp.where(emitted, s_p, neg_inf)
    best = jnp.max(masked)
    candidates = emitted & (masked == best)
    order_key = jnp.where(
        candidates, emit_order, jnp.asarray(2**31 - 1, jnp.int32)
    )
    win = jnp.argmin(order_key)
    any_emitted = jnp.any(emitted)

    limit_reached = nd_count >= limit
    big = jnp.asarray(2**31 - 1, jnp.int32)
    lth_wp = jnp.min(
        jnp.where(nd & (nd_incl == limit), wp, big)
    )
    pulls = jnp.where(limit_reached, lth_wp + 1, n_candidates)
    return win, any_emitted, pulls


def _step(
    cpu_after, cpu_cap, mem_after, mem_cap, coll, desired, pick, penalty,
    aff, feasible, offset, limit, n_candidates,
):
    """The same two phases as ops/batch.py holds them now."""
    dtype = cpu_after.dtype
    scores = ScoreList(
        cpu_after, cpu_cap, mem_after, mem_cap, coll, desired, False, dtype,
        pick=pick,
    )
    scores.penalty(penalty)
    has_aff = aff != 0.0
    scores.append(jnp.where(has_aff, aff, 0.0), has_aff)
    win, any_emitted, pulls, decided = _walk(
        scores.mean(), feasible, offset, limit, n_candidates
    )
    return (win, any_emitted, pulls), decided


def _step_args(dtype):
    n = 16
    col = jnp.arange(1.0, n + 1.0, dtype=dtype)
    return (
        col * 300, col * 500 + 4000, col * 128, col * 256 + 2048,
        jnp.arange(n, dtype=jnp.int32) % 3, jnp.full(4, 10, jnp.int32),
        jnp.int32(1), jnp.arange(n) % 5 == 0, (col % 4) * 0.25,
        jnp.arange(n) % 7 != 0, jnp.int32(3), jnp.int32(4), jnp.int32(n - 2),
    )


def test_at_x64_on_the_score_block_and_the_walk_lower_to_the_float64_trace_of_before():
    """Every line a float32 pair touched — the score list from the
    exponentials to the mean, the skip test, the maximum and its tie —
    is, at float64, the code the chained kernel held before, operation
    for operation; no pair, no flag in the pulls."""
    args = _step_args(jnp.float64)
    def _now_step(*a):
        return _step(*a)[0]

    now = _lowered(_now_step, *args)
    assert _step(*args)[1] is None
    assert now.replace("jit__now_step", "jit__before_step") == _lowered(
        _before_step, *args
    )
    assert "bitcast" not in now


def test_at_x64_on_the_chained_kernel_holds_no_pair_and_flags_no_pull():
    kernel = _near_tie_launch(*_some_near_ties(4), dtype=np.float64)
    text = chained_plan_picks_cols.lower(*kernel[0], **kernel[1]).as_text()
    assert "bitcast" not in text and "1073741824" not in text
    with jax.enable_x64(False):
        kernel = _near_tie_launch(*_some_near_ties(4), dtype=np.float32)
        text = chained_plan_picks_cols.lower(*kernel[0], **kernel[1]).as_text()
    assert "bitcast" in text and "1073741824" in text and "f64" not in text


# ---- the whole score as a pair ------------------------------------------

FLEETS = {
    name: Manifest().config(name)["fleet"] for name in ("binpack-10k", "spread-5k")
}


def _pair_scores(rows, desired_count, penalty, spread_fit=False):
    """(hi, lo) of ``ScoreList``'s mean at x64 off for candidates given
    as rows (cpu after, cpu capacity, memory after, memory capacity,
    collisions)."""
    def mean(cpu_after, cpu_cap, mem_after, mem_cap, coll, pen):
        scores = ScoreList(
            cpu_after, cpu_cap, mem_after, mem_cap, coll,
            jnp.int32(desired_count), spread_fit, jnp.float32,
        )
        scores.penalty(pen)
        return scores.mean()

    with jax.enable_x64(False):
        cols = [jnp.asarray(rows[:, k], jnp.float32) for k in range(4)]
        hi, lo = jax.jit(mean)(
            *cols, jnp.asarray(rows[:, 4], jnp.int32), jnp.asarray(penalty)
        )
        assert hi.dtype == lo.dtype == jnp.float32
        return np.asarray(hi), np.asarray(lo)


def _ordered_as_float64(hi, lo, want):
    """Sorted by the float64 score, the pairs ascend with it wherever it
    rises by more than 2^-40 of itself.  (Nearer than that, float64's
    own rounding orders two different sums of terms that tie in the
    reals; only candidates of identical inputs are held to tie, and
    those the kernel's test holds.)"""
    order = np.argsort(want, kind="stable")
    hi, lo, want = hi[order], lo[order], want[order]
    rises = (hi[:-1] < hi[1:]) | ((hi[:-1] == hi[1:]) & (lo[:-1] < lo[1:]))
    apart = np.diff(want) > np.abs(want[1:]) * 2.0**-40
    assert np.all(rises[apart]), np.flatnonzero(apart & ~rises)[:5]
    return int(apart.sum())


@pytest.mark.parametrize("mixed_terms", [False, True], ids=["binpack", "mixed"])
@pytest.mark.parametrize("config_name", sorted(FLEETS))
def test_the_pair_score_orders_sampled_candidates_of_a_fleet_as_float64(
    config_name, mixed_terms
):
    """(a): candidates drawn from every (used, capacity) the fleet can
    hold; ``mixed``: a third with collisions, a tenth penalised, so the
    means run over one, two and three terms."""
    rng = np.random.default_rng(35)
    n = 300000
    cpu = np.stack(_every_pair(config_name, "cpu"), 1)
    mem = np.stack(_every_pair(config_name, "mem"), 1)
    rows = np.concatenate([
        cpu[rng.integers(0, len(cpu), n)], mem[rng.integers(0, len(mem), n)],
        np.zeros((n, 1), np.int64),
    ], axis=1)
    penalty = np.zeros(n, bool)
    if mixed_terms:
        rows[:, 4] = rng.integers(0, 5, n) * (rng.random(n) < 0.33)
        penalty = rng.random(n) < 0.1
    count = int(Manifest().config(config_name)["job"]["task_groups"][0]["count"])
    hi, lo = _pair_scores(rows, count, penalty)
    assert np.array_equal(hi, (hi.astype(np.float64) + lo).astype(np.float32))
    want = near.float64_score(
        *near.exponentials(*rows[:, :4].T), rows[:, 4], count, penalty
    )
    assert np.max(np.abs(hi.astype(np.float64) + lo - want)) < 2.0**-44
    assert _ordered_as_float64(hi, lo, want) > n // 2
    # one float32 a score does not: it ties candidates float64 tells apart
    plain = near.plain_float32(
        *near.exponentials(*rows[:, :4].T), rows[:, 4], count, penalty
    )
    order = np.argsort(want, kind="stable")
    gap = np.diff(want[order])
    tied = (plain[order][:-1] >= plain[order][1:]) & (
        gap > want[order][1:] * 2.0**-40
    )
    assert tied.sum() > 100


def test_the_lattice_of_binpack_10k_holds_the_near_ties_the_issue_counted():
    sums, _where = near.lattice_sums(FLEETS["binpack-10k"])
    worse, better, gap = _near_ties_of("binpack-10k", 0)
    assert (len(sums), len(gap)) == (489448, 11420)
    # of the score; 18 times that of the sum of the exponentials
    assert 6.6e-9 < gap.min() and gap.max() < 1.2e-7
    assert np.all(worse[:, 4] == 0) and np.all(better[:, 4] == 0)


@pytest.mark.parametrize("collisions", [0, 1, 2], ids=["b", "c1", "c2"])
@pytest.mark.parametrize("config_name", sorted(FLEETS))
def test_the_pair_orders_every_near_tie_of_the_lattice_as_float64(
    config_name, collisions
):
    """(b), (c) below the kernel: over EVERY near-tie of the lattice
    the pair puts the better above the worse; the plain tail never."""
    count = int(Manifest().config(config_name)["job"]["task_groups"][0]["count"])
    worse, better, _gap = _near_ties_of(config_name, collisions, count)
    assert len(worse) > 100
    rows = np.concatenate([worse, better])
    hi, lo = _pair_scores(rows, count, np.zeros(len(rows), bool))
    k = len(worse)
    above = (hi[k:] > hi[:k]) | ((hi[k:] == hi[:k]) & (lo[k:] > lo[:k]))
    assert np.all(above), np.flatnonzero(~above)[:5]
    plain = near.plain_float32(
        *near.exponentials(*rows[:, :4].T), rows[:, 4], count
    )
    assert not np.any(plain[k:] > plain[:k])


ASK = (500, 256)  # cpu, memory of the benchmark's job


@functools.lru_cache(maxsize=None)
def _near_ties_of(fleet, collisions, count=10):
    return near.near_ties(FLEETS[fleet], collisions, count)


def _some_near_ties(n, collisions=0, fleet="binpack-10k"):
    """``n`` near-ties spread evenly over the lattice's, both nodes with
    room for the ask below their lattice point."""
    worse, better, _gap = _near_ties_of(fleet, collisions)
    roomy = np.flatnonzero(
        (worse[:, 0] >= ASK[0]) & (worse[:, 2] >= ASK[1])
        & (better[:, 0] >= ASK[0]) & (better[:, 2] >= ASK[1])
    )
    take = roomy[np.linspace(0, len(roomy) - 1, n).astype(int)]
    return worse[take], better[take]


def _near_tie_launch(worse, better, dtype=np.float32, first="worse"):
    """(args, kwargs) of one chained launch: an evaluation a near-tie,
    one pick each, its two nodes the only feasible ones of the arena
    and the walk long enough for both.  Node 2e is walked before node
    2e + 1."""
    k = len(worse)
    pair = (worse, better) if first == "worse" else (better, worse)
    nodes = np.stack(pair, 1).reshape(2 * k, 5)
    c = 2 * k
    feasible = np.zeros((k, 1, c), bool)
    feasible[np.arange(k), 0, 2 * np.arange(k)] = True
    feasible[np.arange(k), 0, 2 * np.arange(k) + 1] = True
    coll0 = np.broadcast_to(nodes[:, 4].astype(np.int32), (k, 1, c)).copy()
    stacked = ChainInputs(
        feasible=feasible,
        perm=np.tile(np.arange(c, dtype=np.int32), (k, 1)),
        ask_cpu=np.full((k, 1), ASK[0], dtype),
        ask_mem=np.full((k, 1), ASK[1], dtype),
        ask_disk=np.zeros((k, 1), dtype),
        desired_count=np.full((k, 1), 10, np.int32),
        limit=np.full((k, 1), 2, np.int32),
        distinct_hosts=np.zeros(k, bool),
        tg_idx=np.zeros((k, 1), np.int32),
    )
    args = (
        nodes[:, 1].astype(dtype), nodes[:, 3].astype(dtype),
        np.full(c, 1e5, dtype),
        (nodes[:, 0] - ASK[0]).astype(dtype),
        (nodes[:, 2] - ASK[1]).astype(dtype), np.zeros(c, dtype),
        stacked, np.full(k, c, np.int32), 1,
    )
    return args, {"coll0": coll0}


def _kernel_picks(worse, better, first="worse"):
    """(rows picked, whether ``lo`` decided) by the chained kernel at
    x64 off."""
    with jax.enable_x64(False):
        args, kwargs = _near_tie_launch(worse, better, first=first)
        rows, pulls = chained_plan_picks_cols(*args, **kwargs)
        pulls, decided = split_pulls(np.asarray(pulls))
    k = len(worse)
    # the walk draws up to the second of the evaluation's two nodes
    assert np.array_equal(pulls[:, 0], 2 * np.arange(k) + 2)
    return np.asarray(rows)[:, 0], decided[:, 0]


@pytest.mark.parametrize("collisions", [0, 1], ids=["b", "c"])
def test_the_chained_kernel_at_x64_off_picks_float64s_winner_of_a_near_tie(
    collisions,
):
    """(b), (c): the better node LATER in the walk.  One float32 a
    score ties the two (or, with the mean over two terms on one side,
    ranks them the other way), and the pick went to the earlier: this
    fails on a tree whose float32 trace scores with one float32."""
    worse, better = _some_near_ties(192, collisions)
    both = np.concatenate([worse, better])
    k = len(worse)
    want = near.float64_score(*near.exponentials(*both[:, :4].T), both[:, 4])
    plain = near.plain_float32(*near.exponentials(*both[:, :4].T), both[:, 4])
    assert np.all(want[k:] > want[:k]) and np.all(plain[k:] <= plain[:k])
    if collisions:
        assert np.any(worse[:, 4] > 0) and np.any(better[:, 4] > 0)
        assert np.any(plain[k:] < plain[:k])
    rows, decided = _kernel_picks(worse, better)
    assert np.array_equal(rows, 2 * np.arange(k) + 1)
    # lo decided wherever hi ties the two, a good share of the plain
    # tail's ties (a pair's hi is the score rounded ONCE to float32,
    # the plain tail's a rounding a step, so the two tie in different
    # places; the rest of these picks hi orders by itself)
    hi, _lo = _pair_scores(both, 10, np.zeros(2 * k, bool))
    assert np.array_equal(decided, hi[k:] == hi[:k])
    assert np.all(hi[k:] >= hi[:k]) and decided.mean() > 0.25
    # the better node first: it wins all the same, by the pair again
    rows, _decided = _kernel_picks(worse, better, first="better")
    assert np.array_equal(rows, 2 * np.arange(k))


def test_equal_candidates_still_tie_and_the_earlier_wins():
    worse, _better = _some_near_ties(64)
    rows, decided = _kernel_picks(worse, worse)
    assert np.array_equal(rows, 2 * np.arange(len(worse)))
    assert not decided.any()


def test_a_score_of_zero_or_less_is_skipped_on_the_pair_as_on_float64():
    """A node the job's own allocations crowd scores under 0 and is put
    aside (select.go's skip list): later in the order of emission, so
    of two equal candidates the crowded one loses its place."""
    cand = np.asarray([[7900, 7900, 16128, 16128, 0]], np.int64)
    crowded = cand + [0, 0, 0, 0, 9]  # binpack 1.0 - 10/10 over two terms
    want = near.float64_score(
        *near.exponentials(*np.concatenate([crowded, cand])[:, :4].T),
        np.asarray([9, 0]),
    )
    assert want[0] == 0.0 and want[1] == 1.0
    rows, decided = _kernel_picks(crowded, cand)
    assert rows.tolist() == [1] and not decided.any()


CHILD = os.path.join(repo_root(), "tests", "_float32_parity_child.py")


def _served_at_x64_off(config_name, nodes, jobs, seed, *more):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(
        [sys.executable, CHILD, config_name, str(nodes), str(jobs), str(seed),
         *more],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [3, 1000000007, 2**31 + 11])
@pytest.mark.parametrize(
    "config_name,nodes,jobs", [("binpack-10k", 400, 40), ("spread-5k", 150, 24)]
)
def test_the_chained_kernel_at_x64_off_places_as_the_reference_and_the_sequential_scheduler(
    config_name, nodes, jobs, seed
):
    line = _served_at_x64_off(config_name, nodes, jobs, seed)
    assert line["x64"] is False
    # every evaluation through the chained kernel, none down the host path
    assert line["prescored"] == jobs == line["jobs_compared"]
    assert line["differ_from_sequential"] == 0
    assert line["mismatched_placements"] == 0
    assert line["lost_or_duplicate"] == 0
    # the walk's counters: one pick a placement, a pull or more a pick
    assert line["walk_picks"] == line["placements"]
    assert line["walk_pulls"] >= line["walk_picks"]
    assert line["pair_decided_picks"] is not None


@pytest.mark.parametrize("seed", [7, 2**31 + 17])
def test_the_served_pipeline_at_x64_off_places_a_job_of_weighted_affinities_as_float64(
    seed,
):
    """(iv): the spread job with a second affinity, so that its
    affinity terms (35/95, 60/95) are no float32 and ride as pairs with
    the boost (desired 1.8 and 1.2): the served float32 pipeline places
    every allocation where the sequential scheduler and the plain
    reference (float64 both) place it."""
    line = _served_at_x64_off("spread-5k", 150, 24, seed, "weighted")
    assert line["x64"] is False
    assert line["prescored"] == 24 == line["jobs_compared"]
    assert line["differ_from_sequential"] == 0
    assert line["mismatched_placements"] == 0
    assert line["lost_or_duplicate"] == 0
    # the whole fleet a pick: 150 pulls each
    assert line["walk_picks"] == line["placements"] == 144
    assert line["walk_pulls"] == 144 * 150
    assert line["pair_decided_picks"] is not None


@pytest.mark.parametrize("seed", [5, 1000000009, 2**31 + 13])
def test_the_served_pipeline_at_x64_off_places_a_planted_near_tie_world_as_float64(
    seed,
):
    """(d): a fleet that is one near-tie of the lattice.  Every pick
    over fresh nodes meets it; the reference and the sequential
    scheduler (float64 both) give it to the better node wherever that
    sits in the walk, and so does the served float32 pipeline — by the
    ``lo`` half, as its counter says."""
    line = _served_at_x64_off("binpack-10k", 400, 40, seed, "planted")
    assert line["x64"] is False
    assert line["prescored"] == 40 == line["jobs_compared"]
    assert line["differ_from_sequential"] == 0
    assert line["mismatched_placements"] == 0
    assert line["lost_or_duplicate"] == 0
    assert line["walk_picks"] == line["placements"] == 400
    assert line["pair_decided_picks"] >= 10


# ---- the spread boost and the node affinity as pairs ---------------------

SPREAD_5K = Manifest().config("spread-5k")
SPREAD_COUNT = int(SPREAD_5K["job"]["task_groups"][0]["count"])
SPREAD_ASK = (300, 256)  # cpu, memory of the spread job
# a second stanza's and a second affinity's share of the weights: no
# float32, where the benchmark's own job has 60/60 and 35/35
OTHER_WEIGHTS = {"weight": 60.0 / 95.0, "affinity": (0.0, 35.0 / 95.0, 60.0 / 95.0)}


def _pair_boost(desired, used, weight):
    """``spread_contribution`` of one stanza at x64 off, its float64
    ``desired`` and ``weight`` brought as the launch brings them: the
    array narrowed, the low half beside it.  A node a value slot."""
    n = desired.size
    d_hi, d_lo = twofloat.split64(desired[None, :])
    w_hi, w_lo = twofloat.split64(np.asarray([weight]))
    onehot = np.zeros((1, n, 2), np.float32)
    onehot[:, :, 0] = 1.0
    zeros = np.zeros((1, 2), np.float32)

    def boost(d_hi, d_lo, w_hi, w_lo, existing):
        has = d_hi != 0
        return spread_contribution(
            onehot, (d_hi, d_lo), np.zeros((1, n), bool),
            (jnp.where(has, d_hi, 1.0), jnp.where(has, d_lo, 0.0)),
            existing, zeros, zeros, (w_hi, w_lo), np.ones(1, bool), None,
            jnp.float32,
        )

    with jax.enable_x64(False):
        fn = jax.jit(boost)
        for u in used:
            hi, lo = fn(
                d_hi, d_lo, w_hi, w_lo, np.asarray([[u, 0.0]], np.float32)
            )
            assert hi.dtype == lo.dtype == jnp.float32
            yield u, np.asarray(hi, np.float64) + np.asarray(lo, np.float64)


@pytest.mark.parametrize("total", [100, 95, 3], ids=lambda t: f"of{t}")
def test_the_pair_boost_is_float64s_on_the_grid_of_percents_counts_and_weights(
    total,
):
    """(i): percent 1-100 x count 1-64 x used 0-count x weight w / total.
    Held to 2^-44 of the larger of the boost and its weight: the pair
    holds `desired` to 2^-48 of ITSELF (48 of float64's 53 bits), so
    where `desired - used` cancels the error stays that absolute size
    — far below the float32 ulp of a score, 2^-24, which is what an
    ordering needs — and is not relative to the small difference."""
    percent, count = np.meshgrid(
        np.arange(1.0, 101.0), np.arange(1.0, 65.0), indexing="ij"
    )
    desired = ((percent / 100.0) * count).ravel()
    room = count.ravel()
    worst = 0.0
    for w in range(1, min(total, 100) + 1, 1 if total == 100 else 7):
        weight = float(w) / float(total)
        for used, got in _pair_boost(desired, range(0, 65), weight):
            want = near.spread_boost(np.float64, desired, used, weight)
            off = np.abs(got - want) / np.maximum(np.abs(want), weight)
            worst = max(worst, off[used <= room].max())
    assert worst < 2.0**-44, np.log2(worst)
    # one float32 a step misses the float64 boost by far more
    plain = near.spread_boost(np.float32, desired, 1, 60.0 / 95.0)
    want = near.spread_boost(np.float64, desired, 1, 60.0 / 95.0)
    assert np.max(np.abs(plain - want) / np.abs(want).clip(0.6)) > 2.0**-26


def test_the_pair_affinity_is_float64s_on_the_grid_of_weights():
    """(i), the affinity: matched / total for every matched weight
    -100 … 100 of totals 1 … 200, as the host splits it."""
    matched, total = np.meshgrid(
        np.arange(-100.0, 101.0), np.arange(1.0, 201.0), indexing="ij"
    )
    want = (matched / total).ravel()
    hi, lo = twofloat.split64(want)
    assert hi.dtype == lo.dtype == np.float32
    assert np.array_equal(hi, want.astype(np.float32))
    # 48 of float64's 53 bits: 2^-48 of the term, and exact at 0
    off = np.abs(hi.astype(np.float64) + lo - want)
    assert np.all(off <= np.abs(want) * 2.0**-48)
    assert np.all(lo[want == 0.0] == 0.0)
    assert np.mean(lo != 0.0) > 0.9  # most weights are no float32


@functools.lru_cache(maxsize=None)
def _spread_ties(other_weights=False):
    return near.spread_near_ties(
        SPREAD_5K, **(OTHER_WEIGHTS if other_weights else {})
    )


def _spread_terms(other_weights=False):
    desired, weight, affinity = near.job_terms(SPREAD_5K)
    if other_weights:
        weight = OTHER_WEIGHTS["weight"]
        affinity = np.asarray(OTHER_WEIGHTS["affinity"])
    return desired, weight, affinity


def _spread_tie_launch(
    worse, better, terms, pairs=True, first="worse", dtype=np.float32
):
    """(args, kwargs) of one chained launch over near-ties of the
    spread job: an evaluation a near-tie, one pick each, its two nodes
    (of different datacenters) the only feasible ones and the limit
    lifted as a spread lifts it.  A value slot of the stanza is a
    (datacenter, allocations of the job there so far) of the lattice,
    so that one use map gives every node its own count.  ``pairs``
    brings the low halves as the batch worker does at float32; without
    them the launch is the one-float32-a-term trace of before."""
    desired, weight, affinity = terms
    k = len(worse)
    pair = (worse, better) if first == "worse" else (better, worse)
    nodes = np.stack(pair, 1).reshape(2 * k, 7)
    c = 2 * k
    at = np.arange(k)
    feasible = np.zeros((k, 1, c), bool)
    feasible[at, 0, 2 * at] = True
    feasible[at, 0, 2 * at + 1] = True
    stacked = ChainInputs(
        feasible=feasible,
        perm=np.tile(np.arange(c, dtype=np.int32), (k, 1)),
        ask_cpu=np.full((k, 1), SPREAD_ASK[0], dtype),
        ask_mem=np.full((k, 1), SPREAD_ASK[1], dtype),
        ask_disk=np.zeros((k, 1), dtype),
        desired_count=np.full((k, 1), SPREAD_COUNT, np.int32),
        limit=np.full((k, 1), 2**31 - 1, np.int32),
        distinct_hosts=np.zeros(k, bool),
        tg_idx=np.zeros((k, 1), np.int32),
    )
    slots = len(desired) * SPREAD_COUNT
    v1 = 32
    assert slots < v1
    slot_dc, slot_used = np.divmod(np.arange(slots), SPREAD_COUNT)
    s_desired = np.zeros((k, 1, v1))
    s_desired[:, 0, :slots] = desired[slot_dc]
    s_used0 = np.zeros((k, 1, v1))
    s_used0[:, 0, :slots] = slot_used
    s_weight = np.full((k, 1), weight)
    codes = (nodes[:, 5] * SPREAD_COUNT + nodes[:, 6]).astype(np.int32)
    aff = np.broadcast_to(affinity[nodes[:, 5]], (k, 1, c)).copy()
    spread = SpreadInputs(
        codes=np.broadcast_to(codes, (k, 1, c)).copy(),
        desired=s_desired, used0=s_used0,
        proposed0=np.zeros((k, 1, v1)), cleared0=np.zeros((k, 1, v1)),
        weight=s_weight, active=np.ones((k, 1), bool),
    )
    kwargs = {"affinity": aff, "spread": spread}
    if pairs:
        kwargs["affinity_lo"] = twofloat.split64(aff)[1]
        kwargs["spread"] = spread._replace(
            desired_lo=twofloat.split64(s_desired)[1],
            weight_lo=twofloat.split64(s_weight)[1],
        )
    args = (
        nodes[:, 1].astype(dtype), nodes[:, 3].astype(dtype),
        np.full(c, 1e5, dtype),
        (nodes[:, 0] - SPREAD_ASK[0]).astype(dtype),
        (nodes[:, 2] - SPREAD_ASK[1]).astype(dtype), np.zeros(c, dtype),
        stacked, np.full(k, c, np.int32), 1,
    )
    return args, kwargs


def _roomy(worse, better):
    """The near-ties whose two nodes hold the ask below their lattice
    point."""
    return np.flatnonzero(
        np.all(worse[:, [0, 2]] >= SPREAD_ASK, 1)
        & np.all(better[:, [0, 2]] >= SPREAD_ASK, 1)
    )


def spread_tie_picks(worse, better, terms, width=1024, **how):
    """Rows the chained kernel picks at x64 off over the near-ties, in
    launches of ``width`` evaluations of one shape (the last one padded
    with its own first rows), and whether ``lo`` decided.  Also what
    the chip's comparison of ISSUE 38 (c) calls."""
    rows, decided = [], []
    with jax.enable_x64(False):
        for a in range(0, len(worse), width):
            take = (a + np.arange(width)) % len(worse)
            args, kwargs = _spread_tie_launch(
                worse[take], better[take], terms, **how
            )
            got, pulls = chained_plan_picks_cols(*args, **kwargs)
            pulls, flags = split_pulls(np.asarray(pulls))
            # a spread lifts the limit: the walk draws the whole ring
            assert np.all(pulls == 2 * width)
            rows.append(np.asarray(got)[:, 0] - 2 * np.arange(width))
            decided.append(flags[:, 0])
    n = len(worse)
    return np.concatenate(rows)[:n], np.concatenate(decided)[:n]


@pytest.mark.parametrize("other_weights", [False, True], ids=["job", "weights"])
def test_the_chained_kernel_at_x64_off_orders_spread_near_ties_of_two_datacenters_as_float64(
    other_weights,
):
    """(ii): candidates of different datacenters whose float64 means
    over three or four terms differ by less than a float32 ulp.  With
    the boost and the affinity as pairs the kernel gives each to
    float64's winner, wherever it sits in the walk; the same launch
    without the low halves — one float32 a term, the trace of before —
    does not.  ``weights``: shares of the weights that are no float32,
    so the product and the affinity's low half count too."""
    worse, better, gap = _spread_ties(other_weights)
    terms = _spread_terms(other_weights)
    assert len(gap) > 20000 and gap.max() < np.spacing(np.float32(1.0))
    assert np.all(worse[:, 5] != better[:, 5])
    roomy = _roomy(worse, better)
    take = roomy[np.linspace(0, len(roomy) - 1, 1024).astype(int)]
    worse, better = worse[take], better[take]
    picked, decided = spread_tie_picks(worse, better, terms)
    assert np.array_equal(picked, np.ones(len(take), int))
    picked_first, _d = spread_tie_picks(worse, better, terms, first="better")
    assert np.array_equal(picked_first, np.zeros(len(take), int))
    assert decided.any()
    # the control: the worse node walked first wins many of them
    control, flagged = spread_tie_picks(worse, better, terms, pairs=False)
    assert np.mean(control == 0) > 0.25
    # ... and, walked second, takes some it should not have at all
    swapped, _d = spread_tie_picks(
        worse, better, terms, pairs=False, first="better"
    )
    assert np.any(swapped == 1)


def test_equal_candidates_of_a_spread_still_tie_and_the_earlier_wins():
    """(iii): the same lattice point, datacenter and use count twice."""
    worse, better, _gap = _spread_ties()
    take = _roomy(worse, better)[:: len(worse) // 64][:64]
    for cand in (worse[take], better[take]):
        picked, decided = spread_tie_picks(
            cand, cand, _spread_terms(), width=64
        )
        assert np.array_equal(picked, np.zeros(len(take), int))
        assert not decided.any()


def _before_spread_contribution(
    onehot, desired_node, penalty_node, safe_desired,
    existing, prop, clr, weight, active, even, dtype,
):
    """``spread_contribution`` as ops/batch.py held it before the boost
    of a float32 trace became a pair, line for line."""
    clr_adj = clr - jnp.where((prop > 0) & (clr > 1), 1.0, 0.0)
    combined = jnp.maximum(0.0, existing + prop - clr_adj)
    used_node = jnp.einsum("scv,sv->sc", onehot, combined)
    frac = (desired_node - (used_node + 1.0)) / safe_desired
    pct_contrib = frac * weight[:, None]
    pct_full = jnp.where(
        penalty_node, jnp.asarray(-1.0, dtype), pct_contrib
    )
    if even is not None:
        V1 = combined.shape[-1]
        value_slot = jnp.arange(V1) < (V1 - 1)
        present = ((existing + prop) > 0) & value_slot
        has_map = present.any(axis=-1)
        big = jnp.asarray(jnp.inf, dtype)
        min_c = jnp.min(jnp.where(present, combined, big), axis=-1)
        max_c = jnp.max(jnp.where(present, combined, -big), axis=-1)
        min_b = min_c[:, None]
        max_b = max_c[:, None]
        safe_min = jnp.where(min_b > 0, min_b, 1.0)
        delta_boost = jnp.where(
            min_b == 0.0, -1.0, (min_b - used_node) / safe_min
        )
        even_val = jnp.where(
            used_node != min_b,
            delta_boost,
            jnp.where(
                min_b == max_b,
                -1.0,
                jnp.where(
                    min_b == 0.0, 1.0, (max_b - min_b) / safe_min
                ),
            ),
        )
        even_full = jnp.where(
            has_map[:, None],
            jnp.where(
                penalty_node, jnp.asarray(-1.0, dtype), even_val
            ),
            0.0,
        )
        contrib = jnp.where(even[:, None], even_full, pct_full)
    else:
        contrib = pct_full
    contrib = jnp.where(active[:, None], contrib, 0.0)
    return jnp.sum(contrib, axis=0)


def _spread_scope_args(dtype, even):
    s, c, v1 = 2, 12, 4
    codes = (jnp.arange(s * c).reshape(s, c) * 7) % v1
    desired = jnp.asarray([[3.0, 1.8, 1.2, 0.0], [0.0, 0.0, 0.0, 0.0]], dtype)
    desired_node = jnp.take_along_axis(desired, codes, axis=1)
    used = jnp.asarray([[2.0, 0.0, 1.0, 0.0], [1.0, 3.0, 0.0, 0.0]], dtype)
    return (
        jax.nn.one_hot(codes, v1, dtype=dtype), desired_node,
        codes == v1 - 1, jnp.where(desired_node != 0, desired_node, 1.0),
        used, used * 0.5, used * 0.0, jnp.asarray([60.0 / 95.0, 0.0], dtype),
        jnp.asarray([True, True]),
        jnp.asarray([False, True]) if even else None,
    )


@pytest.mark.parametrize("even", [False, True], ids=["percent", "even"])
def test_at_x64_on_the_spread_scope_lowers_to_the_float64_trace_of_before(even):
    """(v): with one array a term — every float64 launch, and a float32
    one that brings no low halves (the sharded planner's) — the spread
    scope is the code it was, operation for operation."""
    for dtype in (jnp.float64, jnp.float32):
        args = _spread_scope_args(dtype, even)

        def now(*a):
            return spread_contribution(*a, dtype)

        def before(*a):
            return _before_spread_contribution(*a, dtype)

        text = _lowered(now, *args)
        assert text.replace("jit_now", "jit_before") == _lowered(before, *args)
        assert "bitcast" not in text


# sha256 (first 16 digits) and line count of the chained kernel's lowered
# text on the commit before the boost and the affinity became pairs
# (610342c), taken with the JAX named here: what "the launch lowers to
# what it lowered to" is held against.  Another JAX lowers both trees
# differently, and the comparison then says nothing.
LOWERED_WITH_JAX = "0.9.0"
LOWERED_BEFORE = {
    "float64, spread and affinity": ("b8bbd381b49b8f7a", 970),
    "float32, spread and affinity, no low halves": ("e0898ab902222764", 2029),
    "float32, neither": ("061771afc3a33a9b", 1893),
}


@pytest.mark.parametrize("launch", sorted(LOWERED_BEFORE))
def test_the_chained_kernel_lowers_to_what_it_did_where_no_low_half_comes(launch):
    """(v): at x64 on the launch of a spread-and-affinity chunk — same
    signature, same transfers — and at x64 off a chunk none of whose
    jobs has a spread or an affinity (``binpack-10k.deploy``'s), or
    one whose launch brings no low halves, trace the program they
    traced; only the low halves turn the two terms into pairs."""
    import hashlib

    if jax.__version__ != LOWERED_WITH_JAX:
        pytest.skip(f"recorded with JAX {LOWERED_WITH_JAX}")
    worse, better, _gap = _spread_ties()
    worse, better = worse[:4], better[:4]
    terms = _spread_terms()
    if launch.startswith("float64"):
        args, kwargs = _spread_tie_launch(
            worse, better, terms, pairs=False, dtype=np.float64
        )
        text = chained_plan_picks_cols.lower(*args, **kwargs).as_text()
        assert "bitcast" not in text and "1073741824" not in text
    else:
        with jax.enable_x64(False):
            if launch.endswith("neither"):
                args, kwargs = _near_tie_launch(*_some_near_ties(4))
            else:
                args, kwargs = _spread_tie_launch(
                    worse, better, terms, pairs=False
                )
            text = chained_plan_picks_cols.lower(*args, **kwargs).as_text()
            args, kwargs = _spread_tie_launch(worse, better, terms)
            paired = chained_plan_picks_cols.lower(*args, **kwargs).as_text()
        assert "f64" not in text
        # the pairs are there when the low halves are
        assert len(paired.splitlines()) > len(text.splitlines()) + 100
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert (digest, len(text.splitlines())) == LOWERED_BEFORE[launch]


@pytest.mark.parametrize("seed", [38, 2**31 + 38])
def test_the_pair_boost_of_the_even_mode_and_of_mixed_stanzas_is_float64s(seed):
    """The even mode's boosts (spread.go:178: quotients of whole use
    counts) ride the same quotient as the percent mode's, and stanzas
    sum as pairs: over seeded use maps with cleared and proposed uses,
    percent and even stanzas mixed, padding and the penalty slot, the
    pair is the float64 array path's sum to 2^-44 of the larger of it
    and 1 (three terms of at most 2^-46 each)."""
    rng = np.random.default_rng(seed)
    s, c, v1, draws = 4, 96, 8, 40
    worst, seen_even = 0.0, 0

    def pairs(onehot, codes, d_hi, d_lo, used, prop, clr, w_hi, w_lo, active, even):
        def at_node(t):
            return jnp.sum(jnp.where(onehot != 0, t[:, None, :], 0.0), -1)

        d = (at_node(d_hi), at_node(d_lo))
        has = d[0] != 0
        return spread_contribution(
            onehot, d, codes == v1 - 1,
            (jnp.where(has, d[0], 1.0), jnp.where(has, d[1], 0.0)),
            used, prop, clr, (w_hi, w_lo), active, even, jnp.float32,
        )

    def arrays(onehot, codes, desired, used, prop, clr, weight, active, even):
        d = jnp.einsum("scv,sv->sc", onehot, desired)
        return spread_contribution(
            onehot, d, codes == v1 - 1, jnp.where(d != 0, d, 1.0),
            used, prop, clr, weight, active, even, jnp.float64,
        )

    for _ in range(draws):
        codes = rng.integers(0, v1, (s, c))
        percent = rng.integers(1, 100, (s, v1)).astype(np.float64)
        desired = (percent / 100.0) * float(rng.integers(1, 65))
        desired[:, -1] = 0.0
        used = rng.integers(0, 10, (s, v1)).astype(np.float64)
        used[:, -1] = 0.0
        used[rng.integers(0, s)] *= rng.integers(0, 2)  # an empty use map
        prop = rng.integers(0, 3, (s, v1)) * (rng.random((s, v1)) < 0.3)
        clr = rng.integers(0, 4, (s, v1)) * (rng.random((s, v1)) < 0.4)
        prop[:, -1] = clr[:, -1] = 0
        even = np.asarray([False, True, True, False])
        weight = np.where(even, 0.0, rng.integers(1, 100, s) / 137.0)
        active = np.asarray([True, True, True, rng.random() < 0.5])
        want = np.asarray(arrays(
            jax.nn.one_hot(codes, v1, dtype=jnp.float64), jnp.asarray(codes),
            desired, used, prop.astype(np.float64), clr.astype(np.float64),
            weight, active, even,
        ))
        with jax.enable_x64(False):
            d_hi, d_lo = twofloat.split64(desired)
            w_hi, w_lo = twofloat.split64(weight)
            hi, lo = jax.jit(pairs)(
                jax.nn.one_hot(codes, v1, dtype=jnp.float32), codes, d_hi, d_lo,
                used.astype(np.float32), prop.astype(np.float32),
                clr.astype(np.float32), w_hi, w_lo, active, even,
            )
            assert hi.dtype == lo.dtype == jnp.float32
        got = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
        assert np.array_equal(np.asarray(hi), got.astype(np.float32))
        worst = max(worst, np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))
        seen_even += int(np.sum(want != 0.0))
    assert worst < 2.0**-44, np.log2(worst)
    assert seen_even > draws * c // 2


def test_the_pair_arithmetic_of_the_boost_stays_in_the_spread_scope():
    """The phases of a pick-step carry ``jax.named_scope`` names, which
    a device profile puts its operations to.  A pair's exact products
    split a float32 by a bit mask (``bitcast_convert_type``), so those
    operations mark where pair arithmetic runs: the boost's under
    ``spread``, the affinity's and the rest of the score's under
    ``score``, none anywhere else; without the low halves the spread
    scope holds none."""
    import re

    worse, better, _gap = _spread_ties()
    terms = _spread_terms()

    def scopes_of_the_splits(pairs):
        with jax.enable_x64(False):
            args, kwargs = _spread_tie_launch(
                worse[:4], better[:4], terms, pairs=pairs
            )
            text = chained_plan_picks_cols.lower(*args, **kwargs).as_text(
                debug_info=True
            )
        named = re.findall(r'= loc\("([a-z_]+)/bitcast_convert_type"', text)
        assert named and len(named) == len(
            re.findall(r'= loc\("[^"]*bitcast_convert_type"', text)
        )
        return {scope: named.count(scope) for scope in set(named)}

    before, now = scopes_of_the_splits(False), scopes_of_the_splits(True)
    assert set(before) == {"score"} and set(now) == {"score", "spread"}
    assert now["spread"] >= 8 and now["score"] == before["score"]
