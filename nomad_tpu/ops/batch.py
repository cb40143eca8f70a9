"""Batched placement kernels: the (candidate-nodes x placements) score
matrix of BASELINE.json's north star.

`plan_picks` runs P sequential placements of one task group entirely on
device: a `lax.scan` where each step scores all nodes, emulates the
reference's rotating limited-walk selection (ops/score.py semantics),
picks the winner, and scatters the plan delta (proposed usage +
anti-affinity collision + optional distinct-hosts exclusion) before the
next step — the "stateful within an eval" scoring the reference gets from
`ProposedAllocs` (scheduler/context.go:120), expressed as in-kernel
updates instead of re-walking allocation lists.

`batch_plan_picks` vmaps that over E independent evaluations sharing the
node table — the optimistic-concurrency analog of the reference's
parallel scheduling workers (scheduler/scheduler.go:46): evals in a batch
do not see each other's placements; the serialized plan applier resolves
conflicts exactly as it does for the reference's workers.

Scope: the scan path covers binpack/spread fitness, job anti-affinity,
rescheduling penalties, node affinities and distinct_hosts.  Spread
stanzas change per-value use counts between picks and currently route
through the per-pick kernel in tpu_stack (exact, host-looped); an
in-kernel vocab-count carry is the planned extension.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import twofloat
from .score import (
    MAX_SKIP,
    NO_NODE,
    ScoreList,
    earliest_best,
    pair_hi,
    under_threshold,
)


# A float32 trace compares scores as pairs (score.py ScoreList) and
# flags, in this bit of a pick's pull count, that the pair's `lo` half
# chose the winner: the pick one float32 a score would have given to an
# earlier node.  The float64 trace never sets it.
PAIR_DECIDED = 1 << 30


def split_pulls(pulls):
    """(pull counts, `lo`-decided flags) of the `pulls` a kernel of
    this module hands back; numpy or jax arrays."""
    return pulls & (PAIR_DECIDED - 1), pulls >= PAIR_DECIDED


def pow2_bucket(n: int, floor: int = 1) -> int:
    """Next power of two >= n: launch-shape bucketing so jit traces
    stay cached across varying pick/row counts."""
    v = max(floor, 1)
    while v < n:
        v *= 2
    return v


class SpreadInputs(NamedTuple):
    """Percent-target spread state for the in-kernel carry (reference
    spread.go:163 boost; the use counts that shift between picks are a
    small per-value vector updated by one-hot scatter each step).

    Shapes: S spread stanzas x (V+1) value slots; slot V is the penalty
    slot (missing attribute, or value with no target and no implicit
    "*") scoring a flat -1.0.  Even-spread mode (spread.go:178) stays on
    the exact host path.

    The per-pick used count reproduces propertySet.GetCombinedUseMap
    (reference propertyset.go): used = max(0, existing + proposed -
    cleared'), where `existing0` counts the job's live allocs at the
    snapshot, `proposed` starts at `proposed0` — in-place/attribute
    updates enter plan.NodeAllocation before any select, so the
    reference counts those allocs BOTH as existing and as proposed —
    and accumulates in-kernel placements, `cleared` starts at
    `cleared0` (plan stops staged before the first pick) and grows as
    per-pick destructive evictions land, and cleared' applies the
    PopulateProposed quirk — a value with both proposed and cleared>1
    counts one fewer cleared."""

    codes: jnp.ndarray  # i32[S, C] value slot per node (V = penalty)
    desired: jnp.ndarray  # f[S, V+1] desired count per slot
    used0: jnp.ndarray  # f[S, V+1] existing (live) use at snapshot
    proposed0: jnp.ndarray  # f[S, V+1] plan placements staged pre-pick
    cleared0: jnp.ndarray  # f[S, V+1] pre-staged plan stops per slot
    weight: jnp.ndarray  # f[S] weight / sum(|weights|)
    active: jnp.ndarray  # bool[S] (padding rows are inert)
    # even-spread mode (no targets, reference spread.go:178): min/max
    # balance boost over the observed use map, UNWEIGHTED (the oracle
    # adds evenSpreadScoreBoost without the weight fraction)
    even: jnp.ndarray = None  # bool[S]
    # owning group slot per stanza (propertysets are GROUP-scoped —
    # propertyset.py:151 filters to one task group): pick k of group t
    # scores with and updates ONLY slots where group == t.  None (the
    # single-group trace) means every slot applies to every pick.
    group: jnp.ndarray = None  # i32[S]
    # float32 trace only: what narrowing the host's float64 `desired`
    # and `weight` to float32 drops (twofloat.split64).  With them the
    # boost is a pair of float32s and the float64 definition's to the
    # pair; None (every float64 launch) leaves the boost one array.
    desired_lo: jnp.ndarray = None  # f32[S, V+1]
    weight_lo: jnp.ndarray = None  # f32[S]


class TGInputs(NamedTuple):
    """Per-pick task-group routing for multi-task-group evals.

    The sequential scheduler iterates every task group's placements
    within ONE eval (reference generic_sched.go:468 computePlacements:
    destructive updates then places, each carrying its own task
    group), with the stack's rotating walk offset persisting across
    groups and failure coalescing applying PER GROUP.  The kernel
    models that with per-pick routing: pick k selects group slot
    ``tg_idx[k]``'s feasibility/affinity/collision columns and its own
    ask/limit scalars, while the walk offset and usage columns stay a
    single carry.  Single-task-group callers normalize to T=1 with
    tg_idx==0 — the arithmetic is identical to the historical
    single-group kernel."""

    tg_idx: jnp.ndarray  # i32[P] group slot per pick
    feasible: jnp.ndarray  # bool[T, C] static feasibility per group
    affinity: jnp.ndarray  # f[T, C]
    coll0: jnp.ndarray  # i32[T, C] anti-affinity base per group
    ask_cpu: jnp.ndarray  # f[P] per-pick resource ask
    ask_mem: jnp.ndarray  # f[P]
    ask_disk: jnp.ndarray  # f[P]
    desired_count: jnp.ndarray  # i32[P] group count for anti score
    limit: jnp.ndarray  # i32[P] walk visit limit per pick
    # float32 trace only: the low half of `affinity` (twofloat.split64)
    affinity_lo: jnp.ndarray = None  # f32[T, C]


class PortInputs(NamedTuple):
    """Static (reserved) host-port occupancy for the chain.

    The reference's binpack skips a port-collided node WITHOUT
    consuming a walk-limit slot (rank.go network path `continue`) —
    identical to an infeasible node in the walk arithmetic, so the
    kernel folds collision into the per-pick feasibility mask.  The Q
    axis enumerates the distinct static ports asked across the batch;
    occupancy chains across evals like the usage columns (a placement
    with static ports blocks those ports for every later pick/eval).
    Port RELEASES (stops/evictions freeing an asked port) are gated to
    the sequential path host-side — modeling only occupation keeps the
    carry monotone and exact for everything admitted."""

    ask: jnp.ndarray  # bool[T, Q] port slots this group's ask needs
    used0: jnp.ndarray  # bool[Q, C] occupied at snapshot (node space)


class DeviceInputs(NamedTuple):
    """Device-capacity accounting for the chain (SURVEY §7.3:
    capacity-count masks on device, exact host-side assignment).

    The D axis enumerates the batch's distinct device-ask signatures
    (each = a set of matching device-group codes).  Free instance
    counts chain across evals like usage columns; a pick is feasible
    only where every asked signature has enough free instances, and
    the winner consumes its group's asked counts.  Pooled counting is
    exact because the host admits only batches whose signatures are
    identical-or-disjoint (overlapping-but-different matched sets gate
    to the sequential path), and instance releases (evictions freeing
    asked devices) cut the chain host-side — the carry is monotone."""

    ask: jnp.ndarray  # i32[T, D] instances needed per signature
    free0: jnp.ndarray  # i32[D, C] free instances at snapshot


def spread_contribution(
    onehot, desired_node, penalty_node, safe_desired,
    existing, prop, clr, weight, active, even, dtype,
):
    """Per-node spread score contribution for one pick — THE single
    implementation shared by the unsharded step and the sharded
    (shard_map) planner so the two can never drift (the parity
    contract between them is bit-identity).  All inputs are in the
    caller's node layout (permuted or shard-local); `existing/prop/
    clr` are the replicated (S, V+1) carries; `even` is None when no
    stanza uses even mode (skips tracing the min/max block).

    Reproduces GetCombinedUseMap incl. the PopulateProposed
    cleared-decrement quirk and spread.py's boost order (empty use
    map short-circuits BEFORE the missing-attribute penalty).

    On a float32 trace whose launch brought the low halves of the
    float64 `desired` and `weight`, `desired_node`, `safe_desired` and
    `weight` are pairs ``(hi, lo)`` (ops/twofloat.py) and so is the
    sum handed back (`_spread_pairs`); the use counts are whole
    numbers and one array either way."""
    clr_adj = clr - jnp.where((prop > 0) & (clr > 1), 1.0, 0.0)
    combined = jnp.maximum(0.0, existing + prop - clr_adj)
    used_node = jnp.einsum("scv,sv->sc", onehot, combined)
    if isinstance(weight, tuple):
        return _spread_pairs(
            desired_node, penalty_node, safe_desired, existing, prop,
            combined, used_node, weight, active, even,
        )
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


def _spread_pairs(
    desired_node, penalty_node, safe_desired, existing, prop,
    combined, used_node, weight, active, even,
):
    """`spread_contribution` with the boost as a pair of float32s:
    ``(desired - (used + 1)) / desired * weight`` with `desired` and
    `weight` the float64 inputs to the pair, the difference, the
    quotient and the product each to about 2^-46, and the stanzas
    summed as pairs in the definition's order.  The even mode's boosts
    are quotients of whole numbers and ride the same call of the
    quotient (a launch shape's compile time is in its count of
    operations); the masks and the -1.0 of the penalty slot are the
    array path's."""
    above = twofloat.add_f(desired_node, -(used_node + 1.0))
    below = safe_desired
    if even is not None:
        V1 = combined.shape[-1]
        value_slot = jnp.arange(V1) < (V1 - 1)
        present = ((existing + prop) > 0) & value_slot
        has_map = present.any(axis=-1)
        big = jnp.asarray(jnp.inf, combined.dtype)
        min_b = jnp.min(
            jnp.where(present, combined, big), axis=-1, keepdims=True
        )
        max_b = jnp.max(
            jnp.where(present, combined, -big), axis=-1, keepdims=True
        )
        behind = used_node != min_b
        # the cases that are no quotient: -1 behind an unused value or
        # where every value is used alike, +1 on an unused value beside
        # used ones (spread.py even_spread_score_boost)
        flat = jnp.where(
            behind,
            jnp.where(min_b == 0.0, -1.0, 0.0),
            jnp.where(
                min_b == max_b, -1.0, jnp.where(min_b == 0.0, 1.0, 0.0)
            ),
        )
        even_above = jnp.where(
            flat != 0.0, 0.0,
            jnp.where(behind, min_b - used_node, max_b - min_b),
        )
        even_below = jnp.broadcast_to(
            jnp.where(min_b > 0, min_b, 1.0), even_above.shape
        )
        # a stanza with no use map yet reads (inf, -inf) here: its
        # lanes are dropped below and must not reach the quotient
        on = has_map[:, None]
        above = (
            jnp.stack([above[0], jnp.where(on, even_above, 0.0)]),
            jnp.stack([above[1], jnp.zeros_like(even_above)]),
        )
        below = (
            jnp.stack([below[0], jnp.where(on, even_below, 1.0)]),
            jnp.stack([below[1], jnp.zeros_like(even_below)]),
        )
    frac = twofloat.quotient(above, below)
    if even is not None:
        even_val = twofloat.add_f((frac[0][1], frac[1][1]), flat)
        frac = (frac[0][0], frac[1][0])
    pct = twofloat.mul(frac, (weight[0][:, None], weight[1][:, None]))
    hi = jnp.where(penalty_node, -1.0, pct[0])
    lo = jnp.where(penalty_node, 0.0, pct[1])
    if even is not None:
        # even boosts carry no weight; a stanza in even mode with no
        # use map scores 0, the penalty slot -1 otherwise
        even_hi = jnp.where(penalty_node, -1.0, even_val[0])
        even_lo = jnp.where(penalty_node, 0.0, even_val[1])
        hi = jnp.where(even[:, None], jnp.where(on, even_hi, 0.0), hi)
        lo = jnp.where(even[:, None], jnp.where(on, even_lo, 0.0), lo)
    hi = jnp.where(active[:, None], hi, 0.0)
    lo = jnp.where(active[:, None], lo, 0.0)
    total = (hi[0], lo[0])
    for s in range(1, hi.shape[0]):
        total = twofloat.add(total, (hi[s], lo[s]))
    return total


class StepDeltas(NamedTuple):
    """Per-pick plan mutations for steady-state evals (leading axis E
    when chained).  The sequential path interleaves plan edits with
    selects inside computePlacements (generic_sched.go:468): a
    destructive update stops its previous alloc *just before* its
    replacement is scored, and each reschedule penalizes the nodes in
    its own previous alloc's history (generic_sched.go:642
    getSelectOptions).  These are those edits, expressed as in-kernel
    deltas applied at the top of pick k's scan step."""

    evict_rows: jnp.ndarray  # i32[P] node row stopped before pick k (-1 none)
    evict_cpu: jnp.ndarray  # f[P] signed usage delta (negative)
    evict_mem: jnp.ndarray  # f[P]
    evict_disk: jnp.ndarray  # f[P]
    evict_coll: jnp.ndarray  # i32[P] anti-affinity collision delta
    penalty_rows: jnp.ndarray  # i32[P, K] penalized node rows (-1 pad)


class PreDeltas(NamedTuple):
    """Per-eval pre-placement plan state (leading axis E when chained):
    usage freed by lost/stopped allocs and shifted by in-place updates,
    applied to the chained usage columns before the eval's first pick —
    the plan-eviction half of ProposedAllocs (context.go:120).  Rows are
    padded with row 0 / delta 0."""

    rows: jnp.ndarray  # i32[R]
    cpu: jnp.ndarray  # f[R] signed deltas
    mem: jnp.ndarray  # f[R]
    disk: jnp.ndarray  # f[R]


class BatchInputs(NamedTuple):
    """Per-eval inputs (leading axis E when vmapped); node columns are
    shared."""

    feasible: jnp.ndarray  # bool[C] static feasibility for this (job, tg)
    base_cpu_used: jnp.ndarray  # f[C] usage at snapshot
    base_mem_used: jnp.ndarray  # f[C]
    base_disk_used: jnp.ndarray  # f[C]
    base_collisions: jnp.ndarray  # i32[C] existing same-job+tg allocs
    penalty: jnp.ndarray  # bool[C]
    affinity_score: jnp.ndarray  # f[C]
    perm: jnp.ndarray  # i32[C] shuffled walk order
    ask_cpu: jnp.ndarray  # f scalar
    ask_mem: jnp.ndarray  # f scalar
    ask_disk: jnp.ndarray  # f scalar
    desired_count: jnp.ndarray  # i32
    limit: jnp.ndarray  # i32
    distinct_hosts: jnp.ndarray  # bool scalar


def _rotated_prefix(cs, c_off, total, in_wrap, is_tail):
    """Inclusive count of set entries at-or-before each position in
    *walk order*, from the inclusive permuted-order cumsum `cs`.

    Walk order is the permuted order rotated left by `offset` within
    the candidate region; `in_wrap` marks positions < offset (they walk
    after the pre-wrap segment), `is_tail` the padding region past
    n_candidates (never rotated, walks last, and carries no set
    entries)."""
    pre = jnp.where(in_wrap, cs + (total - c_off), cs - c_off)
    return jnp.where(is_tail, total, pre)


def _walk(s_p, f_p, offset, limit, n_candidates):
    """The reference's rotating limited-walk selection, evaluated
    entirely in permuted space (no per-step gathers — the rotation is
    closed-form prefix arithmetic; see ops/score.py for the walk
    semantics being emulated).  `s_p`/`f_p` are score/feasibility in
    permuted order; `s_p` is one array on the float64 trace and a pair
    `(hi, lo)` on the float32 trace, compared `hi` first.  Returns
    (win_pos, any_emitted, pulls, decided) where win_pos indexes the
    permuted arrays and `decided` says that `lo` chose the winner
    (score.py earliest_best; None on the float64 trace)."""
    n = f_p.shape[0]
    # int32 throughout: under x64 a default arange is int64, which
    # would promote `pulls` and break the int32 offset scan carry
    pos = jnp.arange(n, dtype=jnp.int32)
    is_tail = pos >= n_candidates
    in_wrap = pos < offset
    # walk position of each permuted index (tail walks last, in place)
    wp = jnp.where(
        is_tail, pos, jnp.mod(pos - offset + n_candidates, n_candidates)
    )

    def rot(b):
        # b has no support in the tail (every mask is ANDed with f_p),
        # so the full-array total equals the candidate-region total
        cs = jnp.cumsum(b.astype(jnp.int32))
        total = cs[-1]
        c_off = jnp.where(offset > 0, cs[offset - 1], 0)
        return (
            _rotated_prefix(cs, c_off, total, in_wrap, is_tail), total
        )

    bad = f_p & under_threshold(s_p)
    bad_rank, _ = rot(bad)
    diverted = bad & (bad_rank <= MAX_SKIP)
    nd = f_p & ~diverted
    nd_incl, nd_count = rot(nd)
    div_incl, n_div = rot(diverted)
    div_rank = div_incl - 1
    # two-diverted replay reversal happens only when a non-diverted
    # emission preceded the replay: the replayed head then re-enters
    # the skip loop and is re-appended behind its sibling
    # (select.py next()).  With NO good nodes the source exhausts
    # inside the first skip loop and the tail _next_option returns
    # the diverted nodes in ORIGINAL order.
    div_order = jnp.where(
        (n_div == 2) & (nd_count > 0), 1 - div_rank, div_rank
    )
    emit_order = jnp.where(nd, nd_incl - 1, nd_count + div_order)
    emitted = f_p & (emit_order < limit)

    win, _best, decided = earliest_best(s_p, emitted, emit_order)
    any_emitted = jnp.any(emitted)

    limit_reached = nd_count >= limit
    big = jnp.asarray(2**31 - 1, jnp.int32)
    lth_wp = jnp.min(
        jnp.where(nd & (nd_incl == limit), wp, big)
    )
    pulls = jnp.where(limit_reached, lth_wp + 1, n_candidates)
    return win, any_emitted, pulls, decided


def _run_picks(
    cpu_total,
    mem_total,
    disk_total,
    used0,  # (cpu_used, mem_used, disk_used) starting columns
    inp: BatchInputs,
    n_candidates,
    n_picks: int,
    spread_fit: bool,
    wanted=None,  # i32 scalar: picks actually desired (<= n_picks);
                  # surplus scan steps are inert so a batch can share one
                  # static scan length without phantom placements
    spread: "SpreadInputs" = None,
    deltas: "StepDeltas" = None,
    tg: "TGInputs" = None,
    port_ask=None,  # bool[T, Q] (PortInputs.ask)
    port_used=None,  # bool[Q, C] node-space occupancy at eval start
    dev_ask=None,  # i32[T, D] (DeviceInputs.ask)
    dev_free=None,  # i32[D, C] node-space free counts at eval start
    dev_aff=None,  # f[T, C] device-affinity score per node (static)
    dev_aff_on=None,  # bool[T] ask has device affinities (weight != 0)
    occ_extra=None,  # i32[C] distinct_hosts occupancy from job groups
                     # placing NOTHING this eval (their allocs are
                     # outside the T axis but still block the node)
    dh_tg=None,  # bool[T] GROUP-level distinct_hosts: block only on
                 # the picking group's own allocs (feasible.py
                 # _satisfies: job_collision AND task_collision)
):
    """Inner pick scan; returns (rows i32[P], final used columns,
    pulls i32[P] — on a float32 trace with the PAIR_DECIDED flags,
    see split_pulls).

    All per-pick state lives in PERMUTED space: every input column is
    gathered through `inp.perm` exactly once up front, and each scan
    step is purely elementwise + cumsum + reductions (the rotated walk
    is closed-form prefix arithmetic in `_walk`).  TPU gathers are the
    expensive op here — hoisting them out of the step turned a
    ~0.54 ms/eval·pick kernel into a bandwidth-bound one.

    Internally the scan runs in per-pick/per-group space (see
    TGInputs): single-task-group callers (``tg is None``) normalize to
    T=1 with every pick routed to slot 0 — numerically identical to
    the historical single-group kernel.

    The phases of a pick-step carry ``jax.named_scope`` names —
    ``score`` (fit, feasibility and the score terms), ``spread`` (the
    spread boost), ``walk`` (the rotating limited walk and the
    winner), ``usage_update`` (evictions before the pick, the winner's
    usage after it) — so an operation of a device profile (a
    ``%while`` or a fusion) can be put to a phase from its metadata.
    Scopes are metadata only: the jit names and the compiled program
    are what they were."""
    if wanted is None:
        wanted = jnp.asarray(n_picks, jnp.int32)
    dtype = cpu_total.dtype
    perm = inp.perm

    def take(col):
        return jnp.take(col, perm)

    if tg is None:
        tg = TGInputs(
            tg_idx=jnp.zeros(n_picks, jnp.int32),
            feasible=inp.feasible[None],
            affinity=inp.affinity_score[None],
            coll0=inp.base_collisions[None],
            ask_cpu=jnp.broadcast_to(inp.ask_cpu, (n_picks,)),
            ask_mem=jnp.broadcast_to(inp.ask_mem, (n_picks,)),
            ask_disk=jnp.broadcast_to(inp.ask_disk, (n_picks,)),
            desired_count=jnp.broadcast_to(
                inp.desired_count, (n_picks,)
            ),
            limit=jnp.broadcast_to(inp.limit, (n_picks,)),
        )
    T = tg.feasible.shape[0]

    cpu_total_p = take(cpu_total)
    mem_total_p = take(mem_total)
    disk_total_p = take(disk_total)
    feas_tp = jnp.take(tg.feasible, perm, axis=1)  # (T, C)
    penalty_p = take(inp.penalty)
    aff_tp = jnp.take(tg.affinity, perm, axis=1)  # (T, C)
    aff_lo_tp = (
        jnp.take(tg.affinity_lo, perm, axis=1)
        if tg.affinity_lo is not None
        else None
    )
    ports_on = port_ask is not None
    if ports_on:
        ports_p0 = jnp.take(port_used, perm, axis=1)  # (Q, C)
    devs_on = dev_ask is not None
    if devs_on:
        devs_p0 = jnp.take(dev_free, perm, axis=1)  # (D, C)
    if dev_aff is not None:
        dev_aff_p = jnp.take(dev_aff, perm, axis=1)  # (T, C)
    occ_extra_p = (
        jnp.take(occ_extra, perm) if occ_extra is not None else None
    )
    safe_cpu = jnp.where(cpu_total_p > 0, cpu_total_p, 1.0)
    safe_mem = jnp.where(mem_total_p > 0, mem_total_p, 1.0)

    if spread is not None:
        # small-vocab lookups as one-hot matmuls (MXU-friendly; avoids
        # per-step gathers): desired/penalty per node are static,
        # used-per-node recomputes from the (S, V+1) carries each step
        _S, V1 = spread.desired.shape
        with jax.named_scope("spread"):
            codes_sp = jnp.take(spread.codes, perm, axis=1)  # (S, C)
            onehot_p = jax.nn.one_hot(codes_sp, V1, dtype=dtype)
            # a float32 launch that brought the low halves of the
            # float64 `desired` and `weight` scores the boost on pairs
            # (spread_contribution)
            pairs = spread.desired_lo is not None

            def at_node(per_slot):
                # a node's own slot, selected: exact whatever the
                # backend's matmul precision is
                return jnp.sum(
                    jnp.where(onehot_p != 0, per_slot[:, None, :], 0.0),
                    axis=-1,
                )

            desired_node = (
                (at_node(spread.desired), at_node(spread.desired_lo))
                if pairs
                else jnp.einsum("scv,sv->sc", onehot_p, spread.desired)
            )
            penalty_node = codes_sp == (V1 - 1)
            has_desired = pair_hi(desired_node) != 0
            safe_desired = (
                (
                    jnp.where(has_desired, desired_node[0], 1.0),
                    jnp.where(has_desired, desired_node[1], 0.0),
                )
                if pairs
                else jnp.where(has_desired, desired_node, 1.0)
            )
            spread_weight = (
                (spread.weight, spread.weight_lo) if pairs else spread.weight
            )
            spread_existing = spread.used0.astype(dtype)  # (S, V+1)

    def step(carry, pick_idx):
        cpu_used = carry["cpu"]
        mem_used = carry["mem"]
        disk_used = carry["disk"]
        collisions = carry["coll"]  # (T, C) per-group carry
        offset = carry["off"]
        dead = carry["dead"]  # (T,) per-group coalescing
        if spread is not None:
            spread_prop = carry["spread_prop"]
            spread_clr = carry["spread_clr"]
        t = tg.tg_idx[pick_idx]
        # once a pick fails, later picks for ITS task group are inert:
        # the sequential path coalesces subsequent placements per task
        # group after its first failure (generic_sched.go:482); other
        # groups' picks continue
        active = (pick_idx < wanted) & ~dead[t]
        penalty_vec = penalty_p
        app = jnp.asarray(False)
        with jax.named_scope("usage_update"):
            if deltas is not None:
                erow = deltas.evict_rows[pick_idx]
                epos = jnp.argmax(perm == erow)
                app = active & (erow >= 0)
                zf = jnp.asarray(0.0, dtype)
                cpu_used = cpu_used.at[epos].add(
                    jnp.where(app, deltas.evict_cpu[pick_idx], zf)
                )
                mem_used = mem_used.at[epos].add(
                    jnp.where(app, deltas.evict_mem[pick_idx], zf)
                )
                disk_used = disk_used.at[epos].add(
                    jnp.where(app, deltas.evict_disk[pick_idx], zf)
                )
                collisions = collisions.at[t, epos].add(
                    jnp.where(app, deltas.evict_coll[pick_idx], 0)
                )
                prow = deltas.penalty_rows[pick_idx]  # (K,)
                penalty_vec = penalty_vec | jnp.any(
                    perm[:, None] == prow[None, :], axis=1
                )
                if spread is not None:
                    # the evicted alloc's value slot gains one cleared use
                    # (its stop is staged into plan.node_update just before
                    # this pick — propertyset counts it as cleared).  A
                    # destructive eviction replaces an alloc of the PICKING
                    # group, so group-scoped slots of other groups are
                    # untouched
                    evict_slot = spread.codes[:, jnp.maximum(erow, 0)]
                    app_slot = jnp.asarray(app)
                    if spread.group is not None:
                        app_slot = (app & (spread.group == t))[:, None]
                    spread_clr = spread_clr + jnp.where(
                        app_slot,
                        jax.nn.one_hot(evict_slot, V1, dtype=dtype),
                        0.0,
                    )
        with jax.named_scope("score"):
            ask_cpu_k = tg.ask_cpu[pick_idx]
            ask_mem_k = tg.ask_mem[pick_idx]
            ask_disk_k = tg.ask_disk[pick_idx]
            coll_t = collisions[t]  # this pick's group's collision row
            cpu_after = cpu_used + ask_cpu_k
            mem_after = mem_used + ask_mem_k
            disk_after = disk_used + ask_disk_k
            fit = (
                (cpu_after <= cpu_total_p)
                & (mem_after <= mem_total_p)
                & (disk_after <= disk_total_p)
            )
            # distinct_hosts (feasible.go:470 DistinctHostsIterator,
            # both scopes): the collision carries ARE the proposed-
            # allocs-per-node counts — live allocs at the snapshot, +1
            # per pick, -1 per staged destructive eviction.  JOB-level
            # scope blocks on any proposed job alloc: the summed carries
            # plus occ_extra (groups placing nothing this eval).
            # GROUP-level scope blocks only on the picking group's own
            # carry; multi-group jobs with ONLY group-level constraints
            # ship dh_tg and leave inp.distinct_hosts False.
            occupancy = collisions.sum(axis=0)
            if occ_extra_p is not None:
                occupancy = occupancy + occ_extra_p
            feasible = feas_tp[t] & fit & ~(
                inp.distinct_hosts & (occupancy > 0)
            )
            if dh_tg is not None:
                feasible = feasible & ~(dh_tg[t] & (coll_t > 0))
            if ports_on:
                # static-port collision: skipped WITHOUT consuming a
                # walk-limit slot (rank.go network path `continue`) —
                # exactly how the walk treats infeasible nodes
                ask_t_ports = port_ask[t]  # (Q,)
                ports_c = carry["ports"]
                collide = jnp.any(
                    ports_c & ask_t_ports[:, None], axis=0
                )
                feasible = feasible & ~collide
            if devs_on:
                # device capacity: feasible only where every ASKED
                # signature still has enough free instances (the
                # DeviceChecker runs pre-binpack, so shortage is plain
                # infeasibility in the walk arithmetic).  Unasked slots
                # (ask 0) must not couple the pick to unrelated pools
                ask_t_dev = dev_ask[t]  # (D,)
                devs_c = carry["dev"]
                feasible = feasible & jnp.all(
                    (ask_t_dev[:, None] == 0)
                    | (devs_c >= ask_t_dev[:, None]),
                    axis=0,
                )

            # the score list from the canonical f32-rounded exponentials
            # (structs/funcs.py _pow10) on: an array at float64, a
            # pair of float32s on the float32 trace (score.py ScoreList)
            scores = ScoreList(
                cpu_after, safe_cpu, mem_after, safe_mem, coll_t,
                tg.desired_count, spread_fit, dtype, pick=pick_idx,
            )
            scores.penalty(penalty_vec)
            aff_k = aff_tp[t]
            has_aff = aff_k != 0.0
            if aff_lo_tp is not None:
                # float32 trace, low halves brought: the term is the
                # float64 affinity to the pair (0 has a low half of 0)
                scores.append((aff_k, aff_lo_tp[t]), has_aff)
            else:
                scores.append(jnp.where(has_aff, aff_k, 0.0), has_aff)
            if dev_aff is not None:
                # device-affinity match fraction (rank.go:460): appended
                # for EVERY scored node when the ask carries affinities
                # with non-zero total weight — even a 0.0 value enters
                # the mean, unlike the node-affinity component
                d_on = dev_aff_on[t]
                scores.append(jnp.where(d_on, dev_aff_p[t], 0.0), d_on)
        with jax.named_scope("spread"):
            if spread is not None:
                # boost per stanza: ((desired - (used+1)) / desired) * w,
                # -1.0 on the penalty slot (spread.py next()); appended
                # to the score list only when the total is non-zero —
                # shared implementation with the sharded planner.  For
                # multi-group evals only the picking group's slots score
                # (group-scoped propertysets)
                slot_active = spread.active
                if spread.group is not None:
                    slot_active = slot_active & (spread.group == t)
                spread_total = spread_contribution(
                    onehot_p, desired_node, penalty_node, safe_desired,
                    spread_existing, spread_prop, spread_clr,
                    spread_weight, slot_active, spread.even, dtype,
                )
                scores.append(
                    spread_total, pair_hi(spread_total) != 0.0
                )
        with jax.named_scope("score"):
            final = scores.mean()

        with jax.named_scope("walk"):
            win, any_emitted, step_pulls, decided = _walk(
                final, feasible, offset, tg.limit[pick_idx], n_candidates
            )
        with jax.named_scope("usage_update"):
            ok = active & any_emitted
            dead = dead.at[t].set(dead[t] | (active & ~any_emitted))
            row = jnp.where(ok, perm[win], NO_NODE)
            pulls = jnp.where(active, step_pulls, 0)
            safe_win = jnp.where(ok, win, 0)
            upd = lambda arr, delta: arr.at[safe_win].add(
                jnp.where(ok, delta, jnp.zeros_like(delta))
            )
            cpu_used = upd(cpu_used, ask_cpu_k)
            mem_used = upd(mem_used, ask_mem_k)
            disk_used = upd(disk_used, ask_disk_k)
            collisions = collisions.at[t, safe_win].add(
                jnp.where(ok, 1, 0)
            )
            offset = jnp.mod(offset + pulls, n_candidates)
            out = {
                "cpu": cpu_used,
                "mem": mem_used,
                "disk": disk_used,
                "coll": collisions,
                "off": offset,
                "dead": dead,
            }
            if ports_on:
                # the winner occupies its group's static ports for every
                # later pick (and, chained, every later eval)
                win_mask = ok & (
                    jnp.arange(ports_c.shape[1]) == safe_win
                )
                out["ports"] = ports_c | (
                    ask_t_ports[:, None] & win_mask[None, :]
                )
            if devs_on:
                out["dev"] = devs_c.at[:, safe_win].add(
                    jnp.where(ok, -ask_t_dev, 0)
                )
            if spread is not None:
                # the placed node's value slot gains one proposed use per
                # stanza — of the PICKING group only, when group-scoped
                slot_ok = jnp.asarray(ok)
                if spread.group is not None:
                    slot_ok = ok & (spread.group == t)
                    slot_ok = slot_ok[:, None]
                out["spread_prop"] = spread_prop + jnp.where(
                    slot_ok, onehot_p[:, safe_win, :], 0.0
                )
                out["spread_clr"] = spread_clr
            if decided is not None:
                pulls = pulls + jnp.where(ok & decided, PAIR_DECIDED, 0)
        return out, (row, app, pulls)

    carry0 = {
        "cpu": take(used0[0]),
        "mem": take(used0[1]),
        "disk": take(used0[2]),
        "coll": jnp.take(tg.coll0, perm, axis=1),  # (T, C)
        "off": jnp.asarray(0, jnp.int32),
        "dead": jnp.zeros((T,), dtype=bool),
    }
    if ports_on:
        carry0["ports"] = ports_p0
    if devs_on:
        carry0["dev"] = devs_p0
    if spread is not None:
        carry0["spread_prop"] = spread.proposed0.astype(dtype)
        carry0["spread_clr"] = spread.cleared0.astype(dtype)
    _final, (rows, eapps, pulls) = jax.lax.scan(
        step, carry0, jnp.arange(n_picks, dtype=jnp.int32)
    )
    # node-space final usage for the chained (serially-equivalent)
    # variant: apply the P placement deltas onto the node-space bases
    ok_rows = rows != NO_NODE
    safe_rows = jnp.where(ok_rows, rows, 0)

    def back(base_col, ask):
        delta = jnp.where(
            ok_rows, jnp.broadcast_to(ask, rows.shape), 0.0
        ).astype(base_col.dtype)
        return base_col.at[safe_rows].add(delta)

    used_cpu = back(used0[0], tg.ask_cpu)
    used_mem = back(used0[1], tg.ask_mem)
    used_disk = back(used0[2], tg.ask_disk)
    if deltas is not None:
        # applied per-pick evictions also shift the chained columns
        safe_er = jnp.where(eapps, deltas.evict_rows, 0)

        def back_evict(col, dvals):
            d = jnp.where(eapps, dvals, 0.0).astype(col.dtype)
            return col.at[safe_er].add(d)

        used_cpu = back_evict(used_cpu, deltas.evict_cpu)
        used_mem = back_evict(used_mem, deltas.evict_mem)
        used_disk = back_evict(used_disk, deltas.evict_disk)
    if ports_on or devs_on:
        # node-space carries for the chain: every successful pick's
        # row gains its group's static ports / loses its group's
        # asked device instances
        onehot_rows = (
            safe_rows[:, None]
            == jnp.arange(used_cpu.shape[0])[None, :]
        ).astype(jnp.int32)  # (P, C)
        extras = {}
        if ports_on:
            ask_rows = port_ask[tg.tg_idx]  # (P, Q)
            hit = (ok_rows[:, None] & ask_rows).astype(jnp.int32)
            extras["ports"] = port_used | (
                jnp.einsum("pq,pc->qc", hit, onehot_rows) > 0
            )
        if devs_on:
            dev_rows = dev_ask[tg.tg_idx]  # (P, D)
            consumed = jnp.einsum(
                "pd,pc->dc",
                jnp.where(ok_rows[:, None], dev_rows, 0),
                onehot_rows,
            )
            extras["dev"] = dev_free - consumed
        return rows, (used_cpu, used_mem, used_disk), pulls, extras
    return rows, (used_cpu, used_mem, used_disk), pulls


@functools.partial(
    jax.jit, static_argnames=("n_picks", "spread_fit")
)
def plan_picks(
    cpu_total,
    mem_total,
    disk_total,
    inp: BatchInputs,
    n_candidates,
    n_picks: int,
    spread_fit: bool = False,
    spread: SpreadInputs = None,
    deltas: StepDeltas = None,
):
    """P sequential placements for one eval; returns rows i32[P]
    (NO_NODE when placement failed)."""
    rows, _used, _pulls = _run_picks(
        cpu_total,
        mem_total,
        disk_total,
        (inp.base_cpu_used, inp.base_mem_used, inp.base_disk_used),
        inp,
        n_candidates,
        n_picks,
        spread_fit,
        spread=spread,
        deltas=deltas,
    )
    return rows


@functools.partial(
    jax.jit, static_argnames=("n_picks", "spread_fit")
)
def plan_picks_full(
    cpu_total,
    mem_total,
    disk_total,
    inp: BatchInputs,
    n_candidates,
    n_picks: int,
    spread_fit: bool = False,
):
    """Like plan_picks but also returns per-pick pull counts so the
    caller can mirror the rotating offset (select.go source position).
    Starting rotation is folded into `inp.perm` by the caller.  Used by
    the TPUGenericStack look-ahead: one launch pre-computes the whole
    placement loop of a task group instead of one device round trip per
    placement (generic_sched.go:468 computePlacements).

    Returns ONE stacked i32[2, P] array ([rows; pulls]) so the host
    pays a single device->host sync — each fetch is a full device
    round trip."""
    rows, _used, pulls = _run_picks(
        cpu_total,
        mem_total,
        disk_total,
        (inp.base_cpu_used, inp.base_mem_used, inp.base_disk_used),
        inp,
        n_candidates,
        n_picks,
        spread_fit,
    )
    if cpu_total.dtype == jnp.float32:
        pulls, _decided = split_pulls(pulls)
    return jnp.stack([rows.astype(jnp.int32), pulls.astype(jnp.int32)])


@functools.partial(
    jax.jit, static_argnames=("n_picks", "spread_fit")
)
def chained_plan_picks(
    cpu_total,
    mem_total,
    disk_total,
    batch: BatchInputs,  # leading axis E
    n_candidates,  # i32[E]
    n_picks: int,
    spread_fit: bool = False,
    wanted=None,  # i32[E]: per-eval pick counts (<= n_picks)
    spread: SpreadInputs = None,  # leading axis E on every field
    deltas: StepDeltas = None,  # leading axis E on every field
    pre: PreDeltas = None,  # leading axis E on every field
):
    """E evals x P picks in ONE launch, *serially equivalent*: a
    lax.scan over the evals carries the proposed-usage columns forward,
    so eval k scores against the state left by evals 0..k-1 — exactly
    what the sequential worker loop produces when each plan commits
    before the next eval runs.  One device round trip amortizes over the
    whole batch while decisions stay bit-identical to serial execution.

    Steady-state evals additionally carry `pre` (usage freed by
    lost/stopped allocs + in-place update shifts, applied before the
    eval's first pick) and `deltas` (per-pick destructive-update
    evictions + reschedule penalty rows), so the chain reflects every
    plan mutation the sequential scheduler would commit — not just
    placements.

    Anti-affinity collision and distinct-hosts state reset per eval
    (they are per-job; the broker's JobID dedup guarantees no two evals
    in flight share a job).  Returns rows i32[E, P]."""
    E = batch.perm.shape[0]
    nc = jnp.broadcast_to(jnp.asarray(n_candidates, jnp.int32), (E,))
    if wanted is None:
        wanted = jnp.full((E,), n_picks, jnp.int32)

    used0 = (
        batch.base_cpu_used[0],
        batch.base_mem_used[0],
        batch.base_disk_used[0],
    )

    def eval_step(used, xs):
        b, n, w, s, d, p = xs
        if p is not None:
            used = (
                used[0].at[p.rows].add(p.cpu.astype(used[0].dtype)),
                used[1].at[p.rows].add(p.mem.astype(used[1].dtype)),
                used[2].at[p.rows].add(p.disk.astype(used[2].dtype)),
            )
        rows, used_next, _pulls = _run_picks(
            cpu_total, mem_total, disk_total, used, b, n,
            n_picks, spread_fit, wanted=w, spread=s, deltas=d,
        )
        return used_next, rows

    # xs entries that are None are threaded as static Nones via a
    # wrapper (lax.scan xs must be arrays): build per-variant closures
    def make_xs():
        parts = [batch, nc, wanted]
        pattern = []
        for x in (spread, deltas, pre):
            pattern.append(x is not None)
            if x is not None:
                parts.append(x)
        return tuple(parts), pattern

    xs_arrays, pattern = make_xs()

    def eval_step_packed(used, xs):
        it = iter(xs[3:])
        s = next(it) if pattern[0] else None
        d = next(it) if pattern[1] else None
        p = next(it) if pattern[2] else None
        return eval_step(used, (xs[0], xs[1], xs[2], s, d, p))

    _final, rows = jax.lax.scan(eval_step_packed, used0, xs_arrays)
    return rows


class ChainInputs(NamedTuple):
    """Per-eval inputs for the production chained launch (leading axis
    E).  Unlike BatchInputs this carries NO copies of the shared node
    columns: the snapshot usage chains through the scan carry and the
    totals are closure inputs, so host assembly ships only what actually
    differs per eval (~5x less host->device traffic at E=64).

    The group axis T (usually 1) and the per-pick routing fields carry
    multi-task-group evals: pick k uses group slot ``tg_idx[:, k]``'s
    feasibility row and its own ask/count/limit scalars, mirroring
    computePlacements' per-group iteration within one eval (reference
    generic_sched.go:468)."""

    feasible: jnp.ndarray  # bool[E, T, C]
    perm: jnp.ndarray  # i32[E, C]
    ask_cpu: jnp.ndarray  # f[E, P]
    ask_mem: jnp.ndarray  # f[E, P]
    ask_disk: jnp.ndarray  # f[E, P]
    desired_count: jnp.ndarray  # i32[E, P]
    limit: jnp.ndarray  # i32[E, P]
    distinct_hosts: jnp.ndarray  # bool[E]
    tg_idx: jnp.ndarray  # i32[E, P]


def chained_plan_picks_cols(
    cpu_total,
    mem_total,
    disk_total,
    used0_cpu,  # f[C] snapshot usage (shared; the chain carries deltas)
    used0_mem,
    used0_disk,
    batch: ChainInputs,
    n_candidates,  # i32[E]
    n_picks: int,
    spread_fit: bool = False,
    wanted=None,  # i32[E]
    coll0=None,  # i32[E, T, C] anti-affinity base (None = zeros)
    affinity=None,  # f[E, T, C] (None = zeros)
    spread: SpreadInputs = None,  # leading axis E
    deltas: StepDeltas = None,  # leading axis E
    pre: PreDeltas = None,  # leading axis E
    port_ask=None,  # bool[E, T, Q] static-port slots per group
    port_used0=None,  # bool[Q, C] occupancy at the chain snapshot
    dev_ask=None,  # i32[E, T, D] device instances asked per group
    dev_free0=None,  # i32[D, C] free instances at the chain snapshot
    dev_aff=None,  # f[E, T, C] device-affinity score per node
    dev_aff_on=None,  # bool[E, T]
    occ0=None,  # i32[E, C] pickless-group distinct_hosts occupancy
    dh_tg=None,  # bool[E, T] group-level distinct_hosts flags
    return_carry: bool = False,
    affinity_lo=None,  # f32[E, T, C]: float32 trace only, the low
                       # half of the host's float64 `affinity`
):
    """Serially-equivalent chained planner over shared node columns —
    the BatchWorker's production launch.  Semantics identical to
    `chained_plan_picks`; only the input layout differs.

    With ``return_carry=True`` the final scan carry — the chained
    (cpu, mem, disk) usage columns plus the port-occupancy and
    device-free carries (None when absent) — is returned as a third
    output.  Splitting one E-eval chain into consecutive launches
    whose carry-out feeds the next launch's ``used0_*``/``port_used0``/
    ``dev_free0`` is bit-identical to the single launch (a lax.scan cut
    at an eval boundary), which is what the BatchWorker's pipelined
    prescore relies on: chunk N+1 dispatches against chunk N's
    device-resident carry while the host replays chunk N-1."""
    E = batch.perm.shape[0]
    C = cpu_total.shape[0]
    T = batch.feasible.shape[1]
    nc = jnp.broadcast_to(jnp.asarray(n_candidates, jnp.int32), (E,))
    if wanted is None:
        wanted = jnp.full((E,), n_picks, jnp.int32)
    zeros_ti = jnp.zeros((T, C), jnp.int32)
    zeros_b = jnp.zeros(C, dtype=bool)
    zeros_tf = jnp.zeros((T, C), cpu_total.dtype)
    ports_on = port_ask is not None
    devs_on = dev_ask is not None

    parts = [batch, nc, wanted]
    pattern = []
    dev_aff_pair = (
        (dev_aff, dev_aff_on) if dev_aff is not None else None
    )
    for x in (coll0, affinity, spread, deltas, pre, port_ask,
              dev_ask, dev_aff_pair, occ0, dh_tg, affinity_lo):
        pattern.append(x is not None)
        if x is not None:
            parts.append(x)

    def eval_step(carry, xs):
        used, ports, devs = carry
        it = iter(xs[3:])
        b = xs[0]
        coll = next(it) if pattern[0] else zeros_ti
        aff = next(it) if pattern[1] else zeros_tf
        s = next(it) if pattern[2] else None
        d = next(it) if pattern[3] else None
        p = next(it) if pattern[4] else None
        pa = next(it) if pattern[5] else None
        da = next(it) if pattern[6] else None
        daff, daff_on = (
            next(it) if pattern[7] else (None, None)
        )
        oc = next(it) if pattern[8] else None
        dhg = next(it) if pattern[9] else None
        aff_lo = next(it) if pattern[10] else None
        if p is not None:
            with jax.named_scope("usage_update"):
                used = (
                    used[0].at[p.rows].add(
                        p.cpu.astype(used[0].dtype)
                    ),
                    used[1].at[p.rows].add(
                        p.mem.astype(used[1].dtype)
                    ),
                    used[2].at[p.rows].add(
                        p.disk.astype(used[2].dtype)
                    ),
                )
        tg_in = TGInputs(
            tg_idx=b.tg_idx,
            feasible=b.feasible,
            affinity=aff,
            coll0=coll,
            ask_cpu=b.ask_cpu,
            ask_mem=b.ask_mem,
            ask_disk=b.ask_disk,
            desired_count=b.desired_count,
            limit=b.limit,
            affinity_lo=aff_lo,
        )
        # the BatchInputs carrier only supplies perm/penalty/
        # distinct_hosts here — group-routed fields ride in tg_in
        inp = BatchInputs(
            feasible=b.feasible[0],
            base_cpu_used=used[0],
            base_mem_used=used[1],
            base_disk_used=used[2],
            base_collisions=coll[0],
            penalty=zeros_b,
            affinity_score=aff[0],
            perm=b.perm,
            ask_cpu=b.ask_cpu[0],
            ask_mem=b.ask_mem[0],
            ask_disk=b.ask_disk[0],
            desired_count=b.desired_count[0],
            limit=b.limit[0],
            distinct_hosts=b.distinct_hosts,
        )
        if ports_on or devs_on:
            rows, used_next, pulls, extras = _run_picks(
                cpu_total, mem_total, disk_total, used, inp, xs[1],
                n_picks, spread_fit, wanted=xs[2], spread=s,
                deltas=d, tg=tg_in, port_ask=pa, port_used=ports,
                dev_ask=da, dev_free=devs, dev_aff=daff,
                dev_aff_on=daff_on, occ_extra=oc, dh_tg=dhg,
            )
            return (
                used_next,
                extras.get("ports"),
                extras.get("dev"),
            ), (rows, pulls)
        rows, used_next, pulls = _run_picks(
            cpu_total, mem_total, disk_total, used, inp, xs[1],
            n_picks, spread_fit, wanted=xs[2], spread=s, deltas=d,
            tg=tg_in, dev_aff=daff, dev_aff_on=daff_on,
            occ_extra=oc, dh_tg=dhg,
        )
        return (used_next, None, None), (rows, pulls)

    used0 = (used0_cpu, used0_mem, used0_disk)
    carry0 = (used0, port_used0, dev_free0)
    final, (rows, pulls) = jax.lax.scan(
        eval_step, carry0, tuple(parts)
    )
    # pulls[E, P]: source-iterator consumption per pick — the host
    # reconstructs the sequential walk offset at any pick from the
    # running sum (preemption-retry passthrough seeds the oracle's
    # StaticIterator offset with it).  On a float32 trace a pick's
    # entry also carries the PAIR_DECIDED flag: split_pulls
    if return_carry:
        return rows, pulls, final
    return rows, pulls


chained_plan_picks_cols = jax.jit(
    chained_plan_picks_cols,
    static_argnames=("n_picks", "spread_fit", "return_carry"),
)

_chained_cols_donated = None


def chained_plan_picks_cols_donated():
    """jit variant of `chained_plan_picks_cols` that donates the
    chain-carry buffers (usage columns + port/device occupancy) so
    back-to-back pipelined launches reuse device memory instead of
    holding every in-flight chunk's carry live.  Created lazily: the
    caller (BatchWorker) only selects it on non-CPU backends, where
    donation is honored, and only when the inputs are the previous
    launch's carry-out (never the persistent usage-column cache, which
    must survive the launch)."""
    global _chained_cols_donated
    if _chained_cols_donated is None:
        fn = jax.jit(
            chained_plan_picks_cols.__wrapped__,
            static_argnames=("n_picks", "spread_fit", "return_carry"),
            donate_argnames=(
                "used0_cpu",
                "used0_mem",
                "used0_disk",
                "port_used0",
                "dev_free0",
            ),
        )
        # distinct name: the cold-compile shield keys signatures by
        # fn name, and the donated executable compiles separately
        fn.__name__ = "chained_plan_picks_cols_donated"
        _chained_cols_donated = fn
    return _chained_cols_donated


@jax.jit
def patch_rows(col, idx, vals):
    """Scatter-patch dirty rows into a persistent device column:
    ``col[idx] = vals`` with out-of-bounds indices DROPPED (padding
    slots use idx == C; negative indices would wrap).  The delta-sync
    primitive for the BatchWorker's device-resident usage mirror."""
    return col.at[idx].set(vals, mode="drop")


_patch_rows_donated = None


def patch_rows_donated():
    """jit variant of `patch_rows` that donates the stale mirror
    column: it is replaced in the caller's cache by the patched
    output, so the old buffer is device memory the scatter can write
    in place — with the chained-launch carry donation this makes the
    steady-state sync path allocate nothing net on device.  (The
    idx/vals staging uploads are NOT donated: their [width] shapes
    can never alias the [C] output, so XLA could not honor it and
    jax would warn on every width bucket.)  The caller
    (BatchWorker._device_columns_locked) only selects this variant on
    non-CPU backends, and only while no abandoned in-flight launch or
    background shield compile could still be reading the column being
    donated (it falls back to the copying `patch_rows` — and a full
    re-upload — whenever that cannot be proven)."""
    global _patch_rows_donated
    if _patch_rows_donated is None:
        fn = jax.jit(
            patch_rows.__wrapped__, donate_argnums=(0,)
        )
        fn.__name__ = "patch_rows_donated"
        _patch_rows_donated = fn
    return _patch_rows_donated


_patch_rows_sharded_cache: dict = {}


def patch_rows_sharded(mesh, donate: bool = False):
    """Per-shard scatter-patch for a ``NamedSharding(P("nodes"))``
    mirror column — the delta-sync primitive for the BatchWorker's
    SHARDED device-resident usage mirror.  Each device receives the
    replicated (idx, vals) staging buffers (O(dirty rows) bytes
    host->device, total) and scatters only the rows that land in its
    own node shard: one local scatter per shard, zero cross-shard
    traffic.  Padding slots use ``idx == C`` (out of this shard's
    range on every shard) and are dropped, exactly like `patch_rows`.

    ``donate=True`` donates the stale column like `patch_rows_donated`
    — the caller replaces it in its cache with the patched output, so
    the scatter writes device memory in place.  The same exclusivity
    gating applies: the caller must prove no abandoned in-flight
    launch or background shield compile could still be reading the
    buffer (BatchWorker falls back to the copying variant — and a full
    re-upload — whenever that cannot be proven).  Compiled runners are
    cached per (mesh, donate)."""
    key = (mesh, bool(donate))
    fn = _patch_rows_sharded_cache.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as _P

        from ..parallel.mesh import shard_map as _shard_map

        def _patch(col, idx, vals):
            shard = jax.lax.axis_index("nodes")
            size = col.shape[0]
            local = idx - shard * size
            ok = (local >= 0) & (local < size)
            # misses (another shard's rows, padding) map to `size`,
            # which mode="drop" discards
            safe = jnp.where(ok, local, size)
            return col.at[safe].set(vals, mode="drop")

        wrapped = functools.partial(
            _shard_map,
            mesh=mesh,
            in_specs=(_P("nodes"), _P(), _P()),
            out_specs=_P("nodes"),
        )(_patch)
        fn = jax.jit(
            wrapped, donate_argnums=(0,) if donate else ()
        )
        fn.__name__ = (
            "patch_rows_sharded_donated"
            if donate
            else "patch_rows_sharded"
        )
        _patch_rows_sharded_cache[key] = fn
    return fn


def patch_rows_hostlocal(mesh, donate: bool = False):
    """Per-DEVICE staging variant of `patch_rows_sharded` for MULTI-
    host meshes: the delta-sync primitive of the cross-host flush
    protocol.  ``idx`` and ``vals`` arrive as ``[D, w]`` arrays
    sharded ``P("nodes")`` along the leading device axis — device d's
    row holds ONLY the dirty rows landing in its own node shard, with
    indices already shard-LOCAL and padding slots set to the shard
    size (out of bounds -> dropped, exactly like `patch_rows`).  Each
    host therefore builds and ships staging for its own devices'
    dirty rows and nothing else: a warm cross-host flush costs every
    host O(its dirty rows) bytes, never a replicated buffer and never
    a full column over the network.  ``w`` is the pow2 bucket of the
    LARGEST per-device dirty count (a shared static shape — every
    process must compile the identical program).

    Bit-identical to `patch_rows_sharded` on the same dirty set: both
    reduce to one local in-shard scatter per device.  The single-
    process mirror keeps the replicated PR 8 staging (same bytes,
    same trace); this variant exists for the world where "replicated"
    means a network broadcast.  ``donate=True`` follows
    `patch_rows_donated`'s exclusivity contract."""
    key = (mesh, "hostlocal", bool(donate))
    fn = _patch_rows_sharded_cache.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as _P

        from ..parallel.mesh import shard_map as _shard_map

        def _patch(col, idx, vals):
            # leading axis: this device's single [1, w] staging row;
            # indices are pre-localized, padding == shard size drops
            return col.at[idx[0]].set(vals[0], mode="drop")

        wrapped = functools.partial(
            _shard_map,
            mesh=mesh,
            in_specs=(_P("nodes"), _P("nodes"), _P("nodes")),
            out_specs=_P("nodes"),
        )(_patch)
        fn = jax.jit(
            wrapped, donate_argnums=(0,) if donate else ()
        )
        fn.__name__ = (
            "patch_rows_hostlocal_donated"
            if donate
            else "patch_rows_hostlocal"
        )
        _patch_rows_sharded_cache[key] = fn
    return fn


def hostlocal_staging(
    mesh, idx: np.ndarray, capacity: int
) -> Tuple[np.ndarray, List[np.ndarray], int]:
    """Build the `patch_rows_hostlocal` index staging for a dirty-row
    set: returns ``(idx_stack[D, w] shard-local i32, order, w)`` where
    ``order[d]`` is the slice of ``idx`` (global rows, sorted) that
    landed in device d's shard — the caller gathers each column's
    values with it.  Deterministic across processes: every process
    computes the identical stack from the shared dirty log, then
    ships only its own devices' rows (`mesh_put`)."""
    n_dev = int(mesh.devices.size)
    size = capacity // n_dev
    per_dev = [
        idx[(idx >= d * size) & (idx < (d + 1) * size)]
        for d in range(n_dev)
    ]
    w = pow2_bucket(
        max(1, max(len(s) for s in per_dev)), floor=8
    )
    idx_stack = np.full((n_dev, w), size, np.int32)
    for d, sel in enumerate(per_dev):
        idx_stack[d, : len(sel)] = sel - d * size
    return idx_stack, per_dev, w


@functools.partial(
    jax.jit, static_argnames=("n_picks", "spread_fit")
)
def batch_plan_picks_shared(
    cpu_total,
    mem_total,
    disk_total,
    feasible,  # bool[C] shared static mask
    base_cpu_used,  # f[C] shared snapshot usage
    base_mem_used,
    base_disk_used,
    perms,  # i32[E, C] per-eval walk orders
    ask_cpu,  # f[E]
    ask_mem,
    ask_disk,
    desired_count,  # i32[E]
    limit,  # i32[E]
    n_candidates,
    n_picks: int,
    spread_fit: bool = False,
):
    """Batched planner for the common case where every eval in the batch
    scores against the same snapshot (fresh jobs, no penalties or
    affinities): node columns ship once, only the E x C walk orders and
    per-eval scalars vary.  Cuts host->device traffic ~12x versus
    stacking full BatchInputs per eval (SURVEY.md section 7.3 Go<->TPU
    latency note)."""
    C = cpu_total.shape[0]
    zeros_i = jnp.zeros(C, jnp.int32)
    zeros_b = jnp.zeros(C, dtype=bool)
    zeros_f = jnp.zeros(C, cpu_total.dtype)

    def one(perm, a_cpu, a_mem, a_disk, desired, lim):
        inp = BatchInputs(
            feasible=feasible,
            base_cpu_used=base_cpu_used,
            base_mem_used=base_mem_used,
            base_disk_used=base_disk_used,
            base_collisions=zeros_i,
            penalty=zeros_b,
            affinity_score=zeros_f,
            perm=perm,
            ask_cpu=a_cpu,
            ask_mem=a_mem,
            ask_disk=a_disk,
            desired_count=desired,
            limit=lim,
            distinct_hosts=jnp.asarray(False),
        )
        return plan_picks(
            cpu_total, mem_total, disk_total, inp,
            n_candidates, n_picks, spread_fit,
        )

    return jax.vmap(one)(
        perms, ask_cpu, ask_mem, ask_disk, desired_count, limit
    )


@functools.partial(
    jax.jit, static_argnames=("n_picks", "spread_fit")
)
def batch_plan_picks(
    cpu_total,
    mem_total,
    disk_total,
    batch: BatchInputs,  # leading axis E on every field
    n_candidates,  # scalar or per-eval i32[E] (walk rotation modulus)
    n_picks: int,
    spread_fit: bool = False,
    spread: SpreadInputs = None,  # leading axis E on every field
):
    """E independent evals x P picks in one launch; returns rows
    i32[E, P]."""
    E = batch.perm.shape[0]
    nc = jnp.broadcast_to(jnp.asarray(n_candidates, jnp.int32), (E,))
    if spread is not None:
        return jax.vmap(
            lambda b, n, s: plan_picks(
                cpu_total, mem_total, disk_total, b, n,
                n_picks, spread_fit, spread=s,
            )
        )(batch, nc, spread)
    return jax.vmap(
        lambda b, n: plan_picks(
            cpu_total,
            mem_total,
            disk_total,
            b,
            n,
            n_picks,
            spread_fit,
        )
    )(batch, nc)
