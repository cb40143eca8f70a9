"""The vectorized score kernel + deterministic selection.

This is the TPU-native replacement for the reference's innermost loop
(SURVEY.md section 3.2): one jitted function scores *all* candidate nodes
at once — fit masks, BestFit-v3 bin-packing (funcs.go:175), job
anti-affinity (rank.go:527), rescheduling penalty (rank.go:573), node
affinity (rank.go:658), spread boosts (spread.go:163), mean normalization
(rank.go:706) — and then *exactly emulates* the reference's shuffled
limited walk (select.go: LimitIterator with skip-threshold 0 / max-skip 3,
MaxScoreIterator's first-wins strict max) over the score vector, so the
selected node is bit-identical to what the pull-based iterator chain
would have chosen while doing O(N) vector math instead of O(limit) pointer
chasing.

Score-append semantics are reproduced as a running sum and a count
(``ScoreList``): each term contributes to the sum and increments the
count only under the reference's append conditions; the final score is
sum/count.  On the float32 trace the sum is two float32s ``(hi, lo)``
from the fitness exponentials to the mean, and the walk's maximum
compares ``hi`` first and ``lo`` second (``earliest_best``), so the
winner is the float64 definition's.

Shapes are fixed to the node arena capacity so jit traces cache across
cluster churn; vacant rows are masked.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import twofloat

MAX_SKIP = 3  # (reference stack.go:17)
SKIP_THRESHOLD = 0.0  # (reference stack.go:13)
NO_NODE = -1


class PolicyTerms(NamedTuple):
    """Optional policy terms fused into the score pass (Gavel-style
    heterogeneity throughput + migration stickiness), PRE-SCALED by
    their coefficients host-side (one numpy mul at assembly — f64
    multiplication is deterministic, so host and device scaling are
    bit-identical and the kernel saves the per-candidate ops).  Shapes
    follow the ScoreInputs they ride in: per-node vectors broadcast
    exactly like `feasible` ([C] for a single select, [A, C] after the
    storm solver's per-row gather), flags like `desired_count` ([] or
    [A, 1]).

    Each term group is independently optional: a None group is absent
    from the pytree, so a throughput-only job (the common identity-
    weights shape) pays ONE vector add plus a scalar count bump and a
    migration-only job pays only the penalty ops.  Single selects drop
    whichever group is inert; storms keep both groups dense (all-zero
    rows for policy-less evals are float-exact no-ops) so one compiled
    signature covers every mixed storm.

    `tput_term` is `tput_coef * tput_norm[node]`, appended for EVERY
    candidate when present (zeros included — an unknown node class
    pulls the mean down exactly like the serial oracle); `has_tput` is
    its 0/1 append-count flag (per-eval in storms).  `mig_term` is
    `mig_coef * mig[node]` where mig is -1 on every node EXCEPT those
    currently hosting this TG's live allocs; it appends only where
    non-zero (node-reschedule-penalty convention — the incumbent's
    score mean stays untouched, movers are dragged down)."""

    tput_term: Optional[jnp.ndarray]  # f[C] coef * normalized tput
    has_tput: Optional[jnp.ndarray]  # f 0/1 flag, paired with tput_term
    mig_term: Optional[jnp.ndarray]  # f[C] coef * (-1 off-host, 0 on)


def _pow10(x, dtype):
    """Canonical 10^x for fitness scoring: the working dtype's pow
    rounded through float32 (see structs/funcs.py _pow10).  At float64
    that IS the definition: XLA's pow and the host's libm differ by one
    f64 ulp on ~5% of inputs and the rounding collapses them to the
    same float32, bit for bit.  It is not so at float32, where the
    TPU's pow is off the definition by up to 5e-6: float32 traces go
    through `_fit_exponentials` below and never call this."""
    raw = jnp.power(jnp.asarray(10.0, dtype), x)
    return raw.astype(jnp.float32).astype(dtype)


def _fit_exponentials(cpu_after, cpu_cap, mem_after, mem_cap, dtype):
    """``10^freeCpu + 10^freeMem`` with each free share ``1 - after /
    cap`` and each exponential the float64 definition's, rounded once
    to float32 (structs/funcs.py _pow10).  The float64 trace computes
    them as it always has and returns the sum as an array; the float32
    trace (``jax_enable_x64`` off, the dtype a chip deploys) carries
    share and exponential as two float32s (ops/twofloat.py), so each
    float32 exponential is the same number, bit for bit, on the CPU and
    on the TPU — which float32 division and pow are not — and returns
    their EXACT sum as a pair ``(hi, lo)``: float64 holds the sum of two
    float32s exactly, one float32 does not (``hi`` is the float32 sum)."""
    if dtype == jnp.float32:
        # both resources through one trace of the arithmetic: a launch
        # shape's compile time is in its count of operations
        cpu_after, cpu_cap, mem_after, mem_cap = jnp.broadcast_arrays(
            cpu_after, cpu_cap, mem_after, mem_cap
        )
        p = twofloat.pow10_free(
            jnp.stack([cpu_after, mem_after]), jnp.stack([cpu_cap, mem_cap])
        )
        return twofloat.exact_sum(p[0], p[1])
    free_cpu = 1.0 - cpu_after / cpu_cap
    free_mem = 1.0 - mem_after / mem_cap
    return _pow10(free_cpu, dtype) + _pow10(free_mem, dtype)


def pair_hi(x):
    """The working-dtype array of a score the kernels carry: a float32
    trace's pair gives its ``hi``, the float64 trace's array itself."""
    return x[0] if isinstance(x, tuple) else x


class ScoreList:
    """The score list of a pick for every candidate at once: the
    running sum of the appended terms and their count (rank.go's
    ``Scores`` and its mean, rank.go:706).  It opens with the two terms
    every job has, bin-packing or spread fitness (funcs.go:175, :202)
    and job anti-affinity (rank.go:527: ``-(collisions + 1) /
    desired_count`` where the node holds allocations of the job's group
    already; ``desired_count`` is the group's count, or with ``pick``
    the counts of a scan's picks and the index of this one).  The
    reschedule penalty (rank.go:573) has its own method, every other
    term goes through ``append``.

    The float64 trace holds the sum as one array.  The float32 trace
    holds it as a pair ``(hi, lo)`` (ops/twofloat.py) from the
    exponentials to ``mean``: ``20 - sum`` exactly, each quotient and
    sum to about 2^-46, so that two candidates whose float64 scores
    differ order as float64 orders them where one float32 would tie
    them.  The node-affinity term and the spread boost of the chained
    kernel (ops/batch.py) are pairs too wherever the launch brings the
    low halves of their float64 inputs; a term that is not a pair
    (device affinity, policy terms, the per-select kernels' affinity
    and boost) enters ``append`` as ``(value, 0)``."""

    def __init__(
        self, cpu_after, cpu_cap, mem_after, mem_cap, collisions,
        desired_count, spread_fit: bool, dtype, pick=None,
    ):
        self.dtype = dtype
        self.paired = dtype == jnp.float32
        base = _fit_exponentials(
            cpu_after, cpu_cap, mem_after, mem_cap, dtype
        )
        if self.paired:
            fitness = twofloat.clip(
                twofloat.add_f(base, -2.0)
                if spread_fit
                else twofloat.add_f(twofloat.neg(base), 20.0),
                0.0, 18.0,
            )
            has_collision = collisions > 0
            if pick is not None:
                desired_count = desired_count[pick]
            # fitness / 18 and -(collisions + 1) / count through one
            # trace of the quotient (a launch shape's compile time is
            # in its count of operations)
            fit_hi, fit_lo, above, by_18, by_count = jnp.broadcast_arrays(
                fitness[0], fitness[1], -(collisions.astype(dtype) + 1.0),
                jnp.asarray(18.0, dtype), desired_count.astype(dtype),
            )
            q_hi, q_lo = twofloat.quotient(
                (
                    jnp.stack([fit_hi, above]),
                    jnp.stack([fit_lo, jnp.zeros_like(fit_lo)]),
                ),
                jnp.stack([by_18, by_count]),
            )
            self.sum = twofloat.add(
                (q_hi[0], q_lo[0]),
                (
                    jnp.where(has_collision, q_hi[1], 0.0),
                    jnp.where(has_collision, q_lo[1], 0.0),
                ),
            )
            self.count = jnp.ones_like(fit_hi)
        else:
            # operation for operation what the kernels always traced:
            # the float64 trace's lowered text is held to it
            # (tests/test_float32_scoring.py)
            if spread_fit:
                fitness = jnp.clip(base - 2.0, 0.0, 18.0)
            else:
                fitness = jnp.clip(20.0 - base, 0.0, 18.0)
            self.sum = fitness / 18.0
            self.count = jnp.ones_like(self.sum)
            has_collision = collisions > 0
            above = -(collisions.astype(dtype) + 1.0)
            if pick is not None:
                desired_count = desired_count[pick]
            self.sum = self.sum + jnp.where(
                has_collision, above / desired_count.astype(dtype), 0.0
            )
        self.count = self.count + has_collision.astype(dtype)

    def penalty(self, penalized) -> None:
        """-1 on a node the allocation is being rescheduled away from."""
        if self.paired:
            self.sum = twofloat.add_f(
                self.sum, -penalized.astype(self.dtype)
            )
        else:
            self.sum = self.sum - penalized.astype(self.dtype)
        self.count = self.count + penalized.astype(self.dtype)

    def append(self, term, present=None) -> None:
        """Add ``term`` (0 where it does not append; on the float32
        trace an array or a pair) to the sum and ``present`` (a mask,
        or a 0/1 flag in the working dtype; by default where ``term``
        is not 0) to the count."""
        if isinstance(term, tuple):
            self.sum = twofloat.add(self.sum, term)
        elif self.paired:
            self.sum = twofloat.add_f(self.sum, term)
        else:
            self.sum = self.sum + term
        if present is None:
            present = pair_hi(term) != 0.0
        if present.dtype == jnp.bool_:
            present = present.astype(self.count.dtype)
        self.count = self.count + present

    def mean(self):
        """sum / count: a pair at float32, an array at float64."""
        if self.paired:
            return twofloat.quotient(self.sum, self.count)
        return self.sum / self.count


def under_threshold(s):
    """The walk's skip test (select.go:52), ``score <= 0``."""
    if isinstance(s, tuple):
        return twofloat.at_most(s, SKIP_THRESHOLD)
    return s <= SKIP_THRESHOLD


def earliest_best(s, emitted, emit_order):
    """MaxScoreIterator over the emitted candidates (select.go:94-113):
    the strict maximum, the earliest emitted of equal scores.  A pair
    compares by ``hi``, then by ``lo``.  Returns (position of the
    winner, its score as one float, whether ``lo`` decided: two or
    more emitted candidates shared the winner's ``hi`` and differed in
    ``lo`` — None on the float64 trace, which has no ``lo``)."""
    hi = pair_hi(s)
    neg_inf = jnp.asarray(-jnp.inf, dtype=hi.dtype)
    masked = jnp.where(emitted, hi, neg_inf)
    best = jnp.max(masked)
    candidates = emitted & (masked == best)
    decided = None
    if isinstance(s, tuple):
        at_hi = candidates
        masked_lo = jnp.where(at_hi, s[1], neg_inf)
        candidates = at_hi & (masked_lo == jnp.max(masked_lo))
        decided = jnp.any(at_hi & ~candidates)
    order_key = jnp.where(
        candidates, emit_order, jnp.asarray(2**31 - 1, jnp.int32)
    )
    return jnp.argmin(order_key), best, decided


class ScoreInputs(NamedTuple):
    """Arena-shaped kernel inputs.  All float arrays share one dtype
    (f64 for bit-parity tests on CPU, f32 on TPU).  `perm` is the rotated
    visit order for this select; `n_candidates` the number of real
    candidates at its front."""

    cpu_total: jnp.ndarray  # [C] node capacity minus node-reserved
    mem_total: jnp.ndarray  # [C]
    disk_total: jnp.ndarray  # [C]
    cpu_used: jnp.ndarray  # [C] proposed usage (state + plan deltas)
    mem_used: jnp.ndarray  # [C]
    disk_used: jnp.ndarray  # [C]
    feasible: jnp.ndarray  # bool[C] all static+dynamic feasibility masks
    collisions: jnp.ndarray  # i32[C] proposed allocs of same job+tg
    penalty: jnp.ndarray  # bool[C] rescheduling penalty nodes
    affinity_score: jnp.ndarray  # f[C] normalized affinity score
    spread_boost: jnp.ndarray  # f[C] total spread boost
    perm: jnp.ndarray  # i32[C] walk order: perm[i] = row at position i
    ask_cpu: jnp.ndarray  # f scalar
    ask_mem: jnp.ndarray  # f scalar
    ask_disk: jnp.ndarray  # f scalar
    desired_count: jnp.ndarray  # i32 scalar (tg.count)
    limit: jnp.ndarray  # i32 scalar (visit limit; INT32_MAX = unlimited)
    n_candidates: jnp.ndarray  # i32 scalar
    # policy-weighted scoring: absent (None) for policy-less jobs.  A
    # None NamedTuple field contributes no pytree leaves, so the
    # policy-off kernel keeps today's compiled signatures AND traces
    # the bit-identical computation (the fused terms below are guarded
    # by a trace-time `is not None`); a present PolicyTerms forks one
    # new pinned signature per ladder rung (ops/contracts.py).
    policy: Optional[PolicyTerms] = None


def _score_terms(inp: ScoreInputs, spread_fit: bool):
    """Returns (feasible_after_fit bool[C], final scores: f[C] on the
    float64 trace, a pair of f[C] on the float32 trace)."""
    dtype = inp.cpu_total.dtype
    cpu_after = inp.cpu_used + inp.ask_cpu
    mem_after = inp.mem_used + inp.ask_mem
    disk_after = inp.disk_used + inp.ask_disk

    fit = (
        (cpu_after <= inp.cpu_total)
        & (mem_after <= inp.mem_total)
        & (disk_after <= inp.disk_total)
    )
    feasible = inp.feasible & fit

    safe_cpu_total = jnp.where(inp.cpu_total > 0, inp.cpu_total, 1.0)
    safe_mem_total = jnp.where(inp.mem_total > 0, inp.mem_total, 1.0)
    # the fitness exponential is DEFINED at float32 precision
    # (structs/funcs.py _pow10) and everything after it at float64: an
    # array there, a pair on the float32 trace (ScoreList)
    scores = ScoreList(
        cpu_after, safe_cpu_total, mem_after, safe_mem_total,
        inp.collisions, inp.desired_count, spread_fit, dtype,
    )
    scores.penalty(inp.penalty)

    has_aff = inp.affinity_score != 0.0
    scores.append(jnp.where(has_aff, inp.affinity_score, 0.0), has_aff)

    has_spread = inp.spread_boost != 0.0
    scores.append(jnp.where(has_spread, inp.spread_boost, 0.0), has_spread)

    # policy-weighted terms append LAST so the serial oracle's
    # left-to-right float-sum order is preserved (PolicyIterator sits
    # after SpreadIterator in the chain).  Trace-time guard: with
    # policy=None this block vanishes and the kernel is bit-identical
    # to the policy-less build.
    if inp.policy is not None:
        pol = inp.policy
        # terms arrive pre-scaled (PolicyTerms docstring), so each
        # present group is one add into the running sum: the term is
        # already 0 wherever it must not append (a zero add is exact —
        # score_sum is never -0.0, and np.zeros stages +0.0), so only
        # the count needs a flag/predicate
        if pol.tput_term is not None:
            scores.append(pol.tput_term, pol.has_tput)
        if pol.mig_term is not None:
            scores.append(pol.mig_term)

    return feasible, scores.mean()


def _score_vectors(inp: ScoreInputs, spread_fit: bool):
    """Returns (feasible_after_fit bool[C], final_scores f[C]): the
    storm solver and the sharded select read one float a candidate
    (a float32 pair's ``hi``)."""
    feasible, final = _score_terms(inp, spread_fit)
    return feasible, pair_hi(final)


def _limited_walk_argmax(
    feasible: jnp.ndarray,
    scores: jnp.ndarray,
    perm: jnp.ndarray,
    limit: jnp.ndarray,
    n_candidates: jnp.ndarray,
):
    """Emulate LimitIterator + MaxScoreIterator over all nodes at once.

    `perm` is the *rotated* visit order for this select: the reference's
    StaticIterator keeps its offset across Reset (feasible.go:75-113), so
    consecutive selects continue round-robin through the shuffled list;
    the caller rotates the permutation by the accumulated pull count and
    advances it by the returned `pulls`.

    The walk visits feasible nodes in order.  The first up-to-3 nodes
    scoring <= threshold are diverted to a side list that is replayed
    only if the source runs dry before `limit` nodes were emitted
    (select.go:35-75).  Replay normally preserves diversion order; with
    exactly two diverted nodes the reference's re-skip quirk replays them
    in reverse (the first diverted node is re-appended before being
    returned), which we reproduce.  The winner is the strict maximum over
    emitted nodes, earliest emitted wins ties (select.go:94-113).

    Pull accounting: if at least `limit` nodes are emitted from the
    source, the walk stops at the limit-th one and the pull count is its
    1-based position; otherwise the whole candidate list is consumed.
    Infeasible nodes consume pulls (they are filtered mid-chain), which
    is exactly how the reference's rotation advances.
    """
    if isinstance(scores, tuple):
        s = (scores[0][perm], scores[1][perm])
    else:
        s = scores[perm]
    f = feasible[perm]

    bad = f & under_threshold(s)
    bad_rank = jnp.cumsum(bad.astype(jnp.int32))
    diverted = bad & (bad_rank <= MAX_SKIP)
    nd = f & ~diverted
    nd_cum = jnp.cumsum(nd.astype(jnp.int32))
    nd_count = nd_cum[-1]
    nd_rank = nd_cum - 1
    n_div = jnp.sum(diverted.astype(jnp.int32))
    div_rank = jnp.cumsum(diverted.astype(jnp.int32)) - 1
    # two-diverted replay reversal (see docstring) — only when a
    # non-diverted emission preceded the replay; with no good nodes
    # the source exhausts inside the first skip loop and the tail
    # _next_option replays in ORIGINAL order (select.py next())
    div_order = jnp.where(
        (n_div == 2) & (nd_count > 0), 1 - div_rank, div_rank
    )
    emit_order = jnp.where(nd, nd_rank, nd_count + div_order)
    emitted = f & (emit_order < limit)

    win_pos, best, _decided = earliest_best(s, emitted, emit_order)
    chosen_row = perm[win_pos]
    any_emitted = jnp.any(emitted)
    chosen_row = jnp.where(any_emitted, chosen_row, NO_NODE)

    limit_reached = nd_count >= limit
    lth_pos = jnp.argmax(nd_cum >= limit)
    pulls = jnp.where(limit_reached, lth_pos + 1, n_candidates)
    return chosen_row, best, jnp.sum(f.astype(jnp.int32)), pulls


@functools.partial(jax.jit, static_argnames=("spread_fit",))
def score_and_select(inp: ScoreInputs, spread_fit: bool = False):
    """Returns (chosen_row, chosen_score, feasible_count, pulls).
    chosen_row == -1 when no feasible node was emitted."""
    feasible, final = _score_terms(inp, spread_fit)
    chosen_row, best, feasible_count, pulls = _limited_walk_argmax(
        feasible, final, inp.perm, inp.limit, inp.n_candidates
    )
    return chosen_row, best, feasible_count, pulls


@functools.partial(jax.jit, static_argnames=("spread_fit",))
def score_and_select_packed(inp: ScoreInputs, spread_fit: bool = False):
    """score_and_select with all outputs packed into ONE i32[2] array
    ([chosen_row, pulls]) so the host pays a single device->host sync
    per select — each fetch is a full device round trip."""
    chosen_row, _best, _n, pulls = score_and_select(
        inp, spread_fit=spread_fit
    )
    return jnp.stack(
        [chosen_row.astype(jnp.int32), pulls.astype(jnp.int32)]
    )


def make_perm(rng, rows, capacity: int) -> np.ndarray:
    """Walk order matching the oracle's seeded Fisher-Yates shuffle
    (sched/feasible.py shuffle_nodes) applied to the same candidate list:
    perm[i] = arena row visited at walk position i.  Arena rows not in the
    candidate list are appended at the end; they are masked infeasible and
    can never win, but keep the perm a full permutation of the arena."""
    rows = list(rows)
    for i in range(len(rows) - 1, 0, -1):
        j = rng.randint(0, i)
        rows[i], rows[j] = rows[j], rows[i]
    present = set(rows)
    rows.extend(r for r in range(capacity) if r not in present)
    return np.asarray(rows, dtype=np.int32)
