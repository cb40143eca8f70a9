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

Score-append semantics are reproduced as a (sum, count) pair: each term
contributes to the sum and increments the count only under the reference's
append conditions; the final score is sum/count.

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
    them as it always has; the float32 trace (``jax_enable_x64`` off,
    the dtype a chip deploys) carries share and exponential as two
    float32s (ops/twofloat.py), so the float32 it scores with is the
    same number, bit for bit, on the CPU and on the TPU — which
    float32 division and pow are not."""
    if dtype == jnp.float32:
        # both resources through one trace of the arithmetic: a launch
        # shape's compile time is in its count of operations
        cpu_after, cpu_cap, mem_after, mem_cap = jnp.broadcast_arrays(
            cpu_after, cpu_cap, mem_after, mem_cap
        )
        p = twofloat.pow10_free(
            jnp.stack([cpu_after, mem_after]), jnp.stack([cpu_cap, mem_cap])
        )
        return p[0] + p[1]
    free_cpu = 1.0 - cpu_after / cpu_cap
    free_mem = 1.0 - mem_after / mem_cap
    return _pow10(free_cpu, dtype) + _pow10(free_mem, dtype)


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


def _score_vectors(inp: ScoreInputs, spread_fit: bool):
    """Returns (feasible_after_fit bool[C], final_scores f[C])."""
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
    # (structs/funcs.py _pow10); the sum continues in the working dtype
    base = _fit_exponentials(
        cpu_after, safe_cpu_total, mem_after, safe_mem_total, dtype
    )
    if spread_fit:
        fitness = jnp.clip(base - 2.0, 0.0, 18.0)
    else:
        fitness = jnp.clip(20.0 - base, 0.0, 18.0)
    binpack = fitness / 18.0

    score_sum = binpack
    count = jnp.ones_like(binpack)

    has_collision = inp.collisions > 0
    anti = jnp.where(
        has_collision,
        -(inp.collisions.astype(dtype) + 1.0)
        / inp.desired_count.astype(dtype),
        0.0,
    )
    score_sum = score_sum + anti
    count = count + has_collision.astype(dtype)

    score_sum = score_sum - inp.penalty.astype(dtype)
    count = count + inp.penalty.astype(dtype)

    has_aff = inp.affinity_score != 0.0
    score_sum = score_sum + jnp.where(has_aff, inp.affinity_score, 0.0)
    count = count + has_aff.astype(dtype)

    has_spread = inp.spread_boost != 0.0
    score_sum = score_sum + jnp.where(has_spread, inp.spread_boost, 0.0)
    count = count + has_spread.astype(dtype)

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
            score_sum = score_sum + pol.tput_term
            count = count + pol.has_tput
        if pol.mig_term is not None:
            score_sum = score_sum + pol.mig_term
            count = count + (pol.mig_term != 0.0).astype(dtype)

    final = score_sum / count
    return feasible, final


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
    s = scores[perm]
    f = feasible[perm]

    bad = f & (s <= SKIP_THRESHOLD)
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

    neg_inf = jnp.asarray(-jnp.inf, dtype=s.dtype)
    masked = jnp.where(emitted, s, neg_inf)
    best = jnp.max(masked)
    candidates = emitted & (masked == best)
    order_key = jnp.where(
        candidates, emit_order, jnp.asarray(2**31 - 1, jnp.int32)
    )
    win_pos = jnp.argmin(order_key)
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
    feasible, final = _score_vectors(inp, spread_fit)
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
