"""Device mesh + shardings for multi-chip scheduling.

The reference scales by running `NumSchedulers` workers per server and
federating regions over Serf/Raft (SURVEY.md section 2.10); the TPU-native
equivalents are two mesh axes:

* ``evals`` — data parallelism over independent evaluations (the unit the
  reference parallelizes across workers; broker dedup keeps them
  conflict-light, the plan applier serializes the rest);
* ``nodes`` — the long axis: the cluster's node table sharded across
  chips, the honest analog of sequence/context parallelism for a cluster
  scheduler (SURVEY.md section 5 "long-context").

Scoring is embarrassingly parallel along ``nodes``: the only cross-shard
communication is an all-gather of the per-node score/feasibility vectors
(f64 + bool per node — tens of KB at 10k nodes, ICI-cheap) plus O(devices)
walk carries.  In the production chained planner (sharded_chained_plan)
the selection walk itself is ALSO sharded along the permuted axis —
local cumsums with an exchanged per-shard carry (parallel scan), pmin/
pmax winner reductions — so per-device FLOPs genuinely scale ~1/devices
(asserted via compiled cost analysis in tests/test_parallel.py) while
decisions stay bit-identical to the single-chip kernel.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.batch import BatchInputs, plan_picks
from ..ops.score import (
    ScoreInputs,
    _fit_exponentials,
    _limited_walk_argmax,
    _score_vectors,
    pair_hi,
)


def shard_map(f=None, **kwargs):
    """shard_map with replication checking off: the selection walk's
    outputs are replicated by construction (post-all-gather), which the
    static varying-axes inference cannot prove."""
    return jax.shard_map(f, check_vma=False, **kwargs)


# -- multi-host distribution (NOMAD_TPU_DIST_*) ------------------------
#
# The same NamedSharding program that shards the node axis across one
# host's chips runs UNCHANGED across processes on a TPU pod: each
# process holds its own slice of every P("nodes") array, the jitted
# shard_map collectives rendezvous over ICI/DCN, and every process
# executes the identical SPMD launch sequence (the multi-controller
# contract).  `distributed_init` is the one-time bring-up; the
# zero-config default (knobs unset, or one process) stays exactly the
# single-process mesh of PR 8.


class DistConfig(NamedTuple):
    coordinator: str  # host:port of process 0's coordinator service
    num_processes: int
    process_id: int


def dist_config() -> Optional[DistConfig]:
    """The NOMAD_TPU_DIST_* knobs, or None when multi-host is not
    opted into (`NOMAD_TPU_DIST` != 1).  With the opt-in set, a
    malformed process count / id RAISES instead of being coerced: a
    member silently degrading to single-host is exactly the
    peer-deadlock the loud-failure contract exists to prevent."""
    if os.environ.get("NOMAD_TPU_DIST") != "1":
        return None
    coord = os.environ.get("NOMAD_TPU_DIST_COORD", "127.0.0.1:8476")
    try:
        procs = int(os.environ.get("NOMAD_TPU_DIST_PROCS", "1"))
        pid = int(os.environ.get("NOMAD_TPU_DIST_ID", "0"))
    except ValueError as exc:
        raise ValueError(
            "NOMAD_TPU_DIST=1 but NOMAD_TPU_DIST_PROCS/"
            "NOMAD_TPU_DIST_ID are not integers — refusing to "
            "guess: a member that silently fell back to "
            "single-host would deadlock its peers' first "
            f"collective ({exc})"
        ) from exc
    if procs <= 1:
        # documented off-switch: <=1 keeps distributed init off
        return DistConfig(coord, 1, 0)
    if not 0 <= pid < procs:
        raise ValueError(
            f"NOMAD_TPU_DIST_ID={pid} out of range for "
            f"NOMAD_TPU_DIST_PROCS={procs}"
        )
    return DistConfig(coord, procs, pid)


_dist_initialized = False


def distributed_init() -> bool:
    """Idempotent `jax.distributed.initialize` from the
    NOMAD_TPU_DIST_* knobs.  Returns True when this process is part
    of a live multi-process world, False for the single-process
    default (knobs unset, or NOMAD_TPU_DIST_PROCS <= 1 — with one
    process nothing needs a coordinator, and calling initialize after
    the backend warmed up would be an error in embedding tests).

    Must run before the first backend touch (`jax.devices()` et al.);
    `make_mesh` and the BatchWorker's mesh construction both call it
    first, so a server whose operator set the knobs joins the pod
    before any kernel compiles.  A misconfigured world (bad
    coordinator, wrong process count) RAISES rather than silently
    degrading to single-process: the peers would deadlock waiting for
    this process inside their first collective.

    On the CPU backend (the tier-1-hermetic harness: spawned local
    processes) cross-process collectives need the gloo implementation;
    it is selected here before the backend initializes.
    """
    global _dist_initialized
    cfg = dist_config()
    if cfg is None or cfg.num_processes <= 1:
        return False
    if _dist_initialized:
        return True
    # the one decision that must read JAX_PLATFORMS itself: the
    # collectives implementation is picked BEFORE the backend exists,
    # so there is nothing resolved to ask yet
    plats = os.environ.get("JAX_PLATFORMS", "")
    cpu_only = bool(plats) and (
        set(p.strip() for p in plats.split(",")) <= {"cpu"}
    )
    if not plats or cpu_only:
        # CPU multiprocess computations are only implemented over
        # gloo; must be picked before the backend client exists.
        # Unset JAX_PLATFORMS counts too — a host whose backend
        # merely RESOLVES to cpu would otherwise handshake fine and
        # then stall every peer at the first collective (the late,
        # pod-wide failure the loud-misconfig contract forbids)
        try:
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo"
            )
        except Exception:
            if cpu_only:
                # an explicitly-CPU world cannot collectivize
                # without gloo — fail now, not mid-chain
                raise
            # unset platform on an accelerator build without the
            # option: the accelerator runtime owns collectives
    jax.distributed.initialize(
        coordinator_address=cfg.coordinator,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
    )
    _dist_initialized = True
    # Touch the backend NOW: the global-topology exchange only
    # completes once every process has initialized its local backend,
    # and jaxlib gives the laggard a hard 5-minute deadline.  A head
    # whose first mesh launch arrives later than that (quiet follower,
    # slow machine) would kill every peer blocked in jax.devices() —
    # warming eagerly makes world formation independent of when the
    # scheduler first needs the mesh.
    jax.devices()
    return True


def host_count(mesh: Mesh) -> int:
    """Distinct processes contributing devices to this mesh."""
    return len({d.process_index for d in mesh.devices.flat})


def is_multihost(mesh: Mesh) -> bool:
    return host_count(mesh) > 1


def local_device_positions(mesh: Mesh) -> list:
    """Positions along the mesh's flattened device order owned by
    THIS process — the rows of a per-device staging stack this host
    actually ships (everything else is another host's slice)."""
    me = jax.process_index()
    return [
        i
        for i, d in enumerate(mesh.devices.flat)
        if d.process_index == me
    ]


def local_device_count(mesh: Mesh) -> int:
    """This process's devices on the mesh's node axis — the divisor
    of every per-host traffic figure."""
    return len(local_device_positions(mesh))


def mesh_put(mesh: Mesh, arr, spec) -> jax.Array:
    """Commit a host array onto the mesh under ``spec``.  Fully
    addressable (single process): a plain ``device_put`` — byte-for-
    byte the PR 8 path.  Multi-host: ``make_array_from_callback``, so
    each process stages ONLY its own addressable shards (a replicated
    spec stages one copy per local device; a P("nodes") column stages
    this host's rows and nothing else) — no host ever ships another
    host's slice, and no full column crosses the network."""
    sh = NamedSharding(mesh, spec)
    if sh.is_fully_addressable:
        return jax.device_put(arr, sh)
    host = np.asarray(arr)
    return jax.make_array_from_callback(
        host.shape, sh, lambda idx: host[idx]
    )


def make_mesh(
    n_devices: Optional[int] = None,
    eval_axis: Optional[int] = None,
    backend: Optional[str] = None,
) -> Mesh:
    """Build an (evals, nodes) mesh over the backend's devices — the
    default backend's unless ``backend`` names another.  A backend
    with fewer devices than requested yields the smaller mesh (callers
    check ``mesh.devices.size``); no other backend is substituted.

    With the NOMAD_TPU_DIST_* knobs set, `distributed_init` joins the
    multi-process world first and ``jax.devices()`` returns EVERY
    host's devices — the node axis then spans the whole pod and the
    same sharded programs run unchanged across processes."""
    distributed_init()
    devices = jax.devices(backend) if backend else jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if eval_axis is None:
        # favor the node axis: it is the long dimension
        eval_axis = 2 if (n % 2 == 0 and n >= 4) else 1
    node_axis = n // eval_axis
    mesh_devices = np.asarray(devices).reshape(eval_axis, node_axis)
    return Mesh(mesh_devices, axis_names=("evals", "nodes"))


def node_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("nodes"))


def eval_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("evals"))


def sharded_score_and_select(mesh: Mesh, spread_fit: bool = False):
    """The node-sharded single-placement kernel: each device scores its
    shard of the node arena locally (O(N/devices) work, columns resident
    per shard), the per-node score/feasibility vectors are all-gathered
    over ICI, and the selection walk runs replicated — bit-identical to
    the single-chip kernel.

    ScoreInputs layout: node-indexed fields sharded P('nodes'); `perm`
    and scalars replicated.
    """
    node_fields = ScoreInputs(
        cpu_total=P("nodes"),
        mem_total=P("nodes"),
        disk_total=P("nodes"),
        cpu_used=P("nodes"),
        mem_used=P("nodes"),
        disk_used=P("nodes"),
        feasible=P("nodes"),
        collisions=P("nodes"),
        penalty=P("nodes"),
        affinity_score=P("nodes"),
        spread_boost=P("nodes"),
        perm=P(),
        ask_cpu=P(),
        ask_mem=P(),
        ask_disk=P(),
        desired_count=P(),
        limit=P(),
        n_candidates=P(),
    )

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(node_fields,),
        out_specs=(P(), P(), P(), P()),
    )
    def _run(inp: ScoreInputs):
        feasible, final = _score_vectors(inp, spread_fit)
        final = jax.lax.all_gather(final, "nodes", axis=0, tiled=True)
        feasible = jax.lax.all_gather(
            feasible, "nodes", axis=0, tiled=True
        )
        return _limited_walk_argmax(
            feasible, final, inp.perm, inp.limit, inp.n_candidates
        )

    return _run


def _sharded_walk(final_full, feas_full, perm, off, lim, nc,
                  shard, n_dev, shard_size):
    """The rotating limited-walk selection with the O(C) math sharded
    along the PERM axis: each device walks its contiguous slice of the
    permuted ordering; global prefix counts come from a local cumsum
    plus an exchanged per-shard carry (classic parallel scan), and the
    winner/pulls reductions exchange only O(devices) scalars.  Decisions
    are bit-identical to ops/batch._walk."""
    from ..ops.score import MAX_SKIP, NO_NODE, SKIP_THRESHOLD

    big = jnp.asarray(2**31 - 1, jnp.int32)
    lo = shard * shard_size
    pos_l = lo + jnp.arange(shard_size, dtype=jnp.int32)
    perm_l = jax.lax.dynamic_slice_in_dim(perm, lo, shard_size)
    s_l = final_full[perm_l]
    f_l = feas_full[perm_l]
    is_tail = pos_l >= nc
    in_wrap = pos_l < off
    wp_l = jnp.where(
        is_tail, pos_l, jnp.mod(pos_l - off + nc, nc)
    )

    off_shard = (off - 1) // shard_size
    off_local = jnp.mod(off - 1, shard_size)

    def rot(b_l):
        local_cs = jnp.cumsum(b_l.astype(jnp.int32))
        sums = jax.lax.all_gather(local_cs[-1], "nodes")  # (D,)
        carry = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(sums)[:-1]]
        )[shard]
        cs_l = local_cs + carry
        total = jnp.sum(sums)
        own = shard == off_shard
        c_off_val = jax.lax.psum(
            jnp.where(own, jnp.take(cs_l, off_local), 0), "nodes"
        )
        c_off = jnp.where(off > 0, c_off_val, 0)
        pre = jnp.where(in_wrap, cs_l + (total - c_off), cs_l - c_off)
        return jnp.where(is_tail, total, pre), total

    bad = f_l & (s_l <= SKIP_THRESHOLD)
    bad_rank, _ = rot(bad)
    diverted = bad & (bad_rank <= MAX_SKIP)
    nd = f_l & ~diverted
    nd_incl, nd_count = rot(nd)
    div_incl, n_div = rot(diverted)
    div_rank = div_incl - 1
    # reversal only when a non-diverted emission preceded the replay
    # (see ops/score.py _limited_walk_argmax)
    div_order = jnp.where(
        (n_div == 2) & (nd_count > 0), 1 - div_rank, div_rank
    )
    emit_order = jnp.where(nd, nd_incl - 1, nd_count + div_order)
    emitted = f_l & (emit_order < lim)

    neg_inf = jnp.asarray(-jnp.inf, dtype=s_l.dtype)
    masked = jnp.where(emitted, s_l, neg_inf)
    best = jax.lax.pmax(jnp.max(masked), "nodes")
    candidates = emitted & (masked == best)
    order_key = jnp.where(candidates, emit_order, big)
    local_win = jnp.argmin(order_key)
    local_key = jnp.take(order_key, local_win)
    gmin = jax.lax.pmin(local_key, "nodes")
    win_pos = jax.lax.pmin(
        jnp.where(
            local_key == gmin,
            (lo + local_win).astype(jnp.int32),
            big,
        ),
        "nodes",
    )
    any_emitted = jax.lax.pmax(jnp.any(emitted), "nodes")

    limit_reached = nd_count >= lim
    lth_wp = jax.lax.pmin(
        jnp.min(jnp.where(nd & (nd_incl == lim), wp_l, big)),
        "nodes",
    )
    pulls = jnp.where(limit_reached, lth_wp + 1, nc)
    row = jnp.where(any_emitted, perm[win_pos], NO_NODE)
    return row, any_emitted, pulls


def chain_in_specs(
    with_spread: bool = False, spread_even: bool = False
) -> tuple:
    """The sharded chained runner's input PartitionSpecs, positionally
    aligned with `sharded_chained_plan`'s argument tuple.  Shared by
    the runner itself and `place_chain_inputs` (the multi-host launch
    staging), so the two cannot drift."""
    from ..ops.batch import PreDeltas, SpreadInputs, StepDeltas

    col = P("nodes")
    in_specs = (
        col, col, col,            # totals
        col, col, col,            # used0
        P(None, "nodes"),         # feasible [E, C]
        P(),                      # perm [E, C] replicated (global ids)
        P(), P(), P(),            # asks [E]
        P(),                      # desired_count [E]
        P(),                      # limit [E]
        P(),                      # wanted [E]
        P(),                      # n_candidates [E]
        P(),                      # distinct_hosts [E]
        P(None, "nodes"),         # coll0 [E, C]
        P(None, "nodes"),         # affinity [E, C]
        StepDeltas(               # leading axis E, row-space
            evict_rows=P(), evict_cpu=P(), evict_mem=P(),
            evict_disk=P(), evict_coll=P(), penalty_rows=P(),
        ),
        PreDeltas(rows=P(), cpu=P(), mem=P(), disk=P()),
    )
    if with_spread:
        in_specs = in_specs + (
            SpreadInputs(              # leading axis E
                codes=P(None, None, "nodes"),  # [E, S, C]
                desired=P(), used0=P(), proposed0=P(),
                cleared0=P(), weight=P(), active=P(),
                # percent-only batches pass even=None (skips tracing
                # the min/max block, mirroring the unsharded kernel)
                even=P() if spread_even else None,
            ),
        )
    return in_specs


def place_chain_inputs(
    mesh: Mesh, args: tuple,
    with_spread: bool = False, spread_even: bool = False,
) -> tuple:
    """Commit a chunk launch's host-staged arguments onto a MULTI-host
    mesh under the runner's own in_specs: node-axis leaves land as each
    process's own shard slices, per-eval leaves replicate onto local
    devices only, and already-committed device arrays (the sharded
    usage mirror, the previous chunk's carry) pass through untouched.
    Single-process launches never need this — jit places host arrays
    itself — but a multi-controller jit cannot conjure a global array
    from process-local host data."""
    specs = chain_in_specs(with_spread, spread_even)

    def place(a, s):
        if a is None:
            return None
        if hasattr(a, "_fields"):  # NamedTuple-of-arrays inputs
            return type(a)(
                *[place(f, sf) for f, sf in zip(a, s)]
            )
        if isinstance(a, jax.Array):  # carry / mirror: committed
            return a
        return mesh_put(mesh, a, s)

    return tuple(place(a, s) for a, s in zip(args, specs))


def sharded_chained_plan(mesh: Mesh, n_picks: int,
                         spread_fit: bool = False,
                         with_spread: bool = False,
                         spread_even: bool = False,
                         return_carry: bool = False):
    """The production chained planner with REAL node-axis sharding:
    every per-pick quantity that is O(nodes) — fit masks, fitness,
    anti-affinity, penalties, usage scatter — is computed on the
    device's own node shard (O(C/devices) FLOPs per device), and only
    the per-pick score/feasibility vectors are all-gathered over ICI
    for the replicated limited-walk selection (f64+bool per node, tens
    of KB at 10k nodes).  Serially equivalent across evals exactly like
    `chained_plan_picks_cols`: the sharded usage columns carry forward
    through the eval scan.

    Scope: single-group shapes (no ports/devices in the sharded
    variant).  ``with_spread=True`` adds the in-kernel spread carry
    (VERDICT r4 #9: spread streams must exercise the multi-chip path):
    the per-node spread contributions (percent AND even mode) compute
    on each shard from its own codes slice, the small (S, V+1)
    proposed/cleared carries stay replicated, and the winner's /
    evictee's value-slot one-hots reduce over shards with one psum per
    pick.  Decisions are bit-identical to the unsharded kernel — the
    walk consumes the same score vector in the same order.

    Returns ``run(cpu_total, mem_total, disk_total, used0_cpu,
    used0_mem, used0_disk, feasible[E,C], perm[E,C], asks..., wanted,
    limits, n_candidates, coll0[E,C], deltas, pre) ->
    (rows[E,P], pulls[E,P])``.  ``pulls`` is the per-pick
    source-iterator consumption — identical to the unsharded kernel's,
    so mesh-path preempt retries replay through the same passthrough
    machinery as the serial chain.

    With ``return_carry=True`` the final eval-scan carry — the chained
    (cpu, mem, disk) usage columns, still sharded ``P("nodes")`` — is
    returned as a third output.  Feeding it into the next launch's
    ``used0_*`` is bit-identical to one longer launch (a lax.scan cut
    at an eval boundary), which is what lets the mesh path run through
    the BatchWorker's double-buffered chunk pipeline: the sharded
    usage columns thread chunk -> chunk entirely on-device.  The
    ``used0_*`` inputs may be host arrays or device-resident
    ``NamedSharding(P("nodes"))`` arrays (the sharded usage mirror /
    the previous chunk's carry) — no resharding happens either way.
    """
    from ..ops.batch import spread_contribution
    from ..ops.score import NO_NODE

    n_dev = mesh.devices.size
    col = P("nodes")

    in_specs = chain_in_specs(with_spread, spread_even)

    # rows/pulls are replicated by construction (post-all-gather walk);
    # the usage carry stays sharded along the node axis so a chunked
    # chain never gathers it
    out_specs = (P(), P())
    if return_carry:
        out_specs = out_specs + ((col, col, col),)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )
    def _run(
        cpu_total, mem_total, disk_total,
        used0_cpu, used0_mem, used0_disk,
        feasible_all, perm_all,
        ask_cpu, ask_mem, ask_disk,
        desired_count, limits, wanted, n_candidates,
        distinct_hosts, coll0_all, affinity_all, deltas, pre,
        *spread_xs,
    ):
        spread_all = spread_xs[0] if with_spread else None
        shard = jax.lax.axis_index("nodes")
        shard_size = cpu_total.shape[0]
        lo = shard * shard_size

        safe_cpu = jnp.where(cpu_total > 0, cpu_total, 1.0)
        safe_mem = jnp.where(mem_total > 0, mem_total, 1.0)
        dtype = cpu_total.dtype

        def local_scatter(colv, row, delta, pred):
            idx = row - lo
            ok = pred & (idx >= 0) & (idx < shard_size)
            safe = jnp.clip(idx, 0, shard_size - 1)
            return colv.at[safe].add(
                jnp.where(ok, delta, jnp.zeros_like(delta))
            )

        def eval_step(used, xs):
            (feas_l, perm, a_cpu, a_mem, a_disk, desired, lim, w,
             nc, dh, coll_l, aff_l, d, p) = xs[:14]
            sp = xs[14] if with_spread else None
            cpu_u, mem_u, disk_u = used
            if sp is not None:
                # per-shard static spread state (mirrors the unsharded
                # kernel's hoisted lookups, on this shard's codes)
                dtype_s = cpu_total.dtype
                _S, V1 = sp.desired.shape
                onehot_l = jax.nn.one_hot(
                    sp.codes, V1, dtype=dtype_s
                )  # (S, Cl, V1)
                desired_node_l = jnp.einsum(
                    "scv,sv->sc", onehot_l, sp.desired
                )
                penalty_node_l = sp.codes == (V1 - 1)
                safe_desired_l = jnp.where(
                    desired_node_l != 0, desired_node_l, 1.0
                )
                spread_existing = sp.used0.astype(dtype_s)

                def slot_onehot(row, pred):
                    # the row's value-slot one-hot, reduced over
                    # shards: the owner contributes, others zero
                    idx = row - lo
                    mine = pred & (idx >= 0) & (idx < shard_size)
                    safe = jnp.clip(idx, 0, shard_size - 1)
                    oh = onehot_l[:, safe, :]  # (S, V1)
                    local = jnp.where(mine, oh, 0.0)
                    return jax.lax.psum(local, "nodes")
            # pre-placement deltas (row space, applied to local shard)
            def apply_pre(colv, vals):
                out = colv
                # R is small; scan-free loop unrolled by XLA
                def body(i, acc):
                    return local_scatter(
                        acc, p.rows[i], vals[i].astype(acc.dtype),
                        jnp.asarray(True),
                    )
                return jax.lax.fori_loop(
                    0, p.rows.shape[0], body, out
                )
            cpu_u = apply_pre(cpu_u, p.cpu)
            mem_u = apply_pre(mem_u, p.mem)
            disk_u = apply_pre(disk_u, p.disk)

            def pick_step(carry, k):
                if sp is not None:
                    (cpu_c, mem_c, disk_c, coll_c, pen_c, off,
                     dead, spread_prop, spread_clr) = carry
                else:
                    (cpu_c, mem_c, disk_c, coll_c, pen_c, off,
                     dead) = carry
                    spread_prop = spread_clr = None
                active = (k < w) & ~dead
                erow = d.evict_rows[k]
                app = active & (erow >= 0)
                if sp is not None:
                    # the evicted alloc's value slot gains one cleared
                    # use BEFORE this pick scores (propertyset counts
                    # the staged stop as cleared)
                    spread_clr = spread_clr + slot_onehot(erow, app)
                cpu_c = local_scatter(
                    cpu_c, erow, d.evict_cpu[k].astype(dtype), app
                )
                mem_c = local_scatter(
                    mem_c, erow, d.evict_mem[k].astype(dtype), app
                )
                disk_c = local_scatter(
                    disk_c, erow, d.evict_disk[k].astype(dtype), app
                )
                coll_c = local_scatter(
                    coll_c, erow, d.evict_coll[k], app
                )
                prow = d.penalty_rows[k]  # (K,) global rows
                local_rows = lo + jnp.arange(shard_size)
                pen_now = pen_c | jnp.any(
                    local_rows[:, None] == prow[None, :], axis=1
                )
                # local scoring (O(C/devices))
                cpu_after = cpu_c + a_cpu
                mem_after = mem_c + a_mem
                disk_after = disk_c + a_disk
                fit = (
                    (cpu_after <= cpu_total)
                    & (mem_after <= mem_total)
                    & (disk_after <= disk_total)
                )
                # distinct_hosts via the collision carry, as in the
                # unsharded kernel
                feas = feas_l & fit & ~(dh & (coll_c > 0))
                # one float a score here: a float32 trace's pair
                # gives its hi, the float32 sum
                base = pair_hi(_fit_exponentials(
                    cpu_after, safe_cpu, mem_after, safe_mem, dtype
                ))
                if spread_fit:
                    fitness = jnp.clip(base - 2.0, 0.0, 18.0)
                else:
                    fitness = jnp.clip(20.0 - base, 0.0, 18.0)
                score_sum = fitness / 18.0
                count = jnp.ones_like(score_sum)
                has_coll = coll_c > 0
                anti = jnp.where(
                    has_coll,
                    -(coll_c.astype(dtype) + 1.0)
                    / desired.astype(dtype),
                    0.0,
                )
                score_sum = score_sum + anti
                count = count + has_coll.astype(dtype)
                score_sum = score_sum - pen_now.astype(dtype)
                count = count + pen_now.astype(dtype)
                has_aff = aff_l != 0.0
                score_sum = score_sum + jnp.where(has_aff, aff_l, 0.0)
                count = count + has_aff.astype(dtype)
                if sp is not None:
                    # spread boost per stanza on this shard's nodes —
                    # the (S, V+1) carries are replicated, so the
                    # combined-use math is collective-free; only the
                    # winner/evictee one-hots psum (slot_onehot).
                    # Shared implementation with the unsharded kernel
                    # (spread_contribution) so the two cannot drift.
                    spread_total_l = spread_contribution(
                        onehot_l, desired_node_l, penalty_node_l,
                        safe_desired_l, spread_existing,
                        spread_prop, spread_clr, sp.weight,
                        sp.active, sp.even, dtype,
                    )
                    has_spread = spread_total_l != 0.0
                    score_sum = score_sum + spread_total_l
                    count = count + has_spread.astype(dtype)
                final_l = score_sum / count

                # the ONLY cross-shard traffic: the per-node score +
                # feasibility vectors (for the permuted re-slice) and
                # O(devices) walk carries
                final = jax.lax.all_gather(
                    final_l, "nodes", axis=0, tiled=True
                )
                feas_full = jax.lax.all_gather(
                    feas, "nodes", axis=0, tiled=True
                )
                win_row, any_emitted, pulls = _sharded_walk(
                    final, feas_full, perm, off, lim, nc,
                    shard, n_dev, shard_size,
                )
                ok = active & any_emitted
                dead = dead | (active & ~any_emitted)
                row = jnp.where(ok, win_row, NO_NODE)
                # per-pick source consumption, surfaced exactly like
                # the unsharded kernel (inactive picks pull nothing)
                pulls_out = jnp.where(active, pulls, 0)
                cpu_c = local_scatter(
                    cpu_c, row, jnp.asarray(a_cpu, dtype), ok
                )
                mem_c = local_scatter(
                    mem_c, row, jnp.asarray(a_mem, dtype), ok
                )
                disk_c = local_scatter(
                    disk_c, row, jnp.asarray(a_disk, dtype), ok
                )
                coll_c = local_scatter(
                    coll_c, row, jnp.asarray(1, jnp.int32), ok
                )
                off = jnp.mod(
                    off + jnp.where(active, pulls, 0), nc
                )
                if sp is not None:
                    # the placed node's value slot gains one proposed
                    # use per stanza
                    spread_prop = spread_prop + slot_onehot(row, ok)
                    return (
                        cpu_c, mem_c, disk_c, coll_c, pen_c, off,
                        dead, spread_prop, spread_clr,
                    ), (row, pulls_out)
                return (
                    cpu_c, mem_c, disk_c, coll_c, pen_c, off, dead
                ), (row, pulls_out)

            carry0 = (
                cpu_u, mem_u, disk_u, coll_l,
                jnp.zeros(shard_size, dtype=bool),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(False),
            )
            if sp is not None:
                carry0 = carry0 + (
                    sp.proposed0.astype(cpu_total.dtype),
                    sp.cleared0.astype(cpu_total.dtype),
                )
            final_carry, (rows, pulls) = jax.lax.scan(
                pick_step, carry0,
                jnp.arange(n_picks, dtype=jnp.int32),
            )
            return (
                (final_carry[0], final_carry[1], final_carry[2]),
                (rows, pulls),
            )

        used0 = (used0_cpu, used0_mem, used0_disk)
        xs_all = (
            feasible_all, perm_all, ask_cpu, ask_mem, ask_disk,
            desired_count, limits, wanted, n_candidates,
            distinct_hosts, coll0_all, affinity_all, deltas, pre,
        )
        if with_spread:
            xs_all = xs_all + (spread_all,)
        final, (rows, pulls) = jax.lax.scan(eval_step, used0, xs_all)
        if return_carry:
            return rows, pulls, final
        return rows, pulls

    return _run


def sharded_batch_plan(
    mesh: Mesh,
    n_candidates: int,
    n_picks: int,
    spread_fit: bool = False,
):
    """Build the sharded batched planner: node columns sharded over the
    ``nodes`` axis, the eval batch sharded over ``evals``; scoring is
    local, score vectors are all-gathered over ``nodes`` for the
    replicated selection walk.

    Returns a function
    ``(cpu_total, mem_total, disk_total, batch: BatchInputs) -> rows[E,P]``
    whose arguments may be host arrays; shardings are applied via
    `jax.device_put` inside.
    """

    col_spec = P("nodes")
    # per-eval fields: node-indexed ones shard on both axes, scalars on
    # evals only
    batch_spec = BatchInputs(
        feasible=P("evals", "nodes"),
        base_cpu_used=P("evals", "nodes"),
        base_mem_used=P("evals", "nodes"),
        base_disk_used=P("evals", "nodes"),
        base_collisions=P("evals", "nodes"),
        penalty=P("evals", "nodes"),
        affinity_score=P("evals", "nodes"),
        perm=P("evals", "nodes"),
        ask_cpu=P("evals"),
        ask_mem=P("evals"),
        ask_disk=P("evals"),
        desired_count=P("evals"),
        limit=P("evals"),
        distinct_hosts=P("evals"),
    )

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(col_spec, col_spec, col_spec, batch_spec),
        out_specs=P("evals"),
    )
    def _run(cpu_total, mem_total, disk_total, batch: BatchInputs):
        # gather full node columns over the nodes axis (ICI all-gather);
        # the walk needs the global ordering
        gather = lambda x: jax.lax.all_gather(
            x, "nodes", axis=0, tiled=True
        )
        cpu_t = gather(cpu_total)
        mem_t = gather(mem_total)
        disk_t = gather(disk_total)

        def one_eval(b: BatchInputs):
            full = BatchInputs(
                feasible=gather(b.feasible),
                base_cpu_used=gather(b.base_cpu_used),
                base_mem_used=gather(b.base_mem_used),
                base_disk_used=gather(b.base_disk_used),
                base_collisions=gather(b.base_collisions),
                penalty=gather(b.penalty),
                affinity_score=gather(b.affinity_score),
                perm=gather(b.perm),
                ask_cpu=b.ask_cpu,
                ask_mem=b.ask_mem,
                ask_disk=b.ask_disk,
                desired_count=b.desired_count,
                limit=b.limit,
                distinct_hosts=b.distinct_hosts,
            )
            return plan_picks(
                cpu_t,
                mem_t,
                disk_t,
                full,
                jnp.asarray(n_candidates, jnp.int32),
                n_picks,
                spread_fit,
            )

        return jax.vmap(one_eval)(batch)

    def run(cpu_total, mem_total, disk_total, batch: BatchInputs):
        return _run(cpu_total, mem_total, disk_total, batch)

    return run
