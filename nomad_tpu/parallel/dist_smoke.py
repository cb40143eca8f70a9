"""Two-process distributed smoke: the tier-1-hermetic proof that the
multi-host mesh actually works, on nothing but the CPU backend.  A
CPU-only CI harness: every child is pinned to ``JAX_PLATFORMS=cpu``
(a chip belongs to one process, so this is never run against one).

``python -m nomad_tpu.parallel.dist_smoke`` spawns N local worker
processes (default 2), wires them into one jax.distributed world via
the production ``NOMAD_TPU_DIST_*`` knobs (gloo CPU collectives), and
drives each through the REAL pipeline in lockstep — the
multi-controller SPMD contract: every process executes the identical
launch sequence, each holding only its own node-axis shards.

Per worker, in order:

1. **Distributed init + pod mesh** — `distributed_init()` from the
   knobs, then a Server whose BatchWorker mesh spans every host's
   devices (`_mesh_hosts == procs`).
2. **Chain** — a batch of single-group jobs through the worker's own
   ``_process_batch``: the full assemble/launch/fetch/replay pipeline
   over the distributed mesh, sharded usage carry threading
   chunk -> chunk, zero lost evals.  Drives the bench row's
   end-to-end placements/s.
3. **Cross-host flush** — dirty rows from a live commit, then a warm
   sharded mirror sync: the per-host delta protocol
   (`patch_rows_hostlocal`) must stage exactly the closed-form
   O(dirty rows) bytes per host, against the O(nodes) full upload.
4. **Storm** — a same-family backlog drained by the real
   ``_maybe_drain_storm`` and solved by the NODE-SHARDED auction over
   the distributed mesh, committed through the normal fences; plus a
   kernel-level A/B asserting the sharded solve is bit-identical to
   the single-device solve (and timing both for the bench row).
5. **Cross-host parity** — placement digests allgathered across
   processes must agree exactly: every host computed the same answer
   from its own shards.

Determinism note: the workers are driven SYNCHRONOUSLY (the broker
consumer thread stays paused) with all evals enqueued before any
dispatch, admission off and the latency budget disabled — so both
processes provably issue the same collective launch sequence.  A
divergent sequence would deadlock the gloo rendezvous, which is
exactly why the production multi-host path pins compiles inline and
plans chunk widths from shared state only.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import socket
import subprocess
import sys
import time
from typing import List, Optional

# world shape: small enough that compiles dominate nothing, big
# enough that every device owns multiple node rows and every phase
# crosses the process boundary
DEVICES_PER_PROC = 2
CHAIN_NODES = 12  # -> capacity 16: tiles over 4 devices
CHAIN_JOBS = 12
FAMILY_JOBS = 16
KERNEL_E, KERNEL_A, KERNEL_C = 16, 64, 256


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# worker (one process of the distributed world; env set by the launcher)
# ---------------------------------------------------------------------------


def _digest(value) -> int:
    blob = json.dumps(value, sort_keys=True, default=str)
    return int.from_bytes(
        hashlib.sha256(blob.encode()).digest()[:8], "big"
    ) % (2**62)


def _assert_same_everywhere(tag: str, value) -> None:
    """Allgather a digest of ``value`` across processes and require
    agreement — the cross-host parity fence (and a phase barrier)."""
    import numpy as np
    from jax.experimental import multihost_utils

    got = multihost_utils.process_allgather(
        np.asarray([_digest(value)], np.int64)
    ).ravel()
    if not (got == got[0]).all():
        raise AssertionError(
            f"cross-host divergence in {tag}: digests {got.tolist()}"
        )


def _make_nodes(n, seed=0):
    import random

    from nomad_tpu import mock
    from nomad_tpu.structs import compute_node_class

    rng = random.Random(seed)
    nodes = []
    for i in range(n):
        node = mock.node(id=f"dist-node-{seed}-{i:03d}")
        node.node_resources.cpu = rng.choice([4000, 8000])
        node.node_resources.memory_mb = rng.choice([8192, 16384])
        node.computed_class = compute_node_class(node)
        nodes.append(node)
    return nodes


def _make_jobs(n, prefix="dist", seed=1):
    import random

    from nomad_tpu import mock

    rng = random.Random(seed)
    jobs = []
    for i in range(n):
        job = mock.job(id=f"{prefix}-{i:03d}")
        job.task_groups[0].count = rng.randint(1, 3)
        job.task_groups[0].tasks[0].resources.cpu = rng.choice(
            [200, 400]
        )
        jobs.append(job)
    return jobs


def _family_jobs(n, fam="distfam"):
    from nomad_tpu import mock

    jobs = []
    for i in range(n):
        job = mock.job(id=f"{fam}/dispatch-{i:04d}")
        job.type = "batch"
        job.task_groups[0].count = 1
        job.task_groups[0].tasks[0].resources.cpu = 500
        job.task_groups[0].tasks[0].resources.memory_mb = 1024
        jobs.append(job)
    return jobs


def _drain_broker(server, worker, expect: int, timeout=30.0):
    """Wait until the quiescent broker holds ``expect`` ready evals,
    then dequeue them all (FIFO) — the deterministic stand-in for the
    run() gulp, taken while the consumer thread is paused."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if server.broker.ready_count(worker.schedulers) >= expect:
            break
        time.sleep(0.02)
    members = []
    for _ in range(expect):
        ev, token = server.broker.dequeue(
            worker.schedulers, timeout=5.0
        )
        assert ev is not None, (
            f"broker ran dry at {len(members)}/{expect}"
        )
        members.append((ev, token))
    return members


def _drain_residuals(server, worker, jobs, timeout=30.0):
    """Process late-arriving evals (blocked-eval requeues, watcher
    re-evaluations) until every eval is terminal and the broker is
    dry — in LOCKSTEP: each round allgathers (ready, terminal) so
    every process dequeues the same batch in the same round, keeping
    the collective launch sequences identical.  State is replicated,
    so only thread TIMING differs across processes; the barrier per
    round absorbs that skew."""
    import numpy as np
    from jax.experimental import multihost_utils

    deadline = time.monotonic() + timeout
    while True:
        ready = server.broker.ready_count(worker.schedulers)
        term = all(
            _settled(e)
            for job in jobs
            for e in server.store.evals_by_job("default", job.id)
        )
        agg = multihost_utils.process_allgather(
            np.asarray([ready, int(term)], np.int64)
        ).reshape(-1, 2)
        max_ready = int(agg[:, 0].max())
        all_term = bool(agg[:, 1].all())
        if max_ready == 0 and all_term:
            return
        assert time.monotonic() < deadline, (
            f"residual evals never settled: ready={agg[:, 0].tolist()}"
            f" terminal={agg[:, 1].tolist()}"
        )
        if max_ready > 0:
            # the same eval set exists on every process (replicated
            # state) — wait for this process's copy, then process
            # the identical batch everywhere
            while (
                server.broker.ready_count(worker.schedulers)
                < max_ready
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            batch = []
            for _ in range(max_ready):
                ev, token = server.broker.dequeue(
                    worker.schedulers, timeout=5.0
                )
                assert ev is not None, "residual eval vanished"
                batch.append((ev, token))
            leftover = worker._process_batch(batch)
            for _ in range(8):
                if not leftover:
                    break
                leftover = worker._process_batch(leftover)
            assert not leftover
        else:
            time.sleep(0.05)


def _placements(server, jobs):
    return sorted(
        (job.id, a.name, a.node_id)
        for job in jobs
        for a in server.store.allocs_by_job("default", job.id)
        if not a.terminal_status()
    )


def _settled(e) -> bool:
    """Fully processed: terminal, or parked BLOCKED for capacity —
    the zero-lost contract is "no eval stranded mid-pipeline", and a
    blocked eval was processed to completion and is waiting on a
    future capacity change, exactly like production."""
    return e.terminal_status() or e.should_block()


def _assert_zero_lost(server, jobs):
    for job in jobs:
        evs = server.store.evals_by_job("default", job.id)
        assert evs, f"no evals for {job.id}"
        bad = [
            (e.id, e.status, e.status_description)
            for e in evs
            if not _settled(e)
        ]
        assert not bad, (
            f"unsettled evals for {job.id}: {bad} "
            f"(broker ready={server.broker.ready_count(['batch', 'service'])})"
        )
    assert server.broker.failed() == []


def _kernel_storm_problem(E, A, C, dtype):
    import numpy as np

    from nomad_tpu.ops.solve import StormInputs

    rng = np.random.default_rng(17)
    perm = np.tile(rng.permutation(C).astype(np.int32), (E, 1))
    inp = StormInputs(
        feasible=rng.random((E, C)) > 0.1,
        affinity=np.where(
            rng.random((E, C)) > 0.8,
            rng.random((E, C)).astype(dtype),
            0.0,
        ).astype(dtype),
        collisions=(rng.random((E, C)) > 0.9).astype(np.int32),
        perm=perm,
        limit=np.full(E, 2, np.int32),
        n_cand=np.full(E, C, np.int32),
        eval_of=(np.arange(A) % E).astype(np.int32),
        penalty=rng.random((A, C)) > 0.95,
        ask=np.tile(
            np.asarray((1000.0, 100.0, 100.0), dtype), (A, 1)
        ),
        desired=np.ones(A, np.int32),
        real=np.ones(A, bool),
        pre_cpu=np.zeros(C, dtype),
        pre_mem=np.zeros(C, dtype),
        pre_disk=np.zeros(C, dtype),
    )
    cols = tuple(
        np.asarray(x, dtype)
        for x in (
            np.full(C, 4000.0),
            np.full(C, 8192.0),
            np.full(C, 100000.0),
            rng.integers(0, 1000, C).astype(dtype),
            np.zeros(C),
            np.zeros(C),
        )
    )
    return inp, cols


def run_worker() -> int:
    """One process of the distributed world.  Exits non-zero on any
    parity or liveness failure; rank 0 prints the result JSON."""
    assert os.environ.get("NOMAD_TPU_DIST") == "1", (
        "worker needs the NOMAD_TPU_DIST_* env (use the launcher)"
    )
    # the ONE ordering requirement: join the world before anything
    # touches the backend
    from nomad_tpu.parallel.mesh import distributed_init

    assert distributed_init(), "distributed init did not engage"
    import jax
    import numpy as np

    rank = jax.process_index()
    procs = jax.process_count()
    result = {
        "procs": procs,
        "devices_per_host": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }

    from nomad_tpu.server import Server

    # -- phase: server + pod mesh -------------------------------------
    # long heartbeat TTL: this harness drives the worker
    # synchronously and pays multi-second XLA compiles mid-phase; the
    # default 30s TTL would mark every (clientless) node down during
    # a cold compile under CI load and block all placements
    server = Server(
        num_schedulers=1, seed=29, batch_pipeline=True,
        heartbeat_ttl=600.0,
    )
    worker = server.workers[0]
    # drive the pipeline synchronously: the consumer thread never
    # starts, so the gulp composition — and with it the collective
    # launch sequence — is identical on every process
    worker.start = lambda: None  # type: ignore[method-assign]
    for node in _make_nodes(CHAIN_NODES, seed=5):
        server.register_node(node)
    chain_jobs = _make_jobs(CHAIN_JOBS, seed=7)
    for job in chain_jobs:
        server.register_job(job)
    server.start()
    try:
        mesh = worker._mesh
        assert mesh is not None, "no mesh on the distributed world"
        assert mesh.devices.size == jax.device_count()
        assert worker._mesh_hosts == procs, (
            worker._mesh_hosts, procs
        )
        table = server.store.node_table
        assert table.capacity % mesh.devices.size == 0, (
            table.capacity, mesh.devices.size
        )

        # -- phase: chain (assemble/launch/fetch/replay) --------------
        members = _drain_broker(server, worker, CHAIN_JOBS)
        t0 = time.monotonic()
        leftover = worker._process_batch(members)
        for _ in range(8):
            if not leftover:
                break
            leftover = worker._process_batch(leftover)
        chain_dt = time.monotonic() - t0
        assert not leftover, f"{len(leftover)} evals stuck"
        assert worker.mesh_used > 0, "sharded launches never ran"
        _drain_residuals(server, worker, chain_jobs)
        _assert_zero_lost(server, chain_jobs)
        placed = _placements(server, chain_jobs)
        assert placed, "chain placed nothing"
        _assert_same_everywhere("chain placements", placed)
        result["chain"] = {
            "evals": CHAIN_JOBS,
            "placements": len(placed),
            "placements_per_sec": round(len(placed) / chain_dt, 1),
            "mesh_launches": worker.mesh_used,
        }

        # -- phase: per-host cross-host flush -------------------------
        from nomad_tpu.ops.batch import pow2_bucket
        from nomad_tpu.parallel.mesh import local_device_count

        n_dev = mesh.devices.size
        n_local = local_device_count(mesh)
        size = table.capacity // n_dev
        gen = worker._usage_cache_sharded["gen"]
        _, dirty = server.store.usage_delta_since(gen)
        worker._device_columns(table, sharded=True)
        staged = server.metrics.get_gauge("mesh.bytes_per_flush")
        full = (
            sum(
                c.nbytes
                for c in (
                    table.cpu_total, table.mem_total,
                    table.disk_total, table.cpu_used,
                    table.mem_used, table.disk_used,
                )
            )
            * n_local
            // n_dev
        )
        if dirty:
            idx = np.asarray(sorted(dirty), np.int32)
            per_dev = [
                int(((idx >= d * size) & (idx < (d + 1) * size)).sum())
                for d in range(n_dev)
            ]
            w = pow2_bucket(max(1, max(per_dev)), floor=8)
            want = n_local * w * 4 + 3 * n_local * w * 8
            assert staged == want, (staged, want, per_dev)
        else:
            assert staged == 0.0, staged
        assert staged < full, (staged, full)
        result["flush"] = {
            "dirty_rows": len(dirty),
            "bytes_per_flush_delta_per_host": staged,
            "bytes_per_flush_full_per_host": full,
        }

        # -- phase: storm (sharded auction over the pod mesh) ---------
        fam_jobs = _family_jobs(FAMILY_JOBS)
        for job in fam_jobs:
            server.register_job(job)
        # wait for the whole wave to land, then dequeue ONE member
        # and let the REAL detector drain the family prefix — the
        # broker is quiescent, so every process sees the same storm
        deadline = time.monotonic() + 30.0
        while (
            server.broker.ready_count(worker.schedulers)
            < FAMILY_JOBS
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        ev0, token0 = server.broker.dequeue(
            worker.schedulers, timeout=5.0
        )
        assert ev0 is not None
        assert ev0.job_id.startswith("distfam/"), (
            f"stray eval {ev0.job_id} raced the storm phase"
        )
        storm = worker._maybe_drain_storm(ev0, token0)
        assert storm is not None and len(storm) == FAMILY_JOBS, (
            "storm detector missed the family backlog"
        )
        leftover = worker._process_storm(storm)
        for _ in range(8):
            if not leftover:
                break
            leftover = worker._process_batch(leftover)
        assert not leftover
        assert worker.storm_solves >= 1, "storm solve never ran"
        _drain_residuals(server, worker, chain_jobs + fam_jobs)
        _assert_zero_lost(server, fam_jobs)
        storm_placed = _placements(server, fam_jobs)
        _assert_same_everywhere("storm placements", storm_placed)
        result["storm"] = {
            "members": FAMILY_JOBS,
            "solves": worker.storm_solves,
            "fallbacks": worker.storm_fallbacks,
            "placements": len(storm_placed),
            "solve_wall_s": round(
                worker.timings["storm_solve"], 4
            ),
        }

        # -- phase: kernel A/B — sharded == single-device, timed ------
        from nomad_tpu.ops.solve import (
            storm_assignment,
            storm_assignment_sharded,
        )
        from nomad_tpu.parallel.mesh import mesh_put
        from nomad_tpu.sched.storm import stage_for_mesh
        from jax.sharding import PartitionSpec as P

        dtype = np.asarray(table.cpu_total).dtype
        inp, cols = _kernel_storm_problem(
            KERNEL_E, KERNEL_A, KERNEL_C, dtype
        )
        single = storm_assignment(
            inp, cols, spread_fit=False, max_rounds=KERNEL_A
        )
        single = tuple(np.asarray(x) for x in single)

        fn = storm_assignment_sharded(
            mesh, spread_fit=False, max_rounds=KERNEL_A
        )
        s_inp = stage_for_mesh(inp, mesh)
        s_cols = tuple(
            mesh_put(mesh, c, P("nodes")) for c in cols
        )
        sharded = tuple(
            np.asarray(x) for x in fn(s_inp, s_cols)
        )
        for name, a, b in zip(
            ("assigned", "pulls", "acc_round", "score", "greedy",
             "rounds"),
            single, sharded,
        ):
            assert np.array_equal(a, b), (
                f"sharded storm diverged from single-device in "
                f"{name}"
            )
        def best_of(f, n=3):
            best = float("inf")
            for _ in range(n):
                t = time.monotonic()
                jax.block_until_ready(f())
                best = min(best, time.monotonic() - t)
            return best

        t_single = best_of(
            lambda: storm_assignment(
                inp, cols, spread_fit=False, max_rounds=KERNEL_A
            )
        )
        t_sharded = best_of(lambda: fn(s_inp, s_cols))
        result["storm_kernel"] = {
            "rows": KERNEL_A,
            "arena": KERNEL_C,
            "rounds": int(single[5]),
            "bit_identical": True,
            "single_device_ms": round(t_single * 1000.0, 2),
            "sharded_ms": round(t_sharded * 1000.0, 2),
        }
        _assert_same_everywhere(
            "kernel assignment", sharded[0].tolist()
        )
        result["cross_host_parity"] = True
        result["zero_lost"] = True
    finally:
        server.stop()
    if rank == 0:
        print("DIST_SMOKE_JSON " + json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# launcher
# ---------------------------------------------------------------------------


def launch(
    procs: int = 2,
    devices_per_proc: int = DEVICES_PER_PROC,
    timeout: float = 420.0,
) -> dict:
    """Spawn the distributed smoke and return rank 0's result row.
    Raises RuntimeError (with the children's log tails) on failure or
    timeout — a collective deadlock must fail the gate, not hang it."""
    import tempfile

    port = _free_port()
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    log_dir = tempfile.mkdtemp(prefix="dist_smoke_")
    children: List[subprocess.Popen] = []
    outs = []
    for rank in range(procs):
        env = dict(os.environ)
        # hermetic world: the parent shell's NOMAD_TPU_* knobs must
        # not reshape (or fail) the deterministic gate — children see
        # ONLY the pinned knob set below
        for key in [k for k in env if k.startswith("NOMAD_TPU_")]:
            del env[key]
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "JAX_ENABLE_X64": "1",
                "XLA_FLAGS": (
                    "--xla_force_host_platform_device_count="
                    f"{devices_per_proc}"
                ),
                "NOMAD_TPU_DIST": "1",
                "NOMAD_TPU_DIST_COORD": f"127.0.0.1:{port}",
                "NOMAD_TPU_DIST_PROCS": str(procs),
                "NOMAD_TPU_DIST_ID": str(rank),
                "NOMAD_TPU_MESH": "1",
                "NOMAD_TPU_STORM": "1",
                "NOMAD_TPU_STORM_MIN": "8",
                # lockstep determinism: no timing-dependent admission
                # or width planning, compiles block inline
                "NOMAD_TPU_ADMIT": "0",
                "NOMAD_TPU_LATENCY_BUDGET_MS": "0",
                "NOMAD_TPU_SYNC_COMPILE": "1",
                "NOMAD_TPU_BROKER_WATCHDOG": "1",
            }
        )
        out = open(
            os.path.join(log_dir, f"p{rank}.log"), "w+"
        )
        outs.append(out)
        children.append(
            subprocess.Popen(
                [
                    sys.executable, "-m",
                    "nomad_tpu.parallel.dist_smoke", "--worker",
                ],
                env=env,
                cwd=repo_root,
                stdout=out,
                stderr=subprocess.STDOUT,
            )
        )
    deadline = time.monotonic() + timeout
    rcs: List[Optional[int]] = [None] * procs
    while time.monotonic() < deadline and any(
        rc is None for rc in rcs
    ):
        for i, child in enumerate(children):
            if rcs[i] is None:
                rcs[i] = child.poll()
        time.sleep(0.2)
    for child in children:
        if child.poll() is None:
            child.kill()
    for child in children:
        # reap before reading tails: a SIGKILL'd child's buffered
        # output may not have landed yet, and an unreaped child
        # lingers as a zombie in long-lived bench/pytest parents
        try:
            child.wait(timeout=10)
        except Exception:  # noqa: BLE001 — diagnostics best-effort
            pass
    tails = []
    for rank, out in enumerate(outs):
        out.seek(0)
        tails.append((rank, out.read()))
        out.close()
    if any(rc != 0 for rc in rcs):
        detail = "\n".join(
            f"--- rank {rank} (rc={rcs[rank]}) ---\n{tail[-3000:]}"
            for rank, tail in tails
        )
        raise RuntimeError(
            f"distributed smoke failed (rcs={rcs}, "
            f"timeout={'yes' if None in rcs else 'no'}, "
            f"logs in {log_dir}):\n{detail}"
        )
    for line in tails[0][1].splitlines():
        if line.startswith("DIST_SMOKE_JSON "):
            return json.loads(line[len("DIST_SMOKE_JSON "):])
    raise RuntimeError(
        "distributed smoke exited clean but emitted no result row"
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="multi-host mesh smoke (spawned CPU processes)"
    )
    parser.add_argument("--worker", action="store_true")
    parser.add_argument("--procs", type=int, default=2)
    parser.add_argument(
        "--devices-per-proc", type=int, default=DEVICES_PER_PROC
    )
    parser.add_argument("--timeout", type=float, default=420.0)
    args = parser.parse_args(argv)
    if args.worker:
        return run_worker()
    result = launch(
        procs=args.procs,
        devices_per_proc=args.devices_per_proc,
        timeout=args.timeout,
    )
    print(json.dumps(result, indent=2))
    print(
        f"dist_smoke: OK — {result['procs']} processes x "
        f"{result['devices_per_host']} devices, zero lost, "
        "cross-host parity held"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
