#!/usr/bin/env python3
"""chip_smoke.py — the served placement path, once, on the real chip.

Drives what a user drives: a ``Server(batch_pipeline=True)`` built as
``nomad-tpu agent`` builds it, behind its HTTP listener, over a fleet
of 10,000 nodes carrying 100,000 resident allocations (the BASELINE
headline; same shapes as ``bench.populate``).  Jobs go in through
``POST /v1/jobs`` and placements come back through
``GET /v1/job/<id>/allocations``:

  warm   ``BatchWorker.warm_shapes`` plus a discarded first wave, so
         every launch shape compiles through the production mechanism
         (the cold-compile shield), never ``NOMAD_TPU_SYNC_COMPILE``
  wave   the asserted wave: binpack service jobs in a burst (mid-chain
         chunks, a delta-patched mirror), one ``batch`` job, one
         service job with a datacenter spread and a node affinity

The same seeded stream then runs through the sequential oracle
``Server(batch_pipeline=False)`` — plain host code, no JAX — and every
job's (alloc name -> node id) set must be identical: bit-identical
placement is the system's guarantee, here checked at the dtype the chip
runs (f32; the run fails if ``jax_enable_x64`` is on).

What it proves, since PR 31: a smoke, not the guarantee.  One seed, 50
jobs, at float32 — where the kernels now score with the float64
definition's own ``10^(1 - used/cap)`` (ops/twofloat.py) and no longer
with the TPU's float32 ``pow``, which used to move 0-5 placements a
benchmark window.  The guarantee is held by the benchmark's cells
(``BENCHMARK.json``: ``binpack-10k-f64.deploy`` is this fleet under a
closed loop of 128 jobs, every placement of about 5,000 jobs a run
judged against a plain reference, at float64; the float32 cells follow
as data, PERF.md section 7) and by tests/test_float32_scoring.py.

The run FAILS (non-zero exit, no ``"ok": true`` line) unless JAX
resolves a TPU, every job placed, placements match the oracle, the
asserted wave was prescored on the device with no fallback, error,
cold shape, failed compile, failover or watchdog trip, the usage
mirror and a launch result live on TPU devices, and the persistent
compile cache was written.

One process, no child processes, no ``NOMAD_TPU_*`` knob set: defaults
are what ships.  Times are information, not claims.

    python3 chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import random
import sys
import time
import traceback
import urllib.error
import urllib.request

N_NODES = 10_000
N_ALLOCS = 100_000
TG_COUNT = 10  # placements per binpack / batch job
SPREAD_COUNT = 6  # placements of the spread job (BASELINE config 3)
N_BINPACK = 48  # binpack service jobs per wave (>= 32)
DATACENTERS = ("dc1", "dc2", "dc3")
WAVE_DEADLINE_S = 420.0
COMPILE_DEADLINE_S = 420.0
# counters the asserted wave must leave untouched
FLAT_COUNTERS = (
    "batch_worker.fallbacks",
    "batch_worker.errors",
    "batch_worker.cold_shape_fallbacks",
    "batch_worker.compile_failures",
)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class SmokeFailure(Exception):
    """A named check failed; the run exits non-zero."""

    def __init__(self, name: str, detail: str) -> None:
        super().__init__(f"{name}: {detail}")
        self.name = name


# -- the chip, or nothing ------------------------------------------------


def require_tpu():
    """Resolve JAX's backend FIRST and refuse anything but a TPU.
    The TPU is asked for explicitly, so a missing chip is JAX's own
    error rather than a quiet drop to the CPU (``,cpu`` keeps the
    supervisor's failover target available, as on a TPU host with the
    variable unset)."""
    from nomad_tpu.backend import resolve_backend

    seen = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    try:
        backend = resolve_backend()
    except Exception as exc:  # noqa: BLE001 — reported, then exit
        first = (str(exc).strip().splitlines() or [repr(exc)])[0]
        log(
            f"no TPU: found no tpu backend (JAX_PLATFORMS seen={seen!r}"
            f", requested 'tpu,cpu'): {type(exc).__name__}: {first}"
        )
        return None
    import jax

    log(
        f"backend: platform={backend.platform} "
        f"device_kind={backend.device_kind} "
        f"devices={backend.device_count} "
        f"(JAX_PLATFORMS seen={seen!r}) "
        f"jax_enable_x64={jax.config.jax_enable_x64}"
    )
    if backend.platform != "tpu":
        log(f"no TPU: found platform={backend.platform}")
        return None
    return backend


# -- world and stream, from the seed -------------------------------------


def seed_world(store, seed: int, n_nodes: int, n_allocs: int) -> None:
    """The bench.populate fleet — node and alloc resource shapes
    unchanged — spread over three datacenters so the spread job has
    something to spread over."""
    from nomad_tpu import mock
    from nomad_tpu.structs import (
        AllocatedResources,
        AllocatedSharedResources,
        AllocatedTaskResources,
        Allocation,
        alloc_name,
        compute_node_class,
    )

    rng = random.Random(seed)
    nodes = []
    class_cache: dict = {}
    for i in range(n_nodes):
        n = mock.node(id=f"smoke-node-{i:05d}")
        n.datacenter = rng.choice(DATACENTERS)
        n.node_resources.cpu = rng.choice([8000, 16000, 32000])
        n.node_resources.memory_mb = rng.choice([16384, 32768, 65536])
        key = (
            n.datacenter, n.node_resources.cpu,
            n.node_resources.memory_mb,
        )
        if key not in class_cache:
            class_cache[key] = compute_node_class(n)
        n.computed_class = class_cache[key]
        store.upsert_node(n)
        nodes.append(n)
    filler = mock.job(id="filler")
    store.upsert_job(filler)
    allocs = []
    for i in range(n_allocs):
        node = nodes[rng.randrange(n_nodes)]
        allocs.append(
            Allocation(
                namespace="default",
                job_id="filler",
                job=filler,
                task_group="web",
                name=alloc_name("filler", "web", i),
                node_id=node.id,
                allocated_resources=AllocatedResources(
                    tasks={
                        "web": AllocatedTaskResources(
                            cpu=rng.choice([100, 200, 500]),
                            memory_mb=rng.choice([128, 256, 512]),
                        )
                    },
                    shared=AllocatedSharedResources(disk_mb=100),
                ),
                client_status="running",
            )
        )
    store.upsert_allocs(allocs)


def make_wave(wave: str, n_binpack: int, spread_count: int) -> list:
    """One wave of the stream as (job id, JSON payload, placements,
    alone) — the three kernel variants BASELINE configs 1-3 launch.
    ``alone`` jobs are submitted after everything before them placed,
    so their launch shape is the same in every wave.  A spread or
    affinity makes the sequential path walk EVERY node for each pick
    (minutes at this fleet size), and both the oracle and a cold
    launch shape take that path: ``spread_count`` keeps the warm
    wave's copy cheap without changing its launch shape."""
    from nomad_tpu import mock
    from nomad_tpu.api.codec import job_to_dict
    from nomad_tpu.structs import Affinity, Spread, SpreadTarget

    jobs = []
    for i in range(n_binpack):
        job = mock.job(id=f"{wave}-binpack-{i}")
        job.task_groups[0].count = TG_COUNT
        jobs.append((job, False))
    batch = mock.job(id=f"{wave}-batch")
    batch.type = "batch"
    batch.task_groups[0].count = TG_COUNT
    batch.task_groups[0].tasks[0].resources.cpu = 300
    jobs.append((batch, True))
    spread = mock.job(id=f"{wave}-spread")
    spread.task_groups[0].count = spread_count
    spread.task_groups[0].tasks[0].resources.cpu = 300
    spread.spreads = [
        Spread(
            attribute="${node.datacenter}",
            weight=60,
            targets=[
                SpreadTarget(value="dc1", percent=50),
                SpreadTarget(value="dc2", percent=30),
            ],
        )
    ]
    spread.affinities = [
        Affinity(
            ltarget="${node.datacenter}",
            operand="=",
            rtarget="dc2",
            weight=35,
        )
    ]
    jobs.append((spread, True))
    out = []
    for job, alone in jobs:
        job.datacenters = list(DATACENTERS)
        out.append(
            (job.id, job_to_dict(job), job.task_groups[0].count, alone)
        )
    return out


# -- the HTTP side, as a user drives it ----------------------------------


class Api:
    def __init__(self, port: int) -> None:
        self.base = f"http://127.0.0.1:{port}"

    def call(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data, method=method,
            headers={"Content-Type": "application/json"},
        )
        t_end = time.monotonic() + WAVE_DEADLINE_S
        while True:
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return json.loads(resp.read())
            except urllib.error.HTTPError as exc:
                # ingress backpressure is the server working as
                # designed: back off as it asks, within the deadline
                if exc.code != 429 or time.monotonic() > t_end:
                    raise
                time.sleep(float(exc.headers.get("Retry-After") or 1))

    def metrics(self) -> dict:
        return self.call("GET", "/v1/metrics")

    def placements(self, job_id: str) -> list:
        return sorted(
            (a["name"], a["node_id"])
            for a in self.call("GET", f"/v1/job/{job_id}/allocations")
            if a.get("desired_status") == "run"
        )


def submit_wave(submit, placed, wave: list, deadline_s: float) -> dict:
    """Submit a wave in order and wait until every job placed.
    ``submit(payload)`` registers one job; ``placed(job_id)`` returns
    its live (alloc name, node id) list.  Returns job id -> list."""
    t_end = time.monotonic() + deadline_s
    done: dict = {}

    def wait(pending: list) -> None:
        for job_id, want in pending:
            while True:
                got = placed(job_id)
                if len(got) >= want:
                    done[job_id] = got
                    break
                if time.monotonic() > t_end:
                    raise SmokeFailure(
                        "placement_deadline",
                        f"{job_id}: {len(got)}/{want} placed after "
                        f"{deadline_s:.0f}s",
                    )
                time.sleep(0.02)

    pending = []
    for job_id, payload, want, alone in wave:
        if alone:
            wait(pending)
            pending = []
        submit(payload)
        pending.append((job_id, want))
        if alone:
            wait(pending)
            pending = []
    wait(pending)
    return done


# -- compile accounting ---------------------------------------------------


class CompileLog:
    """Per-function backend-compile seconds and persistent-cache
    hits/misses, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.by_fun: dict = {}
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            name = str(kw.get("fun_name", "?"))
            self.by_fun.setdefault(name, []).append(secs)

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def report(self) -> dict:
        return {
            "signatures_compiled": sum(
                len(v) for v in self.by_fun.values()
            ),
            # JAX's own clock around compile-or-fetch-from-cache
            "backend_compile_s": round(
                sum(sum(v) for v in self.by_fun.values()), 3
            ),
            "persistent_cache_hits": self.cache_hits,
            "persistent_cache_misses": self.cache_misses,
            "by_function": {
                name: {
                    "n": len(v),
                    "total_s": round(sum(v), 3),
                    "max_s": round(max(v), 3),
                }
                for name, v in sorted(self.by_fun.items())
            },
        }


# -- the run ---------------------------------------------------------------


def build_server(batch_pipeline: bool, seed: int):
    """The server as ``cmd_agent`` builds it (nomad_tpu/cli.py), from
    an agent config: one scheduler and a fixed seed make the stream
    comparable with the oracle, and the simulated fleet never
    heartbeats, so its TTL is out of the way."""
    from nomad_tpu.config import AgentConfig
    from nomad_tpu.server import Server

    cfg = AgentConfig()
    cfg.server.num_schedulers = 1
    cfg.server.seed = seed
    cfg.server.heartbeat_ttl_s = 1e9
    cfg.server.batch_pipeline = batch_pipeline
    return Server(
        num_schedulers=cfg.server.num_schedulers,
        heartbeat_ttl=cfg.server.heartbeat_ttl_s,
        seed=cfg.server.seed,
        acl_enabled=cfg.acl.enabled,
        batch_pipeline=cfg.server.batch_pipeline,
        device_config=cfg.device,
    )


def check(failures: list, name: str, ok: bool, detail: str) -> None:
    if not ok:
        log(f"FAIL {name}: {detail}")
        failures.append({"check": name, "detail": detail})


def chain_counts(metrics: dict) -> tuple:
    """(launches, chains) so far: every chain is one assemble."""
    samples = metrics.get("samples", {})

    def count(stage: str) -> int:
        return int(
            (samples.get(f"batch_worker.{stage}") or {}).get("count", 0)
        )

    return count("launch") + count("mesh_launch"), count("assemble")


def run(args, backend) -> dict:
    """Everything after the platform gate.  ``backend`` is what JAX
    resolved; every device check is held to it."""
    import jax

    from nomad_tpu.api import start_http_server
    from nomad_tpu.api.codec import job_from_dict
    from nomad_tpu.ops.batch import chained_plan_picks_cols

    failures: list = []
    t_run = time.monotonic()
    check(
        failures, "x64_off", not jax.config.jax_enable_x64,
        "jax_enable_x64 is on; the deployed dtype is f32",
    )
    compiles = CompileLog()
    cache_dir = jax.config.jax_compilation_cache_dir
    log(f"compile cache: {cache_dir}")

    waves = {
        "warm": make_wave("warm", args.jobs, 1),
        "wave": make_wave("wave", args.jobs, SPREAD_COUNT),
    }

    # ---- the served path -------------------------------------------------
    t0 = time.monotonic()
    server = build_server(True, args.seed)
    seed_world(server.store, args.seed, args.nodes, args.allocs)
    seed_s = time.monotonic() - t0
    log(f"world: {args.nodes} nodes / {args.allocs} allocs in {seed_s:.1f}s")
    server.start()
    http = start_http_server(server, host="127.0.0.1", port=0)
    api = Api(http.port)
    worker = server.workers[0]
    got: dict = {}
    wave_counters: dict = {}
    try:
        t0 = time.monotonic()
        worker.warm_shapes()
        warm_shapes_s = time.monotonic() - t0
        log(f"warm_shapes: {warm_shapes_s:.1f}s")

        def submit(payload):
            api.call("POST", "/v1/jobs", {"Job": payload})

        got.update(
            submit_wave(
                submit, api.placements, waves["warm"], WAVE_DEADLINE_S
            )
        )
        # the discarded wave's first-seen shapes compile in the
        # background (cold-compile shield): wait them out
        t_end = time.monotonic() + COMPILE_DEADLINE_S
        while worker._compiling:
            if time.monotonic() > t_end:
                raise SmokeFailure(
                    "compile_deadline",
                    f"{len(worker._compiling)} shape(s) still compiling",
                )
            time.sleep(0.05)
        if not server.drain_to_idle(timeout=60):
            raise SmokeFailure("drain", "warm wave did not drain")
        compile_s = time.monotonic() - t0
        metrics0 = api.metrics()
        before = metrics0.get("counters", {})
        log(
            f"warm wave done: compile_s={compile_s:.1f} "
            f"shapes={len(worker._compiled)} "
            f"cold={before.get('batch_worker.cold_shape_fallbacks', 0)}"
        )

        # ---- the asserted wave -------------------------------------------
        def mirror_delta_syncs():
            # warm syncs of the usage mirror (the sharded twin's too)
            return worker._input_cache_hits + worker._mesh_mirror_hits

        launches0, chains0 = chain_counts(metrics0)
        mirror_hits0 = mirror_delta_syncs()
        t0 = time.monotonic()
        got.update(
            submit_wave(
                submit, api.placements, waves["wave"], WAVE_DEADLINE_S
            )
        )
        if not server.drain_to_idle(timeout=60):
            raise SmokeFailure("drain", "asserted wave did not drain")
        wave_s = time.monotonic() - t0
        metrics1 = api.metrics()
        after = metrics1.get("counters", {})
        n_evals = len(waves["wave"])
        rose = {
            k: int(after.get(k, 0) - before.get(k, 0))
            for k in ("batch_worker.prescored",) + FLAT_COUNTERS
        }
        wave_counters = {k.split(".", 1)[1]: v for k, v in rose.items()}
        # information, not a gate: how many mid-chain launches ran the
        # carry-donating executable (off-CPU only)
        wave_counters["donated_launches"] = int(
            after.get("batch_worker.donated_launches", 0)
            - before.get("batch_worker.donated_launches", 0)
        )
        check(
            failures, "prescored",
            rose["batch_worker.prescored"] == n_evals,
            f"prescored rose by {rose['batch_worker.prescored']}, "
            f"the wave holds {n_evals} evals",
        )
        for name in FLAT_COUNTERS:
            check(
                failures, name.split(".", 1)[1], rose[name] == 0,
                f"{name} rose by {rose[name]} in the asserted wave",
            )
        check(
            failures, "compile_failed", not worker._compile_failed,
            f"{len(worker._compile_failed)} launch shape(s) failed to "
            "compile and are parked on the host path",
        )
        # every chain starts with one assemble and one carry-less
        # launch; each further launch chained on a device carry
        launches1, chains1 = chain_counts(metrics1)
        mid_chain = (launches1 - launches0) - (chains1 - chains0)
        wave_counters["mid_chain_launches"] = mid_chain
        check(
            failures, "mid_chain_launches", mid_chain > 0,
            "no chunk of the asserted wave chained on a device carry",
        )
        mirror_hits = mirror_delta_syncs() - mirror_hits0
        wave_counters["mirror_delta_syncs"] = mirror_hits
        check(
            failures, "mirror_delta_sync", mirror_hits > 0,
            "the usage mirror was never delta-synced in the wave",
        )

        # ---- where the data lives ----------------------------------------
        cols = (worker._usage_cache or {}).get("cols") or ()
        col_platforms = sorted(
            {d.platform for c in cols for d in c.devices()}
        )
        check(
            failures, "mirror_on_device",
            bool(cols) and col_platforms == [backend.platform],
            f"usage mirror columns on {col_platforms or 'nothing'}",
        )
        # one launch of the production kernel at an already-warm
        # shape, on the worker's own mirror: the result to look at
        width = worker._chunk_buckets()[0]
        launch_args, launch_kwargs = worker._inert_launch(width)
        rows, pulls, _carry = jax.block_until_ready(
            chained_plan_picks_cols(*launch_args, **launch_kwargs)
        )
        out_platforms = sorted(
            {d.platform for a in (rows, pulls) for d in a.devices()}
        )
        check(
            failures, "launch_on_device",
            out_platforms == [backend.platform],
            f"launch outputs on {out_platforms}",
        )
        check(
            failures, "launch_shape",
            rows.shape == pulls.shape == (width, 16)
            and str(rows.dtype) == "int32",
            f"launch rows {rows.shape} {rows.dtype}",
        )

        device = api.call("GET", "/v1/device")
        for key, want in (
            ("platform", backend.platform),
            ("device_kind", backend.device_kind),
            ("device_count", backend.device_count),
            ("backend", backend.platform),
            ("enabled", backend.accelerated),
            ("failover_count", 0),
            ("watchdog_trips", 0),
            ("backend_epoch", 0),
        ):
            check(
                failures, f"device_{key}", device.get(key) == want,
                f"/v1/device {key}={device.get(key)!r}, want {want!r}",
            )
        stage_s = {k: round(v, 3) for k, v in worker.timings.items() if v}
        mesh = None
        if worker._mesh is not None:
            # NOMAD_TPU_MESH=1 from outside (the four-chip bring-up
            # run): what formed, and where the sharded mirror sits
            sharded = (worker._usage_cache_sharded or {}).get("cols") or ()
            mesh = {
                "devices": int(worker._mesh.devices.size),
                "mesh_used": worker.mesh_used,
                "mirror_shard_devices": sorted(
                    {str(d) for c in sharded for d in c.devices()}
                ),
            }
    finally:
        http.stop()
        server.stop()

    # ---- the oracle: same stream, plain host code ------------------------
    t0 = time.monotonic()
    oracle = build_server(False, args.seed)
    seed_world(oracle.store, args.seed, args.nodes, args.allocs)
    oracle.start()
    want: dict = {}
    try:

        def oracle_placed(job_id):
            return sorted(
                (a.name, a.node_id)
                for a in oracle.store.allocs_by_job("default", job_id)
                if not a.terminal_status()
            )

        for name in ("warm", "wave"):
            want.update(
                submit_wave(
                    lambda p: oracle.register_job(job_from_dict(p)),
                    oracle_placed, waves[name], WAVE_DEADLINE_S,
                )
            )
    finally:
        oracle.stop()
    oracle_s = time.monotonic() - t0
    differing = [j for j in want if got.get(j) != want[j]]
    identical = len(want) - len(differing)
    if differing:
        first = differing[0]
        check(
            failures, "parity", False,
            f"{len(differing)}/{len(want)} jobs differ from the oracle;"
            f" first {first}: chip={got.get(first)} oracle={want[first]}",
        )
    cache_files = (
        sum(len(f) for _r, _d, f in os.walk(cache_dir))
        if cache_dir and os.path.isdir(cache_dir)
        else 0
    )
    check(
        failures, "compile_cache", cache_files > 0,
        f"compile cache {cache_dir!r} holds no file",
    )

    import jaxlib

    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_version = None
    n_jobs = len(want)
    return {
        "ok": not failures,
        "failures": failures,
        "device": {
            "platform": backend.platform,
            "kind": backend.device_kind,
            "count": backend.device_count,
        },
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version,
        },
        "jax_enable_x64": bool(jax.config.jax_enable_x64),
        "seed": args.seed,
        "sizes": {
            "nodes": args.nodes,
            "resident_allocs": args.allocs,
            "arena_rows": int(cols[0].shape[0]) if cols else 0,
            "jobs": n_jobs,
            "placements": sum(len(v) for v in want.values()),
            "wave_evals": len(waves["wave"]),
        },
        "wave_counters": wave_counters,
        "parity": {"identical_jobs": identical, "jobs": n_jobs},
        "device_status": {
            k: device.get(k)
            for k in (
                "state", "backend", "enabled", "failover_count",
                "watchdog_trips", "backend_epoch", "budgets",
            )
        },
        "compile_cache": {"dir": cache_dir, "files": cache_files},
        "compiles": compiles.report(),
        "launch_shapes_ready": len(worker._compiled),
        "mesh": mesh,
        "stage_s": stage_s,
        "seed_world_s": round(seed_s, 2),
        "warm_shapes_s": round(warm_shapes_s, 2),
        "compile_s": round(compile_s, 2),
        "wave_s": round(wave_s, 2),
        "oracle_s": round(oracle_s, 2),
        "wall_s": round(time.monotonic() - t_run, 2),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=7)
    # sizes are the contract; smaller ones exist for debugging only
    parser.add_argument("--nodes", type=int, default=N_NODES)
    parser.add_argument("--allocs", type=int, default=N_ALLOCS)
    parser.add_argument("--jobs", type=int, default=N_BINPACK)
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    rc = 1
    try:
        backend = require_tpu()
        if backend is None:
            rc = 2
        else:
            result = run(args, backend)
            print(json.dumps(result), flush=True)
            if result["ok"]:
                print(
                    json.dumps({"ok": True, "device": result["device"]}),
                    flush=True,
                )
                rc = 0
    except SmokeFailure as exc:
        log(f"FAIL {exc}")
    except Exception:  # noqa: BLE001 — the run failed; say how
        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads may sit inside XLA calls; interpreter teardown can
    # then abort — leave with the REAL status instead
    os._exit(rc)


if __name__ == "__main__":
    main()
