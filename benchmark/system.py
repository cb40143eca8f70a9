"""The system under test, and nothing else of the program: the served
``Server(batch_pipeline=True)`` behind its HTTP listener, its state
store (to load the world and to read answers back), its batch worker's
warm-up call, its counters and its flight-recorder spans.

This is the only module of the benchmark that imports ``nomad_tpu``.
"""
from __future__ import annotations

import os
import time


class NoChip(Exception):
    """JAX resolved no TPU, or fewer chips than the cell asks for."""


def resolve_device(chips: int, allow_cpu: bool):
    """Resolve JAX's backend first and refuse anything but a TPU with
    ``chips`` devices (``chip_smoke.require_tpu`` semantics: the TPU is
    asked for explicitly so a missing chip is an error, never a quiet
    drop to the CPU).  ``allow_cpu`` is the rehearsal switch."""
    from nomad_tpu.backend import resolve_backend

    seen = os.environ.get("JAX_PLATFORMS")
    if not allow_cpu:
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    try:
        backend = resolve_backend()
    except Exception as exc:  # noqa: BLE001 — reported, then refused
        first = (str(exc).strip().splitlines() or [repr(exc)])[0]
        raise NoChip(
            f"no tpu backend (JAX_PLATFORMS seen={seen!r}): "
            f"{type(exc).__name__}: {first}"
        ) from exc
    if allow_cpu:
        return backend
    if backend.platform != "tpu":
        raise NoChip(f"found platform={backend.platform}, need tpu")
    if backend.device_count < chips:
        raise NoChip(
            f"found {backend.device_count} chip(s), the cell asks for {chips}"
        )
    return backend


def build_server(server_seed: int):
    """The server as ``nomad-tpu agent`` builds it from an agent config
    (nomad_tpu/cli.py): one scheduler, the deployment's scheduler seed,
    and a heartbeat TTL out of the way of a simulated fleet."""
    from nomad_tpu.config import AgentConfig
    from nomad_tpu.server import Server

    cfg = AgentConfig()
    cfg.server.num_schedulers = 1
    cfg.server.seed = server_seed
    cfg.server.heartbeat_ttl_s = 1e9
    cfg.server.batch_pipeline = True
    return Server(
        num_schedulers=cfg.server.num_schedulers,
        heartbeat_ttl=cfg.server.heartbeat_ttl_s,
        seed=cfg.server.seed,
        acl_enabled=cfg.acl.enabled,
        batch_pipeline=cfg.server.batch_pipeline,
        device_config=cfg.device,
    )


def node_name(i: int) -> str:
    """The name ``load_world`` gives node ``i`` (``${node.unique.name}``)."""
    return f"n{i}"


def load_world(store, world) -> None:
    """Register the fleet and its resident allocations (the
    ``bench.populate`` / ``chip_smoke.seed_world`` objects, from the
    benchmark's arrays)."""
    from nomad_tpu import mock
    from nomad_tpu.structs import (
        AllocatedResources,
        AllocatedSharedResources,
        AllocatedTaskResources,
        Allocation,
        alloc_name,
        compute_node_class,
    )

    rc, rm, rd = world.reserved
    class_cache: dict = {}
    node_ids = []
    for i in range(world.n_nodes):
        n = mock.node(id=world.node_id(i))
        n.name = node_name(i)
        n.datacenter = world.datacenters[int(world.node_dc[i])]
        n.node_resources.cpu = int(world.node_cpu[i])
        n.node_resources.memory_mb = int(world.node_mem[i])
        n.node_resources.disk_mb = int(world.node_disk[i])
        n.reserved_resources.cpu = rc
        n.reserved_resources.memory_mb = rm
        n.reserved_resources.disk_mb = rd
        key = (n.datacenter, n.node_resources.cpu, n.node_resources.memory_mb)
        if key not in class_cache:
            class_cache[key] = compute_node_class(n)
        n.computed_class = class_cache[key]
        store.upsert_node(n)
        node_ids.append(n.id)
    filler = mock.job(id="filler")
    store.upsert_job(filler)
    alloc_node = world.alloc_node.tolist()
    alloc_cpu = world.alloc_cpu.tolist()
    alloc_mem = world.alloc_mem.tolist()
    disk = world.alloc_disk
    allocs = [
        Allocation(
            namespace="default",
            job_id="filler",
            job=filler,
            task_group="web",
            name=alloc_name("filler", "web", i),
            node_id=node_ids[alloc_node[i]],
            allocated_resources=AllocatedResources(
                tasks={
                    "web": AllocatedTaskResources(
                        cpu=alloc_cpu[i], memory_mb=alloc_mem[i]
                    )
                },
                shared=AllocatedSharedResources(disk_mb=disk),
            ),
            client_status="running",
        )
        for i in range(world.n_allocs)
    ]
    store.upsert_allocs(allocs)


def start_http(server):
    from nomad_tpu.api import start_http_server

    return start_http_server(server, host="127.0.0.1", port=0)


def job_answers(store, job_id: str):
    """(commit index, {alloc name: node id}) of a job's live allocations
    as the state store holds them."""
    placed = {}
    index = None
    for a in store.allocs_by_job("default", job_id):
        if a.desired_status != "run" or a.terminal_status():
            continue
        placed[a.name] = a.node_id
        index = a.create_index if index is None else min(index, a.create_index)
    return index, placed


def eval_status(store, eval_id: str):
    ev = store.eval_by_id(eval_id)
    return None if ev is None else ev.status


def arena_rows(server) -> int:
    """Rows of the node arena the kernels walk (padded capacity)."""
    return int(server.store.node_table.capacity)


def host_path_evals(counters: dict, samples: dict) -> float:
    """Evaluations that took the host path so far, from ``/v1/metrics``:
    a launch shape that is not compiled yet sends its evaluations there."""
    return (
        float(counters.get("batch_worker.fallbacks", 0.0))
        + float(counters.get("batch_worker.cold_shape_fallbacks", 0.0))
        + float(samples.get("batch_worker.sequential", {}).get("count", 0))
    )


def recent_spans(names: tuple, t_lo: float, t_hi: float) -> list:
    """Flight-recorder spans (name, start, end) on ``time.monotonic``'s
    clock that overlap [t_lo, t_hi], for the idle-gap attribution, as
    ``GET /v1/traces?full=1`` gives them (``Tracer.recent``): a trace's
    wall-clock start and its spans' offsets.  The recorder keeps its
    last 1,024 evaluations, so this is read as soon as the window ends."""
    from nomad_tpu.trace import TRACE

    to_monotonic = time.monotonic() - time.time()
    out = []
    for trace in TRACE.recent(limit=1024, full=True):
        base = float(trace["start"]) + to_monotonic
        for span in trace["spans"]:
            if span["name"] not in names or span["dur_ms"] is None:
                continue
            start = base + span["off_ms"] / 1e3
            end = start + span["dur_ms"] / 1e3
            if end >= t_lo and start <= t_hi:
                out.append((span["name"], start, end))
    return out
