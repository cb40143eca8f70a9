"""Composed-topology bigworld smoke/bench: fan-out followers × pods.

The million-node deployment shape this module drives end to end:

* N ``netagent`` server processes form one raft cluster over TCP
  (``--num-schedulers 0``: the leader brokers and commits but plans
  nothing itself);
* EVERY server also heads its own private ``jax.distributed`` world
  (per-server ``NOMAD_TPU_DIST_COORD`` / ``NOMAD_TPU_POD_PORT``) with
  one pod-peer process (``python -m nomad_tpu.parallel.pod``) as the
  second world member — whichever servers are followers run one
  fan-out batch worker (``NOMAD_TPU_FANOUT_MESH=1``) that plans
  through a live 2-process sharded mesh, streaming its launch
  sequence to the peer (``parallel/pod.py``);
* the world itself is synthesized by the ``seed_world`` raft command
  (``loadgen/bigworld.py``): the log carries a tiny spec, every
  replica expands it deterministically to the same bulk-registered
  nodes + array-backed allocation ballast.

Measured/asserted:

* ``placements_per_s`` — jobs driven over HTTP until fully placed;
* ``bytes_per_flush_per_host`` — each follower's
  ``mesh.bytes_per_flush`` gauge (the O(dirty rows) wire accounting);
* ``catchup_s`` — SIGKILL one follower (and its pod peer), restart
  both, time until the seeded sentinel node is queryable again;
* zero lost evals, both followers reporting a ``mesh.hosts`` pod of
  the expected width, at least one mesh launch, and (reduced scale)
  placement parity against an in-process single-server oracle that
  seeds the same spec and replays the same job sequence.  With
  ``NOMAD_TPU_POD_CHECK=1`` pinned in the child env, every mesh
  launch additionally round-trips a result digest from the pod peer,
  so a parity failure between head and peer aborts the drive itself.

Defaults are CI-sized (the ``tools/ci_check.sh`` gate); bench.py's
``bigworld`` block scales the same harness to the >=1M-node /
>=10M-alloc world.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from typing import Dict, List, Optional, Set, Tuple

from .bigworld import normalize_spec, world_datacenters

# settle slack applied on top of per-phase deadlines: first mesh
# launches block on XLA compiles (SYNC_COMPILE) on every world member
COMPILE_SLACK_S = 240.0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, path: str, payload=None, timeout: float = 30.0):
    url = f"http://127.0.0.1:{port}{path}"
    if payload is None:
        req = urllib.request.Request(url)
    else:
        req = urllib.request.Request(
            url,
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _wait(predicate, what: str, timeout: float, poll: float = 0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = predicate()
        if out:
            return out
        time.sleep(poll)
    raise AssertionError(f"timeout waiting for {what} ({timeout}s)")


def _wait_leader(http_ports: List[int], timeout: float) -> str:
    """Every live server agrees on one leader address."""

    def probe():
        views = set()
        for port in http_ports:
            try:
                views.add(_http(port, "/v1/status/leader"))
            except Exception:  # noqa: BLE001 — booting
                return None
        if len(views) == 1 and None not in views:
            (leader,) = views
            return leader or None
        return None

    return _wait(probe, "agreed raft leader", timeout, poll=0.3)


def _log_has(path: str, needle: str) -> bool:
    try:
        with open(path, "r", errors="replace") as fh:
            return needle in fh.read()
    except OSError:
        return False


def _chain_job(spec: dict, i: int, count: int):
    from .. import mock

    job = mock.job(id=f"bw-chain-{i:04d}")
    job.type = "batch"
    job.datacenters = world_datacenters(spec)
    job.task_groups[0].count = count
    job.task_groups[0].tasks[0].resources.cpu = 500
    job.task_groups[0].tasks[0].resources.memory_mb = 1024
    return job


def _storm_job(spec: dict, i: int):
    from .. import mock

    # dispatch-family id shape: the broker's family detector
    # coalesces the contiguous prefix into one global storm solve
    job = mock.job(id=f"bwfam-000/dispatch-{i:04d}")
    job.type = "batch"
    job.datacenters = world_datacenters(spec)
    job.task_groups[0].count = 1
    job.task_groups[0].tasks[0].resources.cpu = 250
    job.task_groups[0].tasks[0].resources.memory_mb = 512
    return job


def _job_allocs(port: int, job_id: str) -> List[dict]:
    if "/" in job_id:
        # dispatch-style ids (bwfam-000/dispatch-NNNN) break the
        # /v1/job/<id>/... route; the flat list is cheap here — the
        # seeded 10M-alloc ballast is array-backed, never Allocation
        # objects, so store.allocs holds only the driven jobs
        allocs = [
            a
            for a in _http(port, "/v1/allocations")
            if a.get("job_id") == job_id
        ]
    else:
        allocs = _http(port, f"/v1/job/{job_id}/allocations")
    return [a for a in allocs if a.get("desired_status") == "run"]


def _placement_keys(
    allocs: List[dict], with_node: bool
) -> Set[Tuple]:
    out: Set[Tuple] = set()
    for a in allocs:
        key = (a["job_id"], a["task_group"], a["name"])
        if with_node:
            key += (a["node_id"],)
        out.add(key)
    return out


class _Fleet:
    """The spawned processes of one composed topology: per server
    index a netagent child and its pod-peer child, plus their log
    files (READY/SEEDED markers are polled from the logs — PIPEs
    would deadlock on chatty jax stderr)."""

    def __init__(self, log_dir: str, cwd: str) -> None:
        self.log_dir = log_dir
        self.cwd = cwd
        self.servers: Dict[int, subprocess.Popen] = {}
        self.peers: Dict[int, subprocess.Popen] = {}

    def log_path(self, kind: str, i: int, gen: int = 0) -> str:
        return os.path.join(self.log_dir, f"{kind}{i}.{gen}.log")

    def spawn(
        self, kind: str, i: int, cmd: List[str], env: dict,
        gen: int = 0,
    ) -> subprocess.Popen:
        out = open(self.log_path(kind, i, gen), "w")
        proc = subprocess.Popen(
            cmd, env=env, stdout=out, stderr=subprocess.STDOUT,
            cwd=self.cwd,
        )
        out.close()  # child holds the fd
        (self.servers if kind == "server" else self.peers)[i] = proc
        return proc

    def kill_pair(self, i: int) -> None:
        for group in (self.servers, self.peers):
            proc = group.get(i)
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)

    def shutdown(self) -> None:
        for proc in self.servers.values():
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in list(self.servers.values()) + list(
            self.peers.values()
        ):
            if proc.poll() is None:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=10)

    def tails(self, limit: int = 3000) -> str:
        chunks = []
        for name in sorted(os.listdir(self.log_dir)):
            try:
                with open(
                    os.path.join(self.log_dir, name),
                    "r", errors="replace",
                ) as fh:
                    chunks.append(
                        f"--- {name} ---\n{fh.read()[-limit:]}"
                    )
            except OSError:
                pass
        return "\n".join(chunks)


def _child_env(
    repo_root: str,
    coord_port: int,
    pod_port: int,
    rank: int,
    procs: int,
    devices_per_proc: int,
) -> dict:
    env = dict(os.environ)
    # hermetic world: the parent shell's knobs must not reshape the
    # gate — children see ONLY the pinned set below
    for key in [k for k in env if k.startswith("NOMAD_TPU_")]:
        del env[key]
    env.update(
        {
            "PYTHONPATH": repo_root
            + os.pathsep
            + env.get("PYTHONPATH", ""),
            "JAX_PLATFORMS": "cpu",
            "JAX_ENABLE_X64": "1",
            "XLA_FLAGS": (
                "--xla_force_host_platform_device_count="
                f"{devices_per_proc}"
            ),
            "NOMAD_TPU_DIST": "1",
            "NOMAD_TPU_DIST_COORD": f"127.0.0.1:{coord_port}",
            "NOMAD_TPU_DIST_PROCS": str(procs),
            "NOMAD_TPU_DIST_ID": str(rank),
            "NOMAD_TPU_MESH": "1",
            # only the follower fan-out worker may head the mesh/pod
            "NOMAD_TPU_FANOUT": "1",
            "NOMAD_TPU_FANOUT_WORKERS": "1",
            "NOMAD_TPU_FANOUT_MESH": "1",
            "NOMAD_TPU_POD_PORT": str(pod_port),
            # parity gate: every chain/storm launch round-trips a
            # result digest from the pod peer
            "NOMAD_TPU_POD_CHECK": "1",
            "NOMAD_TPU_STORM": "1",
            "NOMAD_TPU_STORM_MIN": "8",
            # determinism: no admission shaping, no overload ladder
            # (single-core compiles make eval age trip SHEDDING and
            # 429 the harness polls), compiles block inline
            "NOMAD_TPU_ADMIT": "0",
            "NOMAD_TPU_OVERLOAD": "0",
            "NOMAD_TPU_LATENCY_BUDGET_MS": "0",
            "NOMAD_TPU_SYNC_COMPILE": "1",
            "NOMAD_TPU_BROKER_WATCHDOG": "1",
        }
    )
    return env


def _oracle_placements(
    spec: dict, jobs: int, count: int, storm_jobs: int,
    timeout: float,
) -> Tuple[Set[Tuple], Set[Tuple]]:
    """Single-server in-process oracle: seed the SAME world spec,
    replay the SAME job sequence (sequential chain phase, then the
    storm family as one burst), return (chain keys with node ids,
    storm keys without)."""
    from ..server.cluster import TestCluster

    pinned = {
        "NOMAD_TPU_ADMIT": "0",
        "NOMAD_TPU_OVERLOAD": "0",
        "NOMAD_TPU_LATENCY_BUDGET_MS": "0",
        "NOMAD_TPU_STORM": "1",
        "NOMAD_TPU_STORM_MIN": "8",
    }
    saved = {k: os.environ.get(k) for k in pinned}
    os.environ.update(pinned)
    cluster = TestCluster(
        1, heartbeat_ttl=600.0, name_prefix="bworacle"
    )
    try:
        cluster.start()
        leader = cluster.wait_for_leader(timeout=30.0)
        # seed the store directly — the body of the seed_world FSM
        # command (bigworld.seed_world IS _apply_seed_world), without
        # the raft apply timeout that a minutes-long full-scale
        # expansion would trip
        from .bigworld import seed_world

        seed_world(leader.store, spec)

        def placed(job_id: str, want: int) -> bool:
            allocs = [
                a
                for a in leader.store.allocs_by_job(
                    "default", job_id
                )
                if not a.terminal_status()
            ]
            return len(allocs) >= want

        chain_ids = []
        for i in range(jobs):
            job = _chain_job(spec, i, count)
            chain_ids.append(job.id)
            leader.register_job(job)
            _wait(
                lambda j=job.id: placed(j, count),
                f"oracle placement of {job.id}",
                timeout,
            )
        storm_ids = []
        for i in range(storm_jobs):
            job = _storm_job(spec, i)
            storm_ids.append(job.id)
            leader.register_job(job)
        for job_id in storm_ids:
            _wait(
                lambda j=job_id: placed(j, 1),
                f"oracle placement of {job_id}",
                timeout,
            )
        leader.drain_to_idle(timeout=10.0)

        def keys(ids, with_node: bool) -> Set[Tuple]:
            out: Set[Tuple] = set()
            for job_id in ids:
                for a in leader.store.allocs_by_job(
                    "default", job_id
                ):
                    if a.terminal_status():
                        continue
                    key = (a.job_id, a.task_group, a.name)
                    if with_node:
                        key += (a.node_id,)
                    out.add(key)
            return out

        # name-level keys on BOTH phases: every node pick goes through
        # the placement shuffle (EvalContext's seeded rng), and worker
        # seeds differ across topologies — the repo's oracle-parity
        # contract (chaos_smoke, fanout_bench) is the placement SET
        # (job, group, name), while per-launch numeric identity is
        # covered by the POD_CHECK digest gate
        return keys(chain_ids, False), keys(storm_ids, False)
    finally:
        cluster.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_bigworld(
    nodes: int = 256,
    allocs: int = 2048,
    jobs: int = 4,
    count: int = 2,
    storm_jobs: int = 8,
    servers: int = 3,
    procs_per_follower: int = 2,
    devices_per_proc: int = 2,
    dcs: int = 2,
    seed: int = 0,
    oracle: bool = True,
    timeout: float = 600.0,
) -> dict:
    """Drive the composed topology once; returns the bench block.
    Raises on any correctness-gate failure (lost evals, missing pod,
    parity mismatch, catch-up timeout) with the children's log tails
    attached."""
    import tempfile

    spec = normalize_spec(
        {
            "nodes": nodes,
            "allocs": allocs,
            "dcs": dcs,
            "seed": seed,
            "prefix": "bw",
        }
    )
    sentinel = f"{spec['prefix']}-{spec['nodes'] - 1:08d}"
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    rpc_ports = [_free_port() for _ in range(servers)]
    http_ports = [_free_port() for _ in range(servers)]
    coord_ports = [_free_port() for _ in range(servers)]
    pod_ports = [_free_port() for _ in range(servers)]
    addrs = [f"127.0.0.1:{p}" for p in rpc_ports]
    peers_arg = ",".join(addrs)
    # worlds seeded through raft can take minutes to expand at full
    # scale; elections stay calm because the FSM applies off the raft
    # driver thread, but forwarding retries need headroom
    seed_budget = max(60.0, spec["nodes"] / 4000.0)
    log_dir = tempfile.mkdtemp(prefix="bigworld_")
    fleet = _Fleet(log_dir, cwd=repo_root)

    def server_cmd(i: int, join_to: Optional[str]) -> List[str]:
        cmd = [
            sys.executable, "-m", "nomad_tpu.server.netagent",
            "--addr", addrs[i],
            "--peers", peers_arg,
            "--http-port", str(http_ports[i]),
            "--heartbeat-ttl", "600",
            "--election-timeout", "2.0",
            "--heartbeat-interval", "0.3",
            "--num-schedulers", "0",
        ]
        if join_to:
            cmd += ["--join", join_to]
        return cmd

    def spawn_pair(i: int, join_to: Optional[str],
                   seed_world: bool, gen: int = 0) -> None:
        cmd = server_cmd(i, join_to)
        if seed_world:
            cmd += ["--seed-world", json.dumps(spec)]
        fleet.spawn(
            "server", i, cmd,
            _child_env(
                repo_root, coord_ports[i], pod_ports[i], 0,
                procs_per_follower, devices_per_proc,
            ),
            gen=gen,
        )
        fleet.spawn(
            "peer", i,
            [
                sys.executable, "-m", "nomad_tpu.parallel.pod",
                "--head-port", str(pod_ports[i]),
                "--connect-timeout", str(timeout + seed_budget),
            ],
            _child_env(
                repo_root, coord_ports[i], pod_ports[i], 1,
                procs_per_follower, devices_per_proc,
            ),
            gen=gen,
        )

    try:
        t_boot = time.monotonic()
        for i in range(servers):
            spawn_pair(
                i, addrs[0] if i else None, seed_world=(i == 0)
            )
        for i in range(servers):
            _wait(
                lambda i=i: _log_has(
                    fleet.log_path("server", i), "READY "
                ),
                f"server {i} READY",
                timeout,
            )
        leader_addr = _wait_leader(http_ports, timeout)
        leader_i = addrs.index(leader_addr)
        follower_is = [i for i in range(servers) if i != leader_i]

        # -- seed + replicate the synthetic world -----------------------
        _wait(
            lambda: _log_has(
                fleet.log_path("server", 0), "SEEDED "
            ),
            "seed_world commit",
            seed_budget + timeout,
            poll=1.0,
        )

        def node_visible(port: int) -> bool:
            try:
                _http(port, f"/v1/node/{sentinel}")
                return True
            except Exception:  # noqa: BLE001 — 404 until applied
                return False

        for port in http_ports:
            _wait(
                lambda p=port: node_visible(p),
                "seeded world visible on every replica",
                seed_budget + timeout,
                poll=1.0,
            )
        seed_s = time.monotonic() - t_boot

        # -- drive: sequential chain phase, then the storm family -------
        drive_deadline = timeout + COMPILE_SLACK_S
        t_drive = time.monotonic()
        chain_ids = []
        for i in range(jobs):
            job = _chain_job(spec, i, count)
            chain_ids.append(job.id)
            from ..api.codec import job_to_dict

            out = _http(
                http_ports[leader_i], "/v1/jobs",
                {"Job": job_to_dict(job)},
            )
            assert out.get("EvalID"), out
            _wait(
                lambda j=job.id: len(
                    _job_allocs(http_ports[leader_i], j)
                )
                >= count,
                f"placement of {job.id}",
                drive_deadline,
            )
        storm_ids = []
        for i in range(storm_jobs):
            job = _storm_job(spec, i)
            storm_ids.append(job.id)
            from ..api.codec import job_to_dict

            out = _http(
                http_ports[leader_i], "/v1/jobs",
                {"Job": job_to_dict(job)},
            )
            assert out.get("EvalID"), out
        for job_id in storm_ids:
            _wait(
                lambda j=job_id: len(
                    _job_allocs(http_ports[leader_i], j)
                )
                >= 1,
                f"placement of {job_id}",
                drive_deadline,
            )
        drive_s = time.monotonic() - t_drive

        # -- zero lost + placement sets ---------------------------------
        chain_keys: Set[Tuple] = set()
        storm_keys: Set[Tuple] = set()
        lost = 0
        for job_id in chain_ids:
            allocs_j = _job_allocs(http_ports[leader_i], job_id)
            lost += max(0, count - len(allocs_j))
            chain_keys |= _placement_keys(allocs_j, with_node=False)
        for job_id in storm_ids:
            allocs_j = _job_allocs(http_ports[leader_i], job_id)
            lost += max(0, 1 - len(allocs_j))
            storm_keys |= _placement_keys(allocs_j, with_node=False)
        placements_total = len(chain_keys) + len(storm_keys)
        assert lost == 0, f"lost {lost} placements"

        # -- follower pod accounting ------------------------------------
        mesh_hosts: Dict[str, float] = {}
        mesh_launches: Dict[str, float] = {}
        bytes_per_flush: Dict[str, float] = {}
        for i in follower_is:
            dump = _http(http_ports[i], "/v1/metrics")
            gauges = dump.get("gauges", {})
            counters = dump.get("counters", {})
            mesh_hosts[addrs[i]] = gauges.get("mesh.hosts", 0.0)
            mesh_launches[addrs[i]] = counters.get(
                "mesh.launches", 0.0
            )
            bytes_per_flush[addrs[i]] = gauges.get(
                "mesh.bytes_per_flush", 0.0
            )
        assert all(
            h == float(procs_per_follower)
            for h in mesh_hosts.values()
        ), f"follower pods not fully formed: {mesh_hosts}"
        assert sum(mesh_launches.values()) >= 1, (
            f"no follower mesh launches: {mesh_launches}"
        )

        # -- oracle parity (reduced scale) ------------------------------
        parity = {"oracle": bool(oracle)}
        if oracle:
            oracle_chain, oracle_storm = _oracle_placements(
                spec, jobs, count, storm_jobs,
                timeout=drive_deadline,
            )
            parity["chain_match"] = chain_keys == oracle_chain
            parity["storm_match"] = storm_keys == oracle_storm
            assert parity["chain_match"], (
                "chain placements diverge from oracle: "
                f"only_fanout={sorted(chain_keys - oracle_chain)[:5]} "
                f"only_oracle={sorted(oracle_chain - chain_keys)[:5]}"
            )
            assert parity["storm_match"], (
                "storm placements diverge from oracle: "
                f"only_fanout={sorted(storm_keys - oracle_storm)[:5]} "
                f"only_oracle={sorted(oracle_storm - storm_keys)[:5]}"
            )

        # -- snapshot catch-up: kill + restart one follower -------------
        victim = follower_is[0]
        fleet.kill_pair(victim)
        t_restart = time.monotonic()
        spawn_pair(
            victim, addrs[leader_i], seed_world=False, gen=1
        )
        _wait(
            lambda: _log_has(
                fleet.log_path("server", victim, gen=1), "READY "
            ),
            "restarted follower READY",
            timeout,
        )
        restart_ready_s = time.monotonic() - t_restart
        _wait(
            lambda: node_visible(http_ports[victim]),
            "restarted follower world catch-up",
            seed_budget + timeout,
            poll=0.5,
        )
        catchup_s = time.monotonic() - t_restart
        # the re-established fleet must plan correctly (never against
        # a stale mirror): one more job, placed through the cluster
        post_job = _chain_job(spec, jobs, count)
        post_job.id = "bw-postrestart-0000"
        from ..api.codec import job_to_dict

        out = _http(
            http_ports[leader_i], "/v1/jobs",
            {"Job": job_to_dict(post_job)},
        )
        assert out.get("EvalID"), out
        _wait(
            lambda: len(
                _job_allocs(http_ports[leader_i], post_job.id)
            )
            >= count,
            "post-restart placement",
            drive_deadline,
        )
        # pod re-forms on the restarted follower (it is still a
        # follower: leadership never moved)
        def pod_reformed() -> bool:
            try:
                dump = _http(http_ports[victim], "/v1/metrics")
            except Exception:  # noqa: BLE001
                return False
            return dump.get("gauges", {}).get(
                "mesh.hosts", 0.0
            ) == float(procs_per_follower)

        _wait(
            pod_reformed, "restarted follower pod", drive_deadline,
            poll=0.5,
        )

        return {
            "world": {
                "nodes": spec["nodes"],
                "allocs": spec["allocs"],
                "dcs": spec["dcs"],
                "sentinel": sentinel,
            },
            "topology": {
                "servers": servers,
                "followers": len(follower_is),
                "procs_per_follower": procs_per_follower,
                "devices_per_proc": devices_per_proc,
                "global_devices_per_follower": (
                    procs_per_follower * devices_per_proc
                ),
            },
            "seed_s": round(seed_s, 2),
            "drive_s": round(drive_s, 2),
            "placements_total": placements_total,
            "placements_per_s": round(
                placements_total / max(drive_s, 1e-9), 2
            ),
            "bytes_per_flush_per_host": bytes_per_flush,
            "mesh_hosts": mesh_hosts,
            "mesh_launches": mesh_launches,
            "catchup": {
                "server": addrs[victim],
                "restart_ready_s": round(restart_ready_s, 2),
                "catchup_s": round(catchup_s, 2),
            },
            "lost": lost,
            "pod_check": True,
            "parity": parity,
            "log_dir": log_dir,
        }
    except BaseException as exc:
        raise RuntimeError(
            f"bigworld smoke failed ({exc!r}); logs in {log_dir}:\n"
            f"{fleet.tails()}"
        ) from exc
    finally:
        fleet.shutdown()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=(
            "composed fan-out × pod bigworld smoke "
            "(spawned netagent + pod-peer processes)"
        )
    )
    parser.add_argument("--nodes", type=int, default=256)
    parser.add_argument("--allocs", type=int, default=2048)
    parser.add_argument("--jobs", type=int, default=4)
    parser.add_argument("--count", type=int, default=2)
    parser.add_argument("--storm-jobs", type=int, default=8)
    parser.add_argument("--servers", type=int, default=3)
    parser.add_argument(
        "--procs-per-follower", type=int, default=2
    )
    parser.add_argument(
        "--devices-per-proc", type=int, default=2
    )
    parser.add_argument("--dcs", type=int, default=2)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--no-oracle", action="store_true",
        help="skip the in-process single-server parity oracle",
    )
    parser.add_argument("--timeout", type=float, default=600.0)
    args = parser.parse_args(argv)
    result = run_bigworld(
        nodes=args.nodes,
        allocs=args.allocs,
        jobs=args.jobs,
        count=args.count,
        storm_jobs=args.storm_jobs,
        servers=args.servers,
        procs_per_follower=args.procs_per_follower,
        devices_per_proc=args.devices_per_proc,
        dcs=args.dcs,
        seed=args.seed,
        oracle=not args.no_oracle,
        timeout=args.timeout,
    )
    print("BIGWORLD_JSON " + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
