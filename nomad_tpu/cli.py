"""Command-line interface (reference command/commands.go registry).

    nomad-tpu agent -dev [-http-port N]        run a dev server+client
    nomad-tpu job run <file.hcl|file.json>     submit a job
    nomad-tpu job status [job_id]              list jobs / job detail
    nomad-tpu job stop [-purge] <job_id>       stop a job
    nomad-tpu job scale <job_id> <group> <n>   scale a group
    nomad-tpu node status [node_id]            list/inspect nodes
    nomad-tpu node drain -enable|-disable <id> drain a node
    nomad-tpu node eligibility -enable|-disable <id>
    nomad-tpu alloc status <alloc_id>
    nomad-tpu eval status <eval_id>
    nomad-tpu eval explain <eval_id>           placement explanation
    nomad-tpu deployment status [id] | promote <id> | fail <id>
    nomad-tpu operator scheduler get-config|set-config [...]
    nomad-tpu system gc
    nomad-tpu version

Talks to the HTTP API at $NOMAD_ADDR (default http://127.0.0.1:4646).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Any, Dict, Optional


def _addr() -> str:
    return os.environ.get("NOMAD_ADDR", "http://127.0.0.1:4646")


def _request(
    method: str, path: str, body: Optional[Dict] = None
) -> Any:
    url = _addr() + path
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    req.add_header("Content-Type", "application/json")
    token = os.environ.get("NOMAD_TOKEN")
    if token:
        req.add_header("X-Nomad-Token", token)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as exc:
        try:
            detail = json.loads(exc.read()).get("error", "")
        except Exception:  # noqa: BLE001
            detail = ""
        print(f"Error ({exc.code}): {detail or exc.reason}", file=sys.stderr)
        sys.exit(1)
    except urllib.error.URLError as exc:
        print(
            f"Error connecting to {_addr()}: {exc.reason}", file=sys.stderr
        )
        sys.exit(1)


class _TmplItem(dict):
    """Mapping for -t templates: case-tolerant key lookup plus dotted
    access inside ``{...}`` fields, so both ``{id}`` and ``{ID}`` hit
    the same API field regardless of the endpoint's casing."""

    def __missing__(self, key):
        for k in (key.lower(), key.upper()):
            if k in self:
                return self[k]
        lk = key.lower()
        for k, v in self.items():
            if str(k).lower() == lk:
                return v
        raise KeyError(key)

    def __getitem__(self, key):
        v = super().__getitem__(key) if key in self else self.__missing__(key)
        return _wrap_tmpl(v)


def _wrap_tmpl(v):
    """Keep case-tolerance alive through nested containers: dicts wrap
    as _TmplItem and lists wrap their dict elements, so
    ``{TaskGroups[0][name]}`` resolves regardless of casing."""
    if isinstance(v, dict):
        return _TmplItem(v)
    if isinstance(v, list):
        return [_wrap_tmpl(x) for x in v]
    return v


def _render_template(template: str, item) -> str:
    if not isinstance(item, dict):
        return template.format(item)
    return template.format_map(_TmplItem(item))


def _emit(args, data) -> bool:
    """Shared machine-readable output for status/list/inspect commands
    (reference command/job_status.go:22-40 -json/-t flags +
    command/helpers.go Format).  ``-json`` dumps the raw API payload;
    ``-t`` renders a Python format-string per item (lists render one
    line per element; ``{id}``/``{ID}`` are case-tolerant, nested
    fields via ``{resources[cpu]}``).  Returns True when it handled
    the output (the caller skips its human-readable rendering)."""
    if getattr(args, "json", False):
        print(json.dumps(data, indent=2, sort_keys=True, default=str))
        return True
    template = getattr(args, "template", None)
    if template:
        items = data if isinstance(data, list) else [data]
        try:
            for item in items:
                print(_render_template(template, item))
        except (KeyError, IndexError) as exc:
            print(
                f"Error rendering template: missing field {exc}",
                file=sys.stderr,
            )
            sys.exit(1)
        except (ValueError, TypeError, AttributeError) as exc:
            # malformed template (unbalanced braces, bad conversion):
            # a clean one-line error, not a traceback
            print(
                f"Error rendering template: {exc}", file=sys.stderr
            )
            sys.exit(1)
        return True
    return False


def _add_fmt(parser) -> None:
    """Register the -json / -t flags (every status/list/inspect
    command takes both, mirroring reference-wide support)."""
    parser.add_argument("-json", action="store_true", dest="json")
    parser.add_argument("-t", dest="template", default=None)


def _table(rows, headers) -> None:
    widths = [
        max(len(str(h)), *(len(str(r[i])) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    print(fmt.format(*headers))
    for row in rows:
        print(fmt.format(*[str(c) for c in row]))


# ---------------------------------------------------------------------------
# commands
# ---------------------------------------------------------------------------


def _dev_csi_plugin():
    from .client.csi import FakeCSIPlugin

    return FakeCSIPlugin()


def cmd_agent(args) -> None:
    from .api.http import start_http_server
    from .client import Client
    from .config import AgentConfig, load_config
    from .server import Server

    if getattr(args, "client_mode", False):
        # networked client mode (reference `agent -client
        # -servers=...`): delegate to the netclient entrypoint —
        # registration/heartbeats/alloc sync over HTTP, with the
        # callback endpoint servers proxy fs/exec/logs through
        servers = (
            args.client_mode
            if isinstance(args.client_mode, str)
            else ""
        ) or args.servers
        if not servers:
            raise SystemExit(
                "-client requires -servers=<http addr,...>"
            )
        if (
            args.dev
            or args.config
            or args.server_addr
            or args.http_port is not None
            or args.num_schedulers is not None
        ):
            raise SystemExit(
                "-client does not combine with -dev/-config/"
                "-server-addr/-http-port/-num-schedulers"
            )
        from .client.netclient import main as netclient_main

        argv = ["--servers", servers]
        if args.data_dir:
            argv += ["--data-dir", args.data_dir]
        if args.callback_host:
            argv += ["--callback-host", args.callback_host]
        raise SystemExit(netclient_main(argv))

    if getattr(args, "server_addr", None):
        # networked cluster-server mode: delegate to the netagent
        # entrypoint (framed-TCP raft/gossip/forwarding + HTTP API)
        if args.dev or args.config or args.num_schedulers is not None:
            raise SystemExit(
                "-server-addr does not support -dev/-config/"
                "-num-schedulers yet; configure via netagent flags"
            )
        from .server.netagent import main as netagent_main

        argv = [
            "--addr", args.server_addr,
            "--peers", args.peers or args.server_addr,
            "--http-port", str(args.http_port or 0),
        ]
        if args.join:
            argv += ["--join", args.join]
        raise SystemExit(netagent_main(argv))

    cfg = load_config(args.config) if args.config else AgentConfig()
    if args.dev:
        cfg.client.enabled = True
        if not cfg.data_dir:
            # dev mode needs a real alloc-dir root or the fs/logs
            # surface (alloc logs/fs/exec streaming) has nothing to
            # serve (reference -dev defaults a temp data dir too)
            import tempfile

            cfg.data_dir = tempfile.mkdtemp(prefix="nomad-tpu-dev-")
    if args.num_schedulers is not None:
        cfg.server.num_schedulers = args.num_schedulers
    if args.http_port is not None:
        cfg.http.port = args.http_port

    server = Server(
        num_schedulers=cfg.server.num_schedulers,
        heartbeat_ttl=cfg.server.heartbeat_ttl_s,
        seed=cfg.server.seed,
        acl_enabled=cfg.acl.enabled,
        batch_pipeline=cfg.server.batch_pipeline,
        device_config=cfg.device,
    )
    server.start()
    http = start_http_server(server, host=cfg.http.host, port=cfg.http.port)
    print(f"==> nomad-tpu agent started; HTTP on :{http.port}")
    dev = server.device_supervisor.status()
    backend_line = (
        f"backend: platform={dev['platform']} "
        f"device_kind={dev['device_kind']} "
        f"devices={dev['device_count']} "
        f"supervision={'on' if dev['enabled'] else 'idle'}"
    )
    print(f"==> {backend_line}")
    # lifecycle lines feed /v1/agent/monitor (the logging handler only
    # sees `logging` records, not stdout prints)
    server.log_monitor.write_line(
        f"agent started; HTTP on :{http.port}; {backend_line}"
    )
    bridge = None
    if cfg.bridge_port is not None:
        from .server.bridge_service import BridgeService

        bridge = BridgeService(server, port=cfg.bridge_port)
        bridge.start()
        print(f"==> TPU bridge on :{bridge.port}")
    # external Consul/Vault (reference command/agent: consul sync +
    # vault client wiring; opt-in by configured address)
    secrets = None
    if cfg.consul.address:
        from .external import ConsulClient, ConsulSyncer

        syncer = ConsulSyncer(
            server.catalog,
            ConsulClient(cfg.consul.address, cfg.consul.token),
        )
        syncer.attach(server.store)
        syncer.sync()
        print(f"==> consul sync to {cfg.consul.address}")
    if cfg.vault.address:
        from .external import VaultClient, VaultSecretsProvider

        secrets = VaultSecretsProvider(
            VaultClient(cfg.vault.address, cfg.vault.token)
        )
        print(f"==> vault secrets from {cfg.vault.address}")
    clients = []
    if cfg.client.enabled:
        from .structs import Node

        node = Node(datacenter=cfg.datacenter, name=cfg.name)
        client = Client(
            server,
            node=node,
            data_dir=cfg.data_dir,
            drivers=cfg.client.drivers,
            heartbeat_interval=cfg.client.heartbeat_interval_s,
            include_tpu_fingerprint=cfg.client.include_tpu_fingerprint,
            secrets=secrets,
            # dev mode ships an in-process CSI plugin so the volume
            # flow is drivable out of the box (reference -dev ships
            # the mock driver for the same reason)
            csi_plugins=(
                {"csi-dev": _dev_csi_plugin()} if args.dev else None
            ),
        )
        client.start()
        clients.append(client)
        print(f"==> client node {client.node.id[:8]} registered")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        print("==> shutting down")
    finally:
        for c in clients:
            c.stop()
        if bridge is not None:
            bridge.stop()
        http.stop()
        server.stop()


def cmd_job_run(args) -> None:
    path = args.file
    if path.endswith(".json"):
        with open(path) as f:
            raw = json.load(f)
        job_payload = raw.get("Job") or raw.get("job") or raw
        from .api.codec import job_from_dict, job_to_dict

        job = job_from_dict(job_payload)
    else:
        from . import jobspec
        from .api.codec import job_to_dict

        job = jobspec.parse_file(path)
    from .api.codec import job_to_dict

    resp = _request("POST", "/v1/jobs", {"Job": job_to_dict(job)})
    print(f"==> Evaluation {resp.get('EvalID', '')[:8]} created")


def cmd_job_status(args) -> None:
    if not args.job_id:
        jobs = _request("GET", "/v1/jobs")
        if _emit(args, jobs):
            return
        if not jobs:
            print("No running jobs")
            return
        _table(
            [
                (j["ID"][:20], j["Type"], j["Priority"], j["Status"])
                for j in jobs
            ],
            ["ID", "Type", "Priority", "Status"],
        )
        return
    job = _request("GET", f"/v1/job/{args.job_id}")
    if _emit(args, job):
        return
    print(f"ID            = {job['id']}")
    print(f"Name          = {job['name']}")
    print(f"Type          = {job['type']}")
    print(f"Priority      = {job['priority']}")
    print(f"Status        = {job.get('status', '')}")
    print(f"Datacenters   = {','.join(job['datacenters'])}")
    allocs = _request("GET", f"/v1/job/{args.job_id}/allocations")
    if allocs:
        print("\nAllocations")
        _table(
            [
                (
                    a["id"][:8],
                    a["node_id"][:8],
                    a["task_group"],
                    a["desired_status"],
                    a["client_status"],
                )
                for a in allocs
            ],
            ["ID", "Node ID", "Task Group", "Desired", "Status"],
        )


def cmd_job_plan(args) -> None:
    path = args.file
    if path.endswith(".json"):
        with open(path) as f:
            raw = json.load(f)
        payload = raw.get("Job") or raw.get("job") or raw
        from .api.codec import job_from_dict, job_to_dict

        job = job_from_dict(payload)
    else:
        from . import jobspec
        job = jobspec.parse_file(path)
    from .api.codec import job_to_dict

    resp = _request(
        "POST", f"/v1/job/{job.id}/plan", {"Job": job_to_dict(job)}
    )
    diff = resp.get("Diff") or {}
    print(f"Job: {job.id!r} ({diff.get('Type', 'Added')})")
    for tg, changes in (resp.get("Annotations") or {}).items():
        parts = ", ".join(
            f"{k.lower()} {v}" for k, v in changes.items() if v
        )
        print(f"  group {tg!r}: {parts or 'no changes'}")
    failed = resp.get("FailedTGAllocs") or {}
    for tg, metric in failed.items():
        print(f"  WARNING group {tg!r} would fail placement: {metric}")


def cmd_job_dispatch(args) -> None:
    meta = {}
    for item in args.meta or []:
        key, _, value = item.partition("=")
        meta[key] = value
    resp = _request(
        "POST", f"/v1/job/{args.job_id}/dispatch", {"Meta": meta}
    )
    print(f"==> Dispatched {resp['DispatchedJobID']}")


def _stream_get(path: str):
    """GET a chunked streaming endpoint; yields raw byte frames
    (urllib reads chunked transfer transparently)."""
    url = _addr() + path
    req = urllib.request.Request(url, method="GET")
    token = os.environ.get("NOMAD_TOKEN")
    if token:
        req.add_header("X-Nomad-Token", token)
    resp = urllib.request.urlopen(req, timeout=3600)
    while True:
        data = resp.read1(65536)
        if not data:
            return
        yield data


def cmd_alloc_logs(args) -> None:
    kind = "stderr" if args.stderr else "stdout"
    path = (
        f"/v1/client/fs/logs/{args.alloc_id}?task={args.task}"
        f"&type={kind}"
    )
    if not getattr(args, "follow", False):
        data = _request("GET", path).get("Data", "")
        sys.stdout.write(data)
        return
    # -f: live chunked stream from the server (reference client fs
    # streaming frames)
    try:
        for frame in _stream_get(path + "&follow=true"):
            # raw bytes: a multibyte character straddling a chunk
            # boundary must not be mangled by per-chunk decoding
            sys.stdout.buffer.write(frame)
            sys.stdout.buffer.flush()
    except (KeyboardInterrupt, BrokenPipeError):
        pass
    except urllib.error.HTTPError as exc:
        print(f"Error ({exc.code}): {exc.reason}", file=sys.stderr)
        sys.exit(1)


def cmd_job_history(args) -> None:
    data = _request("GET", f"/v1/job/{args.job_id}/versions")
    if _emit(args, data.get("Versions", [])):
        return
    rows = [
        (
            j["version"],
            "true" if j.get("stable") else "false",
            time.strftime(
                "%Y-%m-%d %H:%M:%S",
                time.localtime(j.get("submit_time", 0)),
            ),
        )
        for j in data.get("Versions", [])
    ]
    _table(rows, ["Version", "Stable", "Submit Date"])


def cmd_job_revert(args) -> None:
    resp = _request(
        "POST",
        f"/v1/job/{args.job_id}/revert",
        {"JobVersion": int(args.version)},
    )
    print(f"==> Evaluation {resp.get('EvalID', '')[:8]} created")


def cmd_job_inspect(args) -> None:
    job = _request("GET", f"/v1/job/{args.job_id}")
    if _emit(args, job):
        return
    print(json.dumps(job, indent=2, sort_keys=True))


def cmd_job_validate(args) -> None:
    if args.file.endswith(".json"):
        with open(args.file) as f:
            raw = json.load(f)
        payload = {"Job": raw.get("Job") or raw.get("job") or raw}
    else:
        with open(args.file) as f:
            parsed = _request(
                "POST", "/v1/jobs/parse", {"JobHCL": f.read()}
            )
        payload = {"Job": parsed}
    resp = _request("POST", "/v1/validate/job", payload)
    errors = resp.get("ValidationErrors") or []
    if errors:
        for e in errors:
            print(f"Error: {e}", file=sys.stderr)
        sys.exit(1)
    print("Job validation successful")


def cmd_alloc_restart(args) -> None:
    _request(
        "POST",
        f"/v1/client/allocation/{args.alloc_id}/restart",
        {"TaskName": args.task or ""},
    )
    print(f"==> Restarted allocation {args.alloc_id[:8]}")


def cmd_alloc_signal(args) -> None:
    _request(
        "POST",
        f"/v1/client/allocation/{args.alloc_id}/signal",
        {"Signal": args.signal, "TaskName": args.task or ""},
    )
    print(f"==> Sent {args.signal} to allocation {args.alloc_id[:8]}")


def cmd_alloc_stop(args) -> None:
    resp = _request(
        "POST", f"/v1/allocation/{args.alloc_id}/stop", {}
    )
    print(f"==> Evaluation {resp.get('EvalID', '')[:8]} created")


def cmd_alloc_exec(args) -> None:
    if getattr(args, "interactive", False):
        sys.exit(_exec_interactive(args))
    resp = _request(
        "POST",
        f"/v1/client/allocation/{args.alloc_id}/exec",
        {
            "Task": args.task or "",
            "Cmd": args.cmd,
        },
    )
    sys.stdout.write(resp.get("Output", ""))
    sys.exit(int(resp.get("ExitCode", 0)))


def _exec_interactive(args) -> int:
    """Live exec session over the websocket transport (reference
    command/alloc_exec.go): stdin streams up, stdout/stderr stream
    down, exit code propagates."""
    import base64
    import threading
    import urllib.parse as _p

    from .api.ws import WebSocketClient

    addr = _p.urlparse(_addr())
    path = (
        f"/v1/client/allocation/{args.alloc_id}/exec"
        f"?task={_p.quote(args.task or '')}"
        f"&command={_p.quote(json.dumps(args.cmd))}"
    )
    headers = {}
    token = os.environ.get("NOMAD_TOKEN")
    if token:
        headers["X-Nomad-Token"] = token
    try:
        ws = WebSocketClient(
            addr.hostname, addr.port or 4646, path, headers
        )
    except (OSError, ConnectionError) as exc:
        print(f"Error connecting: {exc}", file=sys.stderr)
        return 1

    def pump_stdin() -> None:
        try:
            while True:
                data = sys.stdin.buffer.read1(4096)
                if not data:
                    ws.send_text(
                        json.dumps({"stdin": {"close": True}})
                    )
                    return
                ws.send_text(
                    json.dumps(
                        {
                            "stdin": {
                                "data": base64.b64encode(
                                    data
                                ).decode("ascii")
                            }
                        }
                    )
                )
        except (OSError, ValueError):
            pass

    threading.Thread(target=pump_stdin, daemon=True).start()
    code = 1
    try:
        while True:
            got = ws.recv(timeout=3600)
            if got is None:
                break
            _op, payload = got
            try:
                msg = json.loads(payload.decode("utf-8"))
            except ValueError:
                continue
            for stream, out in (
                ("stdout", sys.stdout),
                ("stderr", sys.stderr),
            ):
                frame = msg.get(stream) or {}
                if frame.get("data"):
                    out.buffer.write(
                        base64.b64decode(frame["data"])
                    )
                    out.flush()
            if msg.get("exited"):
                code = int(
                    (msg.get("result") or {}).get("exit_code", 0)
                )
    except KeyboardInterrupt:
        pass
    finally:
        ws.close()
    return code


def cmd_alloc_fs(args) -> None:
    path = args.path or ""
    if args.cat:
        resp = _request(
            "GET",
            f"/v1/client/fs/cat/{args.alloc_id}?path="
            + urllib.parse.quote(path),
        )
        sys.stdout.write(resp.get("Data", ""))
        return
    entries = _request(
        "GET",
        f"/v1/client/fs/ls/{args.alloc_id}?path="
        + urllib.parse.quote(path),
    )
    _table(
        [
            (
                "d" if e["IsDir"] else "-",
                e["Size"],
                e["Name"],
            )
            for e in entries
        ],
        ["Mode", "Size", "Name"],
    )


def cmd_monitor(args) -> None:
    """Follow the agent's logs (reference `nomad monitor`)."""
    if args.follow:
        # chunked live stream (reference agent monitor streaming)
        try:
            buf = b""
            for frame in _stream_get(
                "/v1/agent/monitor?follow=true"
            ):
                buf += frame
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    try:
                        print(json.loads(line)["Line"])
                    except (ValueError, KeyError):
                        pass
        except KeyboardInterrupt:
            pass
        except urllib.error.HTTPError as exc:
            print(
                f"Error ({exc.code}): {exc.reason}", file=sys.stderr
            )
            sys.exit(1)
        return
    index = -1
    try:
        while True:
            resp = _request(
                "GET", f"/v1/agent/monitor?index={index}&wait=2"
            )
            for line in resp.get("Lines", []):
                print(line)
            index = resp.get("Index", index)
            if not args.follow:
                break
    except KeyboardInterrupt:
        pass


def cmd_operator_autopilot(args) -> None:
    if args.action == "get-config":
        cfg = _request("GET", "/v1/operator/autopilot/configuration")
        if _emit(args, cfg):
            return
        for k, v in cfg.items():
            print(f"{k} = {v}")
    elif args.action == "set-config":
        body = {}
        if args.cleanup_dead_servers is not None:
            body["CleanupDeadServers"] = (
                args.cleanup_dead_servers == "true"
            )
        _request(
            "POST", "/v1/operator/autopilot/configuration", body
        )
        print("Configuration updated!")
    elif args.action == "health":
        h = _request("GET", "/v1/operator/autopilot/health")
        if _emit(args, h):
            return
        print(
            f"Healthy = {h['Healthy']}  Servers = {h['NumServers']}  "
            f"FailureTolerance = {h['FailureTolerance']}"
        )
        _table(
            [
                (s["Name"], s["Address"],
                 "alive" if s["Healthy"] else "failed",
                 s["Voter"])
                for s in h.get("Servers", [])
            ],
            ["Name", "Address", "Health", "Voter"],
        )


def cmd_operator_debug(args) -> None:
    """Collect a diagnostic bundle (reference `nomad operator debug`:
    pprof profiles, agent info, metrics, recent logs into an archive)."""
    import tarfile
    import tempfile

    captures = {
        "agent-self.json": ("GET", "/v1/agent/self"),
        "members.json": ("GET", "/v1/agent/members"),
        "metrics.json": ("GET", "/v1/metrics"),
        # accelerator supervisor: state machine + failover/canary
        # history, so a bundle from a degraded server shows WHEN the
        # device was lost and what tripped it
        "device.json": ("GET", "/v1/device"),
        # eval flight recorder: recent full traces, so a bundle from a
        # misbehaving server carries per-eval stage/conflict evidence
        "traces.json": ("GET", "/v1/traces?full=1&limit=256"),
        # metric time-series history: the last N snapshot windows, so
        # the bundle shows "p99 over the last ten minutes", not just
        # the instant the operator finally ran the capture
        "metrics-history.json": ("GET", "/v1/metrics/history"),
        # cluster-scope views (leader fan-in over every peer; on a
        # single-process server these answer with the local share):
        # stitched cross-server traces and every server's metrics,
        # with unreachable peers marked rather than omitted silently
        "cluster-traces.json": (
            "GET", "/v1/cluster/traces?full=1&limit=256"
        ),
        "cluster-metrics.json": ("GET", "/v1/cluster/metrics"),
        "cluster-metrics-history.json": (
            "GET", "/v1/cluster/metrics/history"
        ),
        # placement explainability: recent per-eval score
        # decompositions + filter attributions, cross-referenced with
        # traces.json by eval id
        "placements.json": ("GET", "/v1/placements?limit=256"),
        # control-loop flight data: SLO burn-rate status plus the
        # adaptive-decision ledger, cross-referenced with traces.json
        # by trace id — a bundle from a misbehaving server says WHAT
        # objective is burning and WHY each control loop chose what
        # it chose
        "slo.json": ("GET", "/v1/slo"),
        "decisions.json": ("GET", "/v1/decisions?limit=256"),
        "cluster-slo.json": ("GET", "/v1/cluster/slo"),
        "cluster-decisions.json": (
            "GET", "/v1/cluster/decisions?limit=256"
        ),
        "monitor.json": ("GET", "/v1/agent/monitor"),
        "pprof-goroutine.json": ("GET", "/v1/agent/pprof/goroutine"),
        "pprof-heap.json": ("GET", "/v1/agent/pprof/heap"),
        "jobs.json": ("GET", "/v1/jobs"),
        "nodes.json": ("GET", "/v1/nodes"),
        "scheduler-config.json": (
            "GET", "/v1/operator/scheduler/configuration"
        ),
    }
    out_path = args.output or "nomad-debug.tar.gz"
    with tempfile.TemporaryDirectory() as td:
        names = []
        for name, (method, path) in captures.items():
            try:
                data = _request(method, path)
            except SystemExit:
                # endpoint unavailable (e.g. cluster-only): skip
                continue
            p = os.path.join(td, name)
            with open(p, "w") as f:
                json.dump(data, f, indent=2)
            names.append((p, name))
        with tarfile.open(out_path, "w:gz") as tar:
            for p, name in names:
                tar.add(p, arcname=f"nomad-debug/{name}")
    print(f"==> Wrote debug bundle to {out_path} "
          f"({len(names)} captures)")


def cmd_device_status(args) -> None:
    """Accelerator supervisor status (GET /v1/device)."""
    st = _request("GET", "/v1/device")
    if _emit(args, st):
        return
    print(
        f"Platform: {st.get('platform')}  "
        f"Device kind: {st.get('device_kind')}  "
        f"Devices: {st.get('device_count', 0)}"
    )
    if not st.get("enabled"):
        print("Device supervision idle (JAX resolved no accelerator)")
        return
    lat = st.get("probe_latency_ms", {})
    _table(
        [
            (
                st.get("state", "?"),
                st.get("backend", "?"),
                st.get("failover_count", 0),
                st.get("recovered_count", 0),
                st.get("watchdog_trips", 0),
                f"{st.get('canary_ok', 0)}/{st.get('canary_fail', 0)}",
                f"{lat.get('p50', 0)}/{lat.get('p99', 0)}",
            )
        ],
        [
            "State", "Backend", "Failovers", "Recovered",
            "WatchdogTrips", "Canary ok/fail", "Probe p50/p99 ms",
        ],
    )
    if st.get("last_error"):
        print(f"Last error: {st['last_error']}")
    history = st.get("history", [])
    if history:
        print("Recent transitions:")
        for h in history[-8:]:
            print(
                f"  {h.get('from')} -> {h.get('to')}: "
                f"{h.get('reason')}"
            )


def cmd_slo_status(args) -> None:
    """SLO burn-rate status (GET /v1/slo)."""
    st = _request("GET", "/v1/slo")
    if _emit(args, st):
        return
    if not st.get("enabled"):
        print("SLO engine disabled (NOMAD_TPU_SLO=0)")
        return
    win = st.get("windows", {})
    print(
        f"Worst: {st.get('worst', 'OK')}  "
        f"(windows fast={win.get('fast_n')} slow={win.get('slow_n')} "
        f"x {win.get('interval_s')}s, retained={win.get('retained')})"
    )
    _table(
        [
            (
                o.get("name", "?"),
                o.get("status", "?"),
                o.get("burn_fast", 0),
                o.get("burn_slow", 0),
                o.get("target_ms", "-"),
                o.get("budget", "-"),
            )
            for o in st.get("objectives", [])
        ],
        [
            "Objective", "Status", "BurnFast", "BurnSlow",
            "Target ms", "Budget",
        ],
    )


def cmd_decisions(args) -> None:
    """Adaptive-decision ledger (GET /v1/decisions)."""
    qs = []
    for key in ("site", "outcome", "trace"):
        val = getattr(args, key, None)
        if val:
            qs.append(f"{key}={urllib.parse.quote(val)}")
    qs.append(f"limit={getattr(args, 'limit', None) or 32}")
    st = _request("GET", "/v1/decisions?" + "&".join(qs))
    if _emit(args, st):
        return
    if not st.get("enabled"):
        print("Decision ledger disabled (NOMAD_TPU_DECISIONS=0)")
        return
    ring = st.get("ring", {})
    print(
        f"Ring: {ring.get('depth', 0)}/{ring.get('cap', 0)} "
        f"(evicted {ring.get('evicted', 0)})"
    )
    rows = []
    for rec in st.get("decisions", []):
        inputs = rec.get("inputs", {})
        brief = " ".join(
            f"{k}={inputs[k]}" for k in sorted(inputs)[:3]
        )
        rows.append(
            (
                rec.get("seq", 0),
                rec.get("site", "?"),
                rec.get("action", "?"),
                rec.get("outcome", "?"),
                rec.get("trace_id") or "-",
                brief,
            )
        )
    _table(
        rows,
        ["Seq", "Site", "Action", "Outcome", "Trace", "Inputs"],
    )


def cmd_operator_raft(args) -> None:
    if getattr(args, "action", "list-peers") == "remove-peer":
        _request(
            "DELETE",
            "/v1/operator/raft/peer?address="
            + urllib.parse.quote(args.address or ""),
        )
        print(f"==> Removed raft peer {args.address}")
        return
    cfg = _request("GET", "/v1/operator/raft/configuration")
    if _emit(args, cfg.get("Servers", [])):
        return
    _table(
        [
            (s["ID"], s["Address"], s["Leader"], s["Voter"])
            for s in cfg.get("Servers", [])
        ],
        ["ID", "Address", "Leader", "Voter"],
    )


def cmd_job_allocs(args) -> None:
    """(reference command/job_allocs.go)"""
    allocs = _request("GET", f"/v1/job/{args.job_id}/allocations")
    if _emit(args, allocs):
        return
    _table(
        [
            (
                (a.get("ID") or a.get("id", ""))[:8],
                (a.get("NodeID") or a.get("node_id", ""))[:8],
                a.get("TaskGroup") or a.get("task_group", ""),
                a.get("DesiredStatus")
                or a.get("desired_status", ""),
                a.get("ClientStatus")
                or a.get("client_status", ""),
            )
            for a in allocs
        ],
        ["ID", "Node ID", "Task Group", "Desired", "Status"],
    )


def cmd_volume_detach(args) -> None:
    """(reference command/volume_detach.go)"""
    resp = _request(
        "PUT",
        f"/v1/volume/csi/{args.volume_id}/detach?node="
        + urllib.parse.quote(args.node_id),
        {},
    )
    print(
        f"==> Detached {resp.get('DetachedClaims', 0)} claim(s) "
        f"from {args.node_id[:8]}"
    )


def cmd_server_force_leave(args) -> None:
    """(reference command/server_force_leave.go)"""
    _request(
        "PUT",
        "/v1/agent/force-leave?node="
        + urllib.parse.quote(args.name),
        {},
    )
    print(f"==> Force-left {args.name}")


def cmd_license(args) -> None:
    """(reference command/license_get.go / license_put.go; OSS gates
    the feature to Enterprise — surfacing the server's error is the
    parity behavior)"""
    if args.license_cmd == "get":
        _request("GET", "/v1/operator/license")
    else:
        _request("PUT", "/v1/operator/license", {"License": ""})


def cmd_enterprise_gate(args) -> None:
    """sentinel/quota command families (reference registers them in
    OSS builds; the feature itself is Enterprise-gated server-side)"""
    family = args.family
    _request("GET", f"/v1/{family}s" if family == "quota" else
             "/v1/sentinel/policies")


def cmd_keyring(args) -> None:
    """(reference command/operator_keyring.go: -install/-use/-remove/
    -list against the serf keyring)"""
    if args.install:
        resp = _request(
            "PUT", "/v1/operator/keyring",
            {"Operation": "install", "Key": args.install},
        )
    elif args.use:
        resp = _request(
            "PUT", "/v1/operator/keyring",
            {"Operation": "use", "Key": args.use},
        )
    elif args.remove:
        resp = _request(
            "PUT", "/v1/operator/keyring",
            {"Operation": "remove", "Key": args.remove},
        )
    else:
        resp = _request("GET", "/v1/operator/keyring")
    keys = resp.get("Keys", {})
    primary = set(resp.get("PrimaryKeys", {}))
    for key in keys:
        marker = " (primary)" if key in primary else ""
        print(f"{key}{marker}")


def cmd_check(args) -> None:
    """Agent health probe (reference command/check.go: exit 0 when
    the agent answers)"""
    _request("GET", "/v1/agent/self")
    print("ok")


def cmd_ui(args) -> None:
    """(reference command/ui.go: print/open the web UI URL)"""
    url = _addr() + "/ui/"
    print(url)
    if getattr(args, "open", False):
        import webbrowser

        webbrowser.open(url)


def cmd_job_stop(args) -> None:
    purge = "?purge=true" if args.purge else ""
    resp = _request("DELETE", f"/v1/job/{args.job_id}{purge}")
    print(f"==> Evaluation {resp.get('EvalID', '')[:8]} created")


def cmd_job_scale(args) -> None:
    resp = _request(
        "POST",
        f"/v1/job/{args.job_id}/scale",
        {"Target": {"Group": args.group}, "Count": args.count},
    )
    print(f"==> Evaluation {resp.get('EvalID', '')[:8]} created")


def cmd_volume_register(args) -> None:
    """(reference command/volume_register.go; accepts a JSON volume
    spec file)"""
    with open(args.file) as fh:
        spec = json.load(fh)
    vol_id = spec.get("ID") or spec.get("id")
    if not vol_id:
        print("error: volume spec requires an ID", file=sys.stderr)
        raise SystemExit(1)
    resp = _request("POST", f"/v1/volume/csi/{vol_id}", spec)
    print(f"==> Volume {vol_id} registered")


def cmd_volume_status(args) -> None:
    """(reference command/volume_status.go)"""
    if getattr(args, "volume_id", None):
        v = _request("GET", f"/v1/volume/csi/{args.volume_id}")
        if _emit(args, v):
            return
        print(json.dumps(v, indent=2))
        return
    vols = _request("GET", "/v1/volumes")
    if _emit(args, vols):
        return
    _table(
        [
            (
                v["ID"],
                v["Name"],
                v["PluginID"],
                v["Schedulable"],
                v["AccessMode"],
                f"{v['CurrentReaders']}r/{v['CurrentWriters']}w",
            )
            for v in vols
        ],
        ("ID", "Name", "Plugin", "Schedulable", "Access", "Claims"),
    )


def cmd_volume_deregister(args) -> None:
    """(reference command/volume_deregister.go)"""
    force = "?force=true" if args.force else ""
    _request("DELETE", f"/v1/volume/csi/{args.volume_id}{force}")
    print(f"==> Volume {args.volume_id} deregistered")


def cmd_plugin_status(args) -> None:
    """(reference command/plugin_status.go)"""
    plugins = _request("GET", "/v1/plugins")
    if _emit(args, plugins):
        return
    _table(
        [
            (p["ID"], f"{p['NodesHealthy']}/{p['NodesExpected']}")
            for p in plugins
        ],
        ("ID", "Nodes Healthy"),
    )


def cmd_scaling_policies(args) -> None:
    """(reference command/scaling_policy_list.go)"""
    path = "/v1/scaling/policies"
    if getattr(args, "job_id", None):
        path += f"?job={args.job_id}"
    pols = _request("GET", path)
    if _emit(args, pols):
        return
    _table(
        [
            (
                p["ID"][:8],
                p["Enabled"],
                p["Type"],
                p["Target"].get("Job", ""),
                p["Target"].get("Group", ""),
            )
            for p in pols
        ],
        ("ID", "Enabled", "Type", "Job", "Group"),
    )


def cmd_scaling_policy_info(args) -> None:
    """(reference command/scaling_policy_info.go)"""
    p = _request("GET", f"/v1/scaling/policy/{args.policy_id}")
    if _emit(args, p):
        return
    print(json.dumps(p, indent=2))


def cmd_server_members(args) -> None:
    """(reference command/server_members.go)"""
    info = _request("GET", "/v1/agent/members")
    if _emit(args, info["Members"]):
        return
    _table(
        [
            (
                m["Name"],
                m["Region"],
                m["Role"],
                m["Status"],
                m["Incarnation"],
            )
            for m in info["Members"]
        ],
        ["Name", "Region", "Role", "Status", "Incarnation"],
    )


def cmd_node_status(args) -> None:
    if not args.node_id:
        nodes = _request("GET", "/v1/nodes")
        if _emit(args, nodes):
            return
        _table(
            [
                (
                    n["ID"][:8],
                    n["Name"],
                    n["Datacenter"],
                    n["SchedulingEligibility"],
                    n["Status"],
                )
                for n in nodes
            ],
            ["ID", "Name", "DC", "Eligibility", "Status"],
        )
        return
    node = _request("GET", f"/v1/node/{args.node_id}")
    if _emit(args, node):
        return
    print(f"ID          = {node['id']}")
    print(f"Name        = {node['name']}")
    print(f"Datacenter  = {node['datacenter']}")
    print(f"Status      = {node['status']}")
    print(f"Eligibility = {node['scheduling_eligibility']}")
    print(f"Drain       = {node['drain']}")
    res = node["node_resources"]
    print(
        f"Resources   = cpu {res['cpu']} MHz, mem {res['memory_mb']} MiB,"
        f" disk {res['disk_mb']} MiB"
    )
    allocs = _request("GET", f"/v1/node/{args.node_id}/allocations")
    if allocs:
        print("\nAllocations")
        _table(
            [
                (a["id"][:8], a["job_id"][:20], a["client_status"])
                for a in allocs
            ],
            ["ID", "Job", "Status"],
        )


def cmd_node_drain(args) -> None:
    body = {}
    if args.enable:
        body = {"DrainSpec": {"Deadline": int(args.deadline * 1e9)}}
    _request("POST", f"/v1/node/{args.node_id}/drain", body)
    print(
        f"==> Node {args.node_id[:8]} drain "
        f"{'enabled' if args.enable else 'disabled'}"
    )
    if not (args.enable and getattr(args, "monitor", False)):
        return
    # -monitor: follow until every alloc has migrated off the node
    # (reference command/node_drain.go monitorDrain)
    seen = set()
    while True:
        allocs = _request(
            "GET", f"/v1/node/{args.node_id}/allocations"
        )
        live = [
            a
            for a in allocs
            if a.get("desired_status") == "run"
            and a.get("client_status") in ("pending", "running")
        ]
        for a in allocs:
            key = (a["id"], a.get("desired_status"))
            if key not in seen and a.get("desired_status") != "run":
                seen.add(key)
                print(
                    f"    alloc {a['id'][:8]} ({a.get('job_id')}) "
                    f"-> {a.get('desired_status')}"
                )
        node = _request("GET", f"/v1/node/{args.node_id}")
        if not live and not node.get("Drain", False):
            print("==> Drain complete")
            return
        time.sleep(1.0)


def cmd_node_eligibility(args) -> None:
    elig = "eligible" if args.enable else "ineligible"
    _request(
        "POST",
        f"/v1/node/{args.node_id}/eligibility",
        {"Eligibility": elig},
    )
    print(f"==> Node {args.node_id[:8]} marked {elig}")


def cmd_alloc_status(args) -> None:
    alloc = _request("GET", f"/v1/allocation/{args.alloc_id}")
    if _emit(args, alloc):
        return
    print(f"ID           = {alloc['id']}")
    print(f"Name         = {alloc['name']}")
    print(f"Node ID      = {alloc['node_id']}")
    print(f"Job ID       = {alloc['job_id']}")
    print(f"Desired      = {alloc['desired_status']}")
    print(f"Status       = {alloc['client_status']}")
    for task, state in (alloc.get("task_states") or {}).items():
        print(f"\nTask {task!r}: {state['state']}"
              f"{' (failed)' if state.get('failed') else ''}")


def cmd_eval_status(args) -> None:
    ev = _request("GET", f"/v1/evaluation/{args.eval_id}")
    if _emit(args, ev):
        return
    print(f"ID           = {ev['id']}")
    print(f"Type         = {ev['type']}")
    print(f"TriggeredBy  = {ev['triggered_by']}")
    print(f"Job ID       = {ev['job_id']}")
    print(f"Status       = {ev['status']}")
    if ev.get("blocked_eval"):
        print(f"BlockedEval  = {ev['blocked_eval']}")


def cmd_eval_explain(args) -> None:
    """Render an eval's placement explanation
    (GET /v1/evaluation/<id>/placement): winner, runners-up with
    per-component score terms, and the top filter reasons."""
    rec = _request(
        "GET", f"/v1/evaluation/{args.eval_id}/placement"
    )
    if _emit(args, rec):
        return
    print(f"Eval         = {rec['EvalID']}")
    print(f"Job ID       = {rec['JobID']}")
    print(f"Type         = {rec['Type']} ({rec['TriggeredBy']})")
    if rec.get("served_by"):
        # follower-planned eval: the record came back through the
        # cluster fan-in from the server that ran the scheduler
        print(f"Served by    = {rec['served_by']}")
    if rec.get("TraceID"):
        print(f"Trace        = /v1/traces/{rec['EvalID']}")
    storm = rec.get("Storm")
    if storm:
        # placements came from the global storm solve, not the
        # per-eval greedy walk: show the auction round, the aggregate
        # assignment score and how many rows diverged from the walk
        print(
            f"Storm        = solved round {storm.get('Round')}, "
            f"score {storm.get('AssignmentScore')}, "
            f"{storm.get('DivergentRows', 0)}/{storm.get('Rows', 0)}"
            " rows diverged from the greedy walk"
        )
    for tg, g in (rec.get("TaskGroups") or {}).items():
        metric = g.get("Metric") or {}
        status = "FAILED" if g.get("Failed") else "placed"
        print(
            f"\nTask group {tg!r}: {g.get('Placed', 0)} {status}, "
            f"{metric.get('NodesEvaluated', 0)} evaluated / "
            f"{metric.get('NodesFiltered', 0)} filtered / "
            f"{metric.get('NodesExhausted', 0)} exhausted"
            + (
                f" ({metric.get('CoalescedFailures')} coalesced)"
                if metric.get("CoalescedFailures")
                else ""
            )
        )
        avail = metric.get("NodesAvailable") or {}
        if avail:
            print(
                "Available    = "
                + ", ".join(
                    f"{dc}:{n}" for dc, n in sorted(avail.items())
                )
            )
        if metric.get("AllocationTime"):
            print(
                f"AllocTime    = "
                f"{metric['AllocationTime'] * 1000.0:.2f} ms"
            )
        winner = g.get("Winner", "")
        meta = sorted(
            metric.get("ScoreMetaData") or [],
            key=lambda m: -m.get("NormScore", 0.0),
        )
        if meta:
            rows = []
            for m in meta:
                scores = m.get("Scores") or {}
                terms = ", ".join(
                    f"{k}={v:.4f}"
                    for k, v in sorted(scores.items())
                    if k != "normalized-score"
                )
                rows.append(
                    (
                        ("*" if m["NodeID"] == winner else " ")
                        + m["NodeID"][:8],
                        f"{m.get('NormScore', 0.0):.4f}",
                        terms,
                    )
                )
            _table(rows, ["Node", "NormScore", "Score terms"])
        reasons = sorted(
            (metric.get("ConstraintFiltered") or {}).items(),
            key=lambda kv: -kv[1],
        )
        exhausted = sorted(
            (metric.get("DimensionExhausted") or {}).items(),
            key=lambda kv: -kv[1],
        )
        if reasons or exhausted:
            _table(
                [
                    (reason, n, "filtered")
                    for reason, n in reasons[:8]
                ]
                + [
                    (dim, n, "exhausted")
                    for dim, n in exhausted[:8]
                ],
                ["Reason", "Nodes", "Kind"],
            )


def cmd_deployment(args) -> None:
    if args.action == "status":
        if args.id:
            d = _request("GET", f"/v1/deployment/{args.id}")
            if _emit(args, d):
                return
            print(json.dumps(d, indent=2))
        else:
            ds = _request("GET", "/v1/deployments")
            if _emit(args, ds):
                return
            _table(
                [
                    (d["id"][:8], d["job_id"][:20], d["status"])
                    for d in ds
                ],
                ["ID", "Job", "Status"],
            )
    elif args.action == "list":
        ds = _request("GET", "/v1/deployments")
        if _emit(args, ds):
            return
        _table(
            [(d["id"][:8], d["job_id"][:20], d["status"]) for d in ds],
            ["ID", "Job", "Status"],
        )
    elif args.action == "promote":
        _request("POST", f"/v1/deployment/promote/{args.id}", {})
        print("==> Deployment promoted")
    elif args.action == "fail":
        _request("POST", f"/v1/deployment/fail/{args.id}", {})
        print("==> Deployment failed")
    elif args.action == "pause":
        _request(
            "POST", f"/v1/deployment/pause/{args.id}", {"Pause": True}
        )
        print("==> Deployment paused")
    elif args.action == "resume":
        _request(
            "POST", f"/v1/deployment/pause/{args.id}", {"Pause": False}
        )
        print("==> Deployment resumed")
    elif args.action == "unblock":
        # multiregion deployment coordination is the enterprise no-op
        # in the reference OSS tree (deploymentwatcher/
        # multiregion_oss.go); the command exists for surface parity
        print(
            "Error: deployment unblock applies to multiregion "
            "deployments, which follow the OSS no-op coordination "
            "(deployments never enter the blocked state)",
            file=sys.stderr,
        )
        sys.exit(1)


def cmd_operator_snapshot(args) -> None:
    if args.action == "save":
        resp = _request(
            "POST", "/v1/operator/snapshot/save", {"Path": args.path}
        )
        print(f"==> Snapshot saved to {resp['Saved']}")
    elif args.action == "inspect":
        # local file inspection, no API round trip (reference
        # command/operator_snapshot_inspect.go)
        import gzip
        import pickle

        with open(args.path, "rb") as f:
            raw = f.read()
        try:
            payload = pickle.loads(gzip.decompress(raw))
        except OSError:
            payload = pickle.loads(raw)
        print(f"Version       = {payload.get('version')}")
        print(f"Index         = {payload.get('index')}")
        for table in (
            "nodes", "jobs", "allocs", "evals", "deployments",
            "csi_volumes", "scaling_policies", "namespaces",
            "acl_policies", "acl_tokens",
        ):
            if table in payload:
                print(f"{table:<14}= {len(payload[table])}")
    else:
        resp = _request(
            "POST", "/v1/operator/snapshot/restore", {"Path": args.path}
        )
        print(f"==> Snapshot restored (index {resp['Index']})")


def cmd_namespace(args) -> None:
    if args.ns_cmd == "list":
        nss = _request("GET", "/v1/namespaces")
        if _emit(args, nss):
            return
        _table(
            [(n["Name"], n["Description"]) for n in nss],
            ["Name", "Description"],
        )
    elif args.ns_cmd in ("status", "inspect"):
        n = _request("GET", f"/v1/namespace/{args.name}")
        if _emit(args, n):
            return
        if args.ns_cmd == "inspect":
            print(json.dumps(n, indent=2))
        else:
            print(f"Name        = {n['Name']}")
            print(f"Description = {n['Description']}")
    elif args.ns_cmd == "apply":
        _request(
            "POST",
            "/v1/namespaces",
            {"Name": args.name, "Description": args.description or ""},
        )
        print(f'==> Namespace "{args.name}" applied')
    elif args.ns_cmd == "delete":
        _request("DELETE", f"/v1/namespace/{args.name}")
        print(f'==> Namespace "{args.name}" deleted')


def cmd_acl(args) -> None:
    if args.acl_cmd == "bootstrap":
        resp = _request("POST", "/v1/acl/bootstrap", {})
        print(f"Accessor ID = {resp['AccessorID']}")
        print(f"Secret ID   = {resp['SecretID']}")
        print(f"Type        = {resp.get('Type', 'management')}")
        return
    if args.acl_cmd == "policy":
        if args.action == "list":
            ps = _request("GET", "/v1/acl/policies")
            if _emit(args, ps):
                return
            _table([(p["Name"],) for p in ps], ["Name"])
        elif args.action == "info":
            p = _request("GET", f"/v1/acl/policy/{args.name}")
            if _emit(args, p):
                return
            print(json.dumps(p, indent=2))
        elif args.action == "apply":
            with open(args.file) as f:
                rules = json.load(f)
            _request("POST", f"/v1/acl/policy/{args.name}", rules)
            print(f'==> Policy "{args.name}" applied')
        elif args.action == "delete":
            _request("DELETE", f"/v1/acl/policy/{args.name}")
            print(f'==> Policy "{args.name}" deleted')
        return
    # token family
    if args.action == "list":
        ts = _request("GET", "/v1/acl/tokens")
        if _emit(args, ts):
            return
        _table(
            [
                (
                    t["AccessorID"][:8],
                    t["Name"],
                    t["Type"],
                    ",".join(t.get("Policies") or []),
                )
                for t in ts
            ],
            ["Accessor", "Name", "Type", "Policies"],
        )
    elif args.action == "create":
        resp = _request(
            "POST",
            "/v1/acl/tokens",
            {
                "Name": args.name or "",
                "Type": args.type,
                "Policies": args.policy or [],
            },
        )
        print(f"Accessor ID = {resp['AccessorID']}")
        print(f"Secret ID   = {resp['SecretID']}")
    elif args.action == "info":
        t = _request("GET", f"/v1/acl/token/{args.accessor}")
        if _emit(args, t):
            return
        print(json.dumps(t, indent=2))
    elif args.action == "self":
        t = _request("GET", "/v1/acl/token/self")
        if _emit(args, t):
            return
        print(json.dumps(t, indent=2))
    elif args.action == "update":
        body = {}
        if args.name:
            body["Name"] = args.name
        if args.policy:
            body["Policies"] = args.policy
        _request("POST", f"/v1/acl/token/{args.accessor}", body)
        print(f"==> Token {args.accessor[:8]} updated")
    elif args.action == "delete":
        _request("DELETE", f"/v1/acl/token/{args.accessor}")
        print(f"==> Token {args.accessor[:8]} deleted")


def cmd_job_deployments(args) -> None:
    ds = _request("GET", f"/v1/job/{args.job_id}/deployments")
    if _emit(args, ds):
        return
    _table(
        [
            (d["id"][:8], d.get("job_version", 0), d["status"])
            for d in ds
        ],
        ["ID", "Job Version", "Status"],
    )


def cmd_job_eval(args) -> None:
    resp = _request("POST", f"/v1/job/{args.job_id}/evaluate", {})
    print(f"==> Created eval {resp['EvalID']}")


def cmd_job_promote(args) -> None:
    ds = _request("GET", f"/v1/job/{args.job_id}/deployments")
    live = [d for d in ds if d["status"] == "running"]
    if not live:
        print("No running deployment to promote", file=sys.stderr)
        sys.exit(1)
    _request("POST", f"/v1/deployment/promote/{live[0]['id']}", {})
    print(f"==> Promoted deployment {live[0]['id'][:8]}")


def cmd_job_periodic(args) -> None:
    resp = _request(
        "POST", f"/v1/job/{args.job_id}/periodic/force", {}
    )
    print(f"==> Forced launch: {resp['JobID']}")


EXAMPLE_JOB_HCL = '''job "example" {
  datacenters = ["dc1"]
  type        = "service"

  group "cache" {
    count = 1

    task "redis" {
      driver = "exec"

      config {
        command = "/usr/bin/redis-server"
        args    = ["--port", "6379"]
      }

      resources {
        cpu    = 500
        memory = 256
      }
    }
  }
}
'''


def cmd_job_init(args) -> None:
    path = args.filename or "example.nomad"
    if os.path.exists(path):
        print(f"File {path!r} already exists", file=sys.stderr)
        sys.exit(1)
    with open(path, "w") as f:
        f.write(EXAMPLE_JOB_HCL)
    print(f"==> Example job file written to {path}")


def cmd_server_join(args) -> None:
    resp = _request(
        "POST", "/v1/agent/join", {"address": args.address}
    )
    print(f"==> Joined {resp.get('num_joined', 0)} server(s)")


def cmd_node_config(args) -> None:
    n = _request("GET", f"/v1/node/{args.node_id}")
    if _emit(args, n):
        return
    print(json.dumps(n, indent=2))


def cmd_operator_keygen(args) -> None:
    # 32 random bytes, base64 (reference command/operator_keygen.go);
    # usable as cluster key material (e.g. seeding TLS cert passphrases
    # or gossip keys in external tooling)
    import base64
    import secrets

    print(base64.b64encode(secrets.token_bytes(32)).decode())


def cmd_status(args) -> None:
    """Generic status: dispatch an identifier to the right family by
    prefix search (reference command/status.go resolves jobs, allocs,
    nodes, evals, deployments through the search endpoint)."""
    if not args.job_id:
        return cmd_job_status(args)
    ident = args.job_id
    matches = _request(
        "GET", f"/v1/search?prefix={urllib.parse.quote(ident)}&context=all"
    ).get("Matches", {})
    for context, handler in (
        ("jobs", cmd_job_status),
        ("allocs", None),
        ("nodes", None),
        ("evals", None),
        ("deployments", None),
    ):
        hits = matches.get(context) or []
        if ident in hits or (len(hits) == 1 and hits[0].startswith(ident)):
            full = ident if ident in hits else hits[0]
            if context == "jobs":
                args.job_id = full
                return cmd_job_status(args)
            if context == "allocs":
                args.alloc_id = full
                return cmd_alloc_status(args)
            if context == "nodes":
                args.node_id = full
                return cmd_node_status(args)
            if context == "evals":
                args.eval_id = full
                return cmd_eval_status(args)
            if context == "deployments":
                args.action, args.id = "status", full
                return cmd_deployment(args)
    # fall through: treat as a job id (matches reference behavior of
    # erroring with the most likely family)
    return cmd_job_status(args)


def cmd_system(args) -> None:
    if args.action == "gc":
        _request("POST", "/v1/system/gc", {})
        print("==> GC triggered")
    elif args.action == "reconcile":
        _request("POST", "/v1/system/reconcile/summaries", {})
        print("==> Job summaries reconciled")


def cmd_operator_scheduler(args) -> None:
    if args.action == "get-config":
        cfg = _request("GET", "/v1/operator/scheduler/configuration")
        if _emit(args, cfg):
            return
        print(json.dumps(cfg, indent=2))
    else:
        cfg = _request("GET", "/v1/operator/scheduler/configuration")
        if args.algorithm:
            cfg["SchedulerAlgorithm"] = args.algorithm
        if args.tpu is not None:
            cfg["TPUSchedulerEnabled"] = args.tpu == "true"
        _request("POST", "/v1/operator/scheduler/configuration", cfg)
        print("==> Scheduler configuration updated")


def cmd_system_gc(args) -> None:
    _request("POST", "/v1/system/gc", {})
    print("==> GC triggered")


def cmd_agent_info(args) -> None:
    info = _request("GET", "/v1/agent/self")
    if _emit(args, info):
        return
    print(json.dumps(info, indent=2))


def cmd_version(args) -> None:
    from . import __version__

    print(f"nomad-tpu v{__version__}")


# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="nomad-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    agent = sub.add_parser("agent")
    agent.add_argument("-dev", action="store_true", dest="dev")
    agent.add_argument(
        "-server-addr", default=None, dest="server_addr",
        help="host:port RPC bind — runs a TCP cluster server "
        "(multi-process control plane; see nomad_tpu.server.netagent)",
    )
    agent.add_argument(
        "-peers", default=None, dest="peers",
        help="comma-separated raft peer addresses incl. self",
    )
    agent.add_argument(
        "-join", default=None, dest="join",
        help="gossip seed address of a live server",
    )
    agent.add_argument(
        "-client", nargs="?", const=True, default=False,
        dest="client_mode", metavar="SERVERS",
        help="run a standalone CLIENT agent; server addresses come "
        "from -servers (reference agent -client -servers=...) or "
        "inline as -client=ADDR[,ADDR]",
    )
    agent.add_argument(
        "-servers", default="", dest="servers",
        help="comma-separated server HTTP addresses for -client",
    )
    agent.add_argument(
        "-callback-host", default="", dest="callback_host",
        help="address the SERVERS can reach this client on for "
        "fs/exec/logs proxying (cross-host clients must set it; "
        "default 127.0.0.1 only works same-box)",
    )
    agent.add_argument(
        "-data-dir", default="", dest="data_dir",
    )
    agent.add_argument("-http-port", type=int, default=None,
                       dest="http_port")
    agent.add_argument("-num-schedulers", type=int, default=None,
                       dest="num_schedulers")
    agent.add_argument("-config", default=None, dest="config")
    agent.set_defaults(fn=cmd_agent)

    job = sub.add_parser("job")
    job_sub = job.add_subparsers(dest="job_cmd", required=True)
    jr = job_sub.add_parser("run")
    jr.add_argument("file")
    jr.set_defaults(fn=cmd_job_run)
    jp = job_sub.add_parser("plan")
    jp.add_argument("file")
    jp.set_defaults(fn=cmd_job_plan)
    jd = job_sub.add_parser("dispatch")
    jd.add_argument("job_id")
    jd.add_argument("-meta", action="append", dest="meta")
    jd.set_defaults(fn=cmd_job_dispatch)
    js = job_sub.add_parser("status")
    js.add_argument("job_id", nargs="?")
    _add_fmt(js)
    js.set_defaults(fn=cmd_job_status)
    jst = job_sub.add_parser("stop")
    jst.add_argument("-purge", action="store_true", dest="purge")
    jst.add_argument("job_id")
    jst.set_defaults(fn=cmd_job_stop)
    jsc = job_sub.add_parser("scale")
    jsc.add_argument("job_id")
    jsc.add_argument("group")
    jsc.add_argument("count", type=int)
    jsc.set_defaults(fn=cmd_job_scale)
    jh = job_sub.add_parser("history")
    jh.add_argument("job_id")
    _add_fmt(jh)
    jh.set_defaults(fn=cmd_job_history)
    jrev = job_sub.add_parser("revert")
    jrev.add_argument("job_id")
    jrev.add_argument("version", type=int)
    jrev.set_defaults(fn=cmd_job_revert)
    jin = job_sub.add_parser("inspect")
    jin.add_argument("job_id")
    _add_fmt(jin)
    jin.set_defaults(fn=cmd_job_inspect)
    jv = job_sub.add_parser("validate")
    jv.add_argument("file")
    jv.set_defaults(fn=cmd_job_validate)
    jdep = job_sub.add_parser("deployments")
    jdep.add_argument("job_id")
    _add_fmt(jdep)
    jdep.set_defaults(fn=cmd_job_deployments)
    jev = job_sub.add_parser("eval")
    jev.add_argument("job_id")
    jev.set_defaults(fn=cmd_job_eval)
    jpr = job_sub.add_parser("promote")
    jpr.add_argument("job_id")
    jpr.set_defaults(fn=cmd_job_promote)
    jpf = job_sub.add_parser("periodic")
    jpf_sub = jpf.add_subparsers(
        dest="periodic_action", required=True
    )
    jpff = jpf_sub.add_parser("force")
    jpff.add_argument("job_id")
    jpff.set_defaults(fn=cmd_job_periodic)
    jini = job_sub.add_parser("init")
    jini.add_argument("filename", nargs="?", default="")
    jini.set_defaults(fn=cmd_job_init)
    jal = job_sub.add_parser("allocs")
    _add_fmt(jal)
    jal.add_argument("job_id")
    jal.set_defaults(fn=cmd_job_allocs)

    volume = sub.add_parser("volume")
    volume_sub = volume.add_subparsers(dest="volume_cmd", required=True)
    vr = volume_sub.add_parser("register")
    vr.add_argument("file")
    vr.set_defaults(fn=cmd_volume_register)
    vs = volume_sub.add_parser("status")
    vs.add_argument("volume_id", nargs="?", default=None)
    _add_fmt(vs)
    vs.set_defaults(fn=cmd_volume_status)
    vd = volume_sub.add_parser("deregister")
    vd.add_argument("volume_id")
    vd.add_argument("-force", dest="force", action="store_true")
    vd.set_defaults(fn=cmd_volume_deregister)
    vdet = volume_sub.add_parser("detach")
    vdet.add_argument("volume_id")
    vdet.add_argument("node_id")
    vdet.set_defaults(fn=cmd_volume_detach)

    plugin = sub.add_parser("plugin")
    plugin_sub = plugin.add_subparsers(dest="plugin_cmd", required=True)
    ps = plugin_sub.add_parser("status")
    _add_fmt(ps)
    ps.set_defaults(fn=cmd_plugin_status)

    scaling = sub.add_parser("scaling")
    scaling_sub = scaling.add_subparsers(dest="scaling_cmd", required=True)
    scp = scaling_sub.add_parser("policies")
    scp.add_argument("-job", dest="job_id", default=None)
    _add_fmt(scp)
    scp.set_defaults(fn=cmd_scaling_policies)
    sci = scaling_sub.add_parser("policy")
    sci.add_argument("policy_id")
    _add_fmt(sci)
    sci.set_defaults(fn=cmd_scaling_policy_info)

    server = sub.add_parser("server")
    server_sub = server.add_subparsers(dest="server_cmd", required=True)
    sm = server_sub.add_parser("members")
    _add_fmt(sm)
    sm.set_defaults(fn=cmd_server_members)
    sj = server_sub.add_parser("join")
    sj.add_argument("address")
    sj.set_defaults(fn=cmd_server_join)
    sfl = server_sub.add_parser("force-leave")
    sfl.add_argument("name")
    sfl.set_defaults(fn=cmd_server_force_leave)

    node = sub.add_parser("node")
    node_sub = node.add_subparsers(dest="node_cmd", required=True)
    ns = node_sub.add_parser("status")
    ns.add_argument("node_id", nargs="?")
    _add_fmt(ns)
    ns.set_defaults(fn=cmd_node_status)
    nd = node_sub.add_parser("drain")
    nd_group = nd.add_mutually_exclusive_group(required=True)
    nd_group.add_argument("-enable", action="store_true", dest="enable")
    nd_group.add_argument("-disable", action="store_false", dest="enable")
    nd.add_argument("-deadline", type=float, default=3600.0,
                    dest="deadline")
    nd.add_argument("-monitor", action="store_true", dest="monitor")
    nd.add_argument("node_id")
    nd.set_defaults(fn=cmd_node_drain)
    nc = node_sub.add_parser("config")
    nc.add_argument("node_id")
    _add_fmt(nc)
    nc.set_defaults(fn=cmd_node_config)
    ne = node_sub.add_parser("eligibility")
    ne_group = ne.add_mutually_exclusive_group(required=True)
    ne_group.add_argument("-enable", action="store_true", dest="enable")
    ne_group.add_argument("-disable", action="store_false", dest="enable")
    ne.add_argument("node_id")
    ne.set_defaults(fn=cmd_node_eligibility)

    alloc = sub.add_parser("alloc")
    alloc_sub = alloc.add_subparsers(dest="alloc_cmd", required=True)
    als = alloc_sub.add_parser("status")
    als.add_argument("alloc_id")
    _add_fmt(als)
    als.set_defaults(fn=cmd_alloc_status)
    all_ = alloc_sub.add_parser("logs")
    all_.add_argument("-stderr", action="store_true", dest="stderr")
    all_.add_argument("-f", action="store_true", dest="follow")
    all_.add_argument("alloc_id")
    all_.add_argument("task")
    all_.set_defaults(fn=cmd_alloc_logs)
    alr = alloc_sub.add_parser("restart")
    alr.add_argument("alloc_id")
    alr.add_argument("task", nargs="?", default="")
    alr.set_defaults(fn=cmd_alloc_restart)
    alsg = alloc_sub.add_parser("signal")
    alsg.add_argument("-s", dest="signal", default="SIGTERM")
    alsg.add_argument("alloc_id")
    alsg.add_argument("task", nargs="?", default="")
    alsg.set_defaults(fn=cmd_alloc_signal)
    alst = alloc_sub.add_parser("stop")
    alst.add_argument("alloc_id")
    alst.set_defaults(fn=cmd_alloc_stop)
    alex = alloc_sub.add_parser("exec")
    alex.add_argument("-task", dest="task", default="")
    alex.add_argument(
        "-i", action="store_true", dest="interactive",
        help="interactive session over the websocket stream",
    )
    alex.add_argument("alloc_id")
    # REMAINDER so the command's own flags (e.g. sh -c) pass through
    alex.add_argument("cmd", nargs=argparse.REMAINDER)
    alex.set_defaults(fn=cmd_alloc_exec)
    alfs = alloc_sub.add_parser("fs")
    alfs.add_argument("-cat", action="store_true", dest="cat")
    alfs.add_argument("alloc_id")
    alfs.add_argument("path", nargs="?", default="")
    alfs.set_defaults(fn=cmd_alloc_fs)

    ev = sub.add_parser("eval")
    ev_sub = ev.add_subparsers(dest="eval_cmd", required=True)
    evs = ev_sub.add_parser("status")
    evs.add_argument("eval_id")
    _add_fmt(evs)
    evs.set_defaults(fn=cmd_eval_status)
    eve = ev_sub.add_parser("explain")
    eve.add_argument("eval_id")
    _add_fmt(eve)
    eve.set_defaults(fn=cmd_eval_explain)

    dep = sub.add_parser("deployment")
    dep_sub = dep.add_subparsers(dest="action", required=True)
    for name in (
        "status", "list", "promote", "fail", "pause", "resume",
        "unblock",
    ):
        dp = dep_sub.add_parser(name)
        if name in ("status", "list"):
            _add_fmt(dp)
            dp.add_argument("id", nargs="?")
        else:
            # promote/fail/pause/resume/unblock act on ONE
            # deployment: a missing id is a usage error, not a
            # request to /v1/deployment/<action>/None
            dp.add_argument("id")
        dp.set_defaults(fn=cmd_deployment)

    nsp = sub.add_parser("namespace")
    nsp_sub = nsp.add_subparsers(dest="ns_cmd", required=True)
    nsl = nsp_sub.add_parser("list")
    _add_fmt(nsl)
    nsl.set_defaults(fn=cmd_namespace)
    for name in ("status", "inspect", "delete"):
        sp = nsp_sub.add_parser(name)
        if name != "delete":
            _add_fmt(sp)
        sp.add_argument("name")
        sp.set_defaults(fn=cmd_namespace)
    nsa = nsp_sub.add_parser("apply")
    nsa.add_argument("-description", dest="description", default="")
    nsa.add_argument("name")
    nsa.set_defaults(fn=cmd_namespace)

    acl = sub.add_parser("acl")
    acl_sub = acl.add_subparsers(dest="acl_cmd", required=True)
    aclb = acl_sub.add_parser("bootstrap")
    aclb.set_defaults(fn=cmd_acl)
    aclp = acl_sub.add_parser("policy")
    aclp_sub = aclp.add_subparsers(dest="action", required=True)
    app_ = aclp_sub.add_parser("apply")
    app_.add_argument("name")
    app_.add_argument("file")
    app_.set_defaults(fn=cmd_acl)
    apl = aclp_sub.add_parser("list")
    _add_fmt(apl)
    apl.set_defaults(fn=cmd_acl)
    for name in ("info", "delete"):
        sp = aclp_sub.add_parser(name)
        if name == "info":
            _add_fmt(sp)
        sp.add_argument("name")
        sp.set_defaults(fn=cmd_acl)
    aclt = acl_sub.add_parser("token")
    aclt_sub = aclt.add_subparsers(dest="action", required=True)
    atc = aclt_sub.add_parser("create")
    atc.add_argument("-name", dest="name", default="")
    atc.add_argument("-type", dest="type", default="client")
    atc.add_argument("-policy", action="append", dest="policy")
    atc.set_defaults(fn=cmd_acl)
    atl = aclt_sub.add_parser("list")
    _add_fmt(atl)
    atl.set_defaults(fn=cmd_acl)
    ats = aclt_sub.add_parser("self")
    _add_fmt(ats)
    ats.set_defaults(fn=cmd_acl)
    for name in ("info", "delete"):
        sp = aclt_sub.add_parser(name)
        if name == "info":
            _add_fmt(sp)
        sp.add_argument("accessor")
        sp.set_defaults(fn=cmd_acl)
    atu = aclt_sub.add_parser("update")
    atu.add_argument("-name", dest="name", default="")
    atu.add_argument("-policy", action="append", dest="policy")
    atu.add_argument("accessor")
    atu.set_defaults(fn=cmd_acl)

    op = sub.add_parser("operator")
    op_sub = op.add_subparsers(dest="op_cmd", required=True)
    osch = op_sub.add_parser("scheduler")
    osch.add_argument("action", choices=["get-config", "set-config"])
    osch.add_argument("-algorithm", choices=["binpack", "spread"],
                      default=None)
    osch.add_argument("-tpu", choices=["true", "false"], default=None)
    _add_fmt(osch)
    osch.set_defaults(fn=cmd_operator_scheduler)
    osnap = op_sub.add_parser("snapshot")
    osnap_sub = osnap.add_subparsers(dest="action", required=True)
    for name in ("save", "restore", "inspect"):
        sp_p = osnap_sub.add_parser(name)
        sp_p.add_argument("path")
        sp_p.set_defaults(fn=cmd_operator_snapshot)
    oap = op_sub.add_parser("autopilot")
    oap_sub = oap.add_subparsers(dest="action", required=True)
    for name in ("get-config", "set-config", "health"):
        ap_p = oap_sub.add_parser(name)
        if name == "set-config":
            ap_p.add_argument(
                "-cleanup-dead-servers",
                dest="cleanup_dead_servers",
                choices=["true", "false"], default=None,
            )
        else:
            _add_fmt(ap_p)
        ap_p.set_defaults(fn=cmd_operator_autopilot)
    oraft = op_sub.add_parser("raft")
    oraft_sub = oraft.add_subparsers(dest="action", required=True)
    orl = oraft_sub.add_parser("list-peers")
    _add_fmt(orl)
    orl.set_defaults(fn=cmd_operator_raft)
    orr = oraft_sub.add_parser("remove-peer")
    orr.add_argument(
        "-peer-address", dest="address", default=""
    )
    orr.set_defaults(fn=cmd_operator_raft)
    okg = op_sub.add_parser("keygen")
    okg.set_defaults(fn=cmd_operator_keygen)
    okr = op_sub.add_parser("keyring")
    okr_group = okr.add_mutually_exclusive_group()
    okr_group.add_argument("-install", dest="install", default="")
    okr_group.add_argument("-use", dest="use", default="")
    okr_group.add_argument("-remove", dest="remove", default="")
    okr_group.add_argument(
        "-list", action="store_true", dest="list_keys"
    )
    okr.set_defaults(fn=cmd_keyring)
    odbg = op_sub.add_parser("debug")
    odbg.add_argument("-output", dest="output", default="")
    odbg.set_defaults(fn=cmd_operator_debug)

    devp = sub.add_parser("device")
    devp_sub = devp.add_subparsers(dest="action", required=True)
    dst = devp_sub.add_parser("status")
    _add_fmt(dst)
    dst.set_defaults(fn=cmd_device_status)

    slop = sub.add_parser("slo")
    slop_sub = slop.add_subparsers(dest="action", required=True)
    sst = slop_sub.add_parser("status")
    _add_fmt(sst)
    sst.set_defaults(fn=cmd_slo_status)

    decp = sub.add_parser("decisions")
    decp.add_argument("-site", dest="site", default="")
    decp.add_argument("-outcome", dest="outcome", default="")
    decp.add_argument("-trace", dest="trace", default="")
    decp.add_argument(
        "-limit", dest="limit", type=int, default=32
    )
    _add_fmt(decp)
    decp.set_defaults(fn=cmd_decisions)

    mon = sub.add_parser("monitor")
    mon.add_argument(
        "-no-follow", action="store_false", dest="follow",
        default=True,
    )
    mon.set_defaults(fn=cmd_monitor)

    system = sub.add_parser("system")
    system_sub = system.add_subparsers(dest="action", required=True)
    sg = system_sub.add_parser("gc")
    sg.set_defaults(fn=cmd_system)
    sr = system_sub.add_parser("reconcile")
    sr_sub = sr.add_subparsers(dest="target", required=False)
    srs = sr_sub.add_parser("summaries")
    srs.set_defaults(fn=cmd_system, target="summaries")
    sr.set_defaults(fn=cmd_system, target="summaries")

    lic = sub.add_parser("license")
    lic_sub = lic.add_subparsers(dest="license_cmd", required=True)
    for name in ("get", "put"):
        lp = lic_sub.add_parser(name)
        lp.add_argument("file", nargs="?", default="")
        lp.set_defaults(fn=cmd_license)

    # sentinel/quota: registered like the reference OSS build; the
    # server gates the features to Enterprise (command/commands.go
    # registers them unconditionally)
    sentinel = sub.add_parser("sentinel")
    sentinel_sub = sentinel.add_subparsers(
        dest="sentinel_cmd", required=True
    )
    for name in ("apply", "delete", "list", "read"):
        sn = sentinel_sub.add_parser(name)
        sn.add_argument("args", nargs=argparse.REMAINDER)
        sn.set_defaults(fn=cmd_enterprise_gate, family="sentinel")
    quota = sub.add_parser("quota")
    quota_sub = quota.add_subparsers(
        dest="quota_cmd", required=True
    )
    for name in ("apply", "delete", "init", "inspect", "list",
                 "status"):
        qp = quota_sub.add_parser(name)
        qp.add_argument("args", nargs=argparse.REMAINDER)
        qp.set_defaults(fn=cmd_enterprise_gate, family="quota")

    kg = sub.add_parser("keygen")
    kg.set_defaults(fn=cmd_operator_keygen)
    kr = sub.add_parser("keyring")
    kr_group = kr.add_mutually_exclusive_group()
    kr_group.add_argument("-install", dest="install", default="")
    kr_group.add_argument("-use", dest="use", default="")
    kr_group.add_argument("-remove", dest="remove", default="")
    kr_group.add_argument(
        "-list", action="store_true", dest="list_keys"
    )
    kr.set_defaults(fn=cmd_keyring)

    chk = sub.add_parser("check")
    chk.set_defaults(fn=cmd_check)
    ui = sub.add_parser("ui")
    ui.add_argument("-open", action="store_true", dest="open")
    ui.set_defaults(fn=cmd_ui)
    dbg = sub.add_parser("debug")
    dbg.add_argument("-output", dest="output", default="")
    dbg.set_defaults(fn=cmd_operator_debug)

    # top-level aliases (reference registers e.g. "run" -> job run,
    # "status" -> job status; command/commands.go)
    tr = sub.add_parser("run")
    tr.add_argument("file")
    tr.set_defaults(fn=cmd_job_run)
    tp = sub.add_parser("plan")
    tp.add_argument("file")
    tp.set_defaults(fn=cmd_job_plan)
    tst = sub.add_parser("status")
    tst.add_argument("job_id", nargs="?")
    _add_fmt(tst)
    tst.set_defaults(fn=cmd_status)
    tstop = sub.add_parser("stop")
    tstop.add_argument("-purge", action="store_true", dest="purge")
    tstop.add_argument("job_id")
    tstop.set_defaults(fn=cmd_job_stop)
    tv = sub.add_parser("validate")
    tv.add_argument("file")
    tv.set_defaults(fn=cmd_job_validate)
    ti = sub.add_parser("init")
    ti.add_argument("filename", nargs="?", default="")
    ti.set_defaults(fn=cmd_job_init)
    tl = sub.add_parser("logs")
    tl.add_argument("-stderr", action="store_true", dest="stderr")
    tl.add_argument("-f", action="store_true", dest="follow")
    tl.add_argument("alloc_id")
    tl.add_argument("task")
    tl.set_defaults(fn=cmd_alloc_logs)
    tex = sub.add_parser("exec")
    tex.add_argument("-task", dest="task", default="")
    tex.add_argument("alloc_id")
    tex.add_argument("cmd", nargs=argparse.REMAINDER)
    tex.set_defaults(fn=cmd_alloc_exec)
    tin = sub.add_parser("inspect")
    tin.add_argument("job_id")
    _add_fmt(tin)
    tin.set_defaults(fn=cmd_job_inspect)
    tfs = sub.add_parser("fs")
    tfs.add_argument("-cat", action="store_true", dest="cat")
    tfs.add_argument("alloc_id")
    tfs.add_argument("path", nargs="?", default="")
    tfs.set_defaults(fn=cmd_alloc_fs)

    ai = sub.add_parser("agent-info")
    _add_fmt(ai)
    ai.set_defaults(fn=cmd_agent_info)

    # hyphenated legacy aliases (the reference registers both forms,
    # command/commands.go: "node-status", "server-members", ...)
    # deprecated alias for `node config` (reference commands.go:755
    # registers client-config as the Old form of node config)
    hcc = sub.add_parser("client-config")
    _add_fmt(hcc)
    hcc.add_argument("node_id")
    hcc.set_defaults(fn=cmd_node_config)
    hns = sub.add_parser("node-status")
    hns.add_argument("node_id", nargs="?")
    _add_fmt(hns)
    hns.set_defaults(fn=cmd_node_status)
    hnd = sub.add_parser("node-drain")
    hnd_group = hnd.add_mutually_exclusive_group(required=True)
    hnd_group.add_argument(
        "-enable", action="store_true", dest="enable"
    )
    hnd_group.add_argument(
        "-disable", action="store_false", dest="enable"
    )
    hnd.add_argument(
        "-deadline", type=float, default=3600.0, dest="deadline"
    )
    hnd.add_argument(
        "-monitor", action="store_true", dest="monitor"
    )
    hnd.add_argument("node_id")
    hnd.set_defaults(fn=cmd_node_drain)
    has = sub.add_parser("alloc-status")
    has.add_argument("alloc_id")
    _add_fmt(has)
    has.set_defaults(fn=cmd_alloc_status)
    hes = sub.add_parser("eval-status")
    hes.add_argument("eval_id")
    _add_fmt(hes)
    hes.set_defaults(fn=cmd_eval_status)
    hsj = sub.add_parser("server-join")
    hsj.add_argument("address")
    hsj.set_defaults(fn=cmd_server_join)
    hsm = sub.add_parser("server-members")
    _add_fmt(hsm)
    hsm.set_defaults(fn=cmd_server_members)
    hsfl = sub.add_parser("server-force-leave")
    hsfl.add_argument("name")
    hsfl.set_defaults(fn=cmd_server_force_leave)

    version = sub.add_parser("version")
    version.set_defaults(fn=cmd_version)
    return p


def main(argv=None) -> None:
    parser = build_parser()
    args = parser.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
