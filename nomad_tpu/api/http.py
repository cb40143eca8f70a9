"""HTTP API (reference command/agent/http.go:252-327 route table).

Serves the `/v1/*` surface over the in-process server: jobs (list,
register, read, delete, evaluations, allocations, plan, scale,
periodic force), nodes (list, read, drain, eligibility), allocations,
evaluations, deployments (+promote/fail/pause), operator scheduler
configuration (incl. the TPU-backend toggle), agent info/members, status
leader, search, system gc, and metrics.

ACL enforcement: when the server has ACLs enabled, every request resolves
its X-Nomad-Token header to a policy set and is checked against the
namespace capability the route requires (reference nomad/acl.go).
"""
from __future__ import annotations

import json
import re
import threading
from dataclasses import replace as dc_replace
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional, Tuple
from urllib.parse import parse_qs, urlencode, urlparse

from ..structs import DrainStrategy, SchedulerConfiguration, PreemptionConfig
from ..trace import TRACE
from .codec import (
    alloc_to_dict,
    deployment_to_dict,
    eval_to_dict,
    job_from_dict,
    job_to_dict,
    node_to_dict,
    csi_plugin_to_dict,
    csi_volume_from_dict,
    csi_volume_stub,
    csi_volume_to_dict,
    scaling_event_to_dict,
    scaling_policy_stub,
    scaling_policy_to_dict,
)


# data GET endpoints eligible for ?index= blocking queries
_BLOCKING_PREFIXES = (
    "/v1/jobs",
    "/v1/job/",
    "/v1/nodes",
    "/v1/node/",
    "/v1/allocations",
    "/v1/allocation/",
    "/v1/evaluations",
    "/v1/evaluation/",
    "/v1/deployments",
    "/v1/deployment/",
    "/v1/volumes",
    "/v1/volume/",
    "/v1/catalog/",
)


class HTTPError(Exception):
    def __init__(self, code: int, message: str) -> None:
        super().__init__(message)
        self.code = code


# deadline for a ?region= read proxied to another region's advertised
# HTTP address: a wedged remote region must cost the caller a bounded
# wait, never a pinned thread
FED_PROXY_TIMEOUT_S = 2.0


class APIHandler(BaseHTTPRequestHandler):
    server_ref = None  # class attr set by start_http_server
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # silence default logging
        pass

    # -- plumbing -------------------------------------------------------

    def _consume_body(self) -> None:
        """Drain the request body exactly once, at dispatch entry.

        With HTTP/1.1 keep-alive, a handler that responds without
        reading its request body leaves those bytes in the stream —
        the NEXT request parse then reads ``{}`` as a request line
        and answers 501, poisoning every other request on a
        persistent connection (found by the swarm harness, whose
        generators hold one connection per worker; urllib-based
        tests reconnect per request and never hit it).  Draining up
        front also lets the overload shed path answer 429 without
        the connection-corruption tax."""
        length = int(self.headers.get("Content-Length") or 0)
        self._raw_body = self.rfile.read(length) if length > 0 else b""

    def _body(self) -> Dict:
        raw = getattr(self, "_raw_body", b"")
        if not raw:
            return {}
        try:
            return json.loads(raw)
        except ValueError as exc:
            raise HTTPError(400, f"invalid JSON body: {exc}")

    def _respond(self, payload: Any, code: int = 200) -> None:
        data = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        index = getattr(self, "_reply_index", None)
        if index is not None:
            self.send_header("X-Nomad-Index", str(index))
        self.end_headers()
        self.wfile.write(data)

    def _error(self, code: int, message: str) -> None:
        self._respond({"error": message}, code)

    def _stream_chunked(
        self, frames, content_type: str = "application/octet-stream"
    ) -> None:
        """HTTP/1.1 chunked streaming: one chunk per yielded bytes
        value, until the generator ends or the consumer disconnects
        (the streaming-transport analog of the reference's yamux
        frames for logs -f / agent monitor)."""
        import select as _select
        import socket as _socket

        self.send_response(200)
        self.send_header("Content-Type", content_type)
        self.send_header("Transfer-Encoding", "chunked")
        self.send_header("X-Nomad-Stream", "chunked")
        self.end_headers()
        try:
            for data in frames:
                if not data:
                    # idle tick: a consumer that hung up must not pin
                    # this thread for the stream's max lifetime — a
                    # readable socket that yields no bytes is EOF
                    r, _w, _x = _select.select(
                        [self.connection], [], [], 0
                    )
                    if r:
                        try:
                            peek = self.connection.recv(
                                1, _socket.MSG_PEEK
                            )
                        except OSError:
                            return
                        if not peek:
                            return
                    continue
                self.wfile.write(
                    f"{len(data):x}\r\n".encode() + data + b"\r\n"
                )
                self.wfile.flush()
            self.wfile.write(b"0\r\n\r\n")
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass
        finally:
            self.close_connection = True

    def _serve_exec_websocket(self, handle) -> None:
        """Bridge an ExecStreamHandle onto the upgraded connection:
        inbound frames carry stdin/tty_size, outbound frames carry
        stdout/stderr and the final exited/result."""
        import base64 as _b64
        import queue as _queue
        import threading as _threading

        from . import ws as _ws

        if not _ws.server_handshake(self):
            raise HTTPError(400, "websocket handshake failed")
        self.close_connection = True
        sock = self.connection
        done = _threading.Event()
        # one writer at a time: the reader thread answers PINGs on
        # the same socket the output pump writes to — interleaved
        # sendalls would corrupt the frame stream
        send_lock = _threading.Lock()

        def send(op, payload) -> None:
            with send_lock:
                _ws.write_frame(sock, op, payload)

        def reader() -> None:
            try:
                while not done.is_set():
                    frame = _ws.read_frame(self.rfile)
                    op, payload = frame
                    if op == _ws.OP_CLOSE:
                        handle.terminate()
                        return
                    if op == _ws.OP_PING:
                        send(_ws.OP_PONG, payload)
                        continue
                    try:
                        msg = json.loads(payload.decode("utf-8"))
                    except ValueError:
                        continue
                    stdin = msg.get("stdin") or {}
                    if stdin.get("data"):
                        handle.write_stdin(
                            _b64.b64decode(stdin["data"])
                        )
                    if stdin.get("close"):
                        handle.close_stdin()
                    tty = msg.get("tty_size") or {}
                    if tty:
                        handle.resize(
                            int(tty.get("height", 0)),
                            int(tty.get("width", 0)),
                        )
            except (ConnectionError, OSError, ValueError):
                handle.terminate()

        _threading.Thread(target=reader, daemon=True).start()
        try:
            while True:
                try:
                    event = handle.read_event(timeout=0.25)
                except _queue.Empty:
                    continue
                if event is None:
                    break
                stream, data = event
                send(
                    _ws.OP_TEXT,
                    json.dumps(
                        {
                            stream: {
                                "data": _b64.b64encode(
                                    data
                                ).decode("ascii")
                            }
                        }
                    ).encode("utf-8"),
                )
            try:
                code = handle.wait(timeout=10)
            except Exception:  # noqa: BLE001 — report, don't hang
                handle.terminate()
                code = -1
            send(
                _ws.OP_TEXT,
                json.dumps(
                    {
                        "exited": True,
                        "result": {"exit_code": code},
                    }
                ).encode("utf-8"),
            )
            send(_ws.OP_CLOSE, b"")
        except (ConnectionError, OSError):
            handle.terminate()
        finally:
            done.set()

    def _check_acl(self, capability: str, namespace: str = "default"):
        self._check_acl_any((capability,), namespace)

    def _check_acl_any(self, capabilities, namespace: str = "default"):
        """Pass if the token holds ANY of the capabilities (reference
        endpoints often accept e.g. scale-job OR submit-job)."""
        srv = self.server_ref
        acls = getattr(srv, "acls", None)
        if acls is None or not acls.enabled:
            return
        token = self.headers.get("X-Nomad-Token", "")
        if not any(
            acls.allowed(token, namespace, c) for c in capabilities
        ):
            raise HTTPError(403, "Permission denied")

    @staticmethod
    def _cluster_obs(
        srv, what: str, params: dict, region: Optional[str] = None
    ) -> dict:
        """Cluster observability fan-in when the server is
        cluster-capable; a single-process Server answers with its
        local share in the same merged shape.  The fan-in is
        region-local by construction — an explicit ``region``
        (the ?region= escape hatch) forwards the whole query to that
        region's leader and counts a federation.wan_reads."""
        regional = getattr(srv, "cluster_query_region", None)
        if regional is not None:
            return regional(what, params, region=region)
        query = getattr(srv, "cluster_query", None)
        if query is not None:
            return query(what, params)
        return {
            "servers": {"local": srv._obs_local(what, params)},
            "asked": 1,
            "unreachable": 0,
        }

    # -- dispatch -------------------------------------------------------

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_PUT(self):
        self._dispatch("PUT")

    def do_DELETE(self):
        self._dispatch("DELETE")

    def _shed(self, retry_after_s: float, mode: int) -> None:
        """429 + Retry-After: the backpressure half of the overload
        ladder.  Clients (the CLI, the swarm harness, any
        well-behaved SDK) back off for Retry-After seconds and retry
        — bounded sheds absorb the overload instead of an unbounded
        broker backlog absorbing the p99.

        On a federated server, the shed also names the nearest
        healthy OTHER region (X-Nomad-Retry-Region, with one of its
        advertised HTTP addresses) derived from gossip health — a
        redirect-aware client moves its traffic to the next region
        instead of hammering this dying one."""
        from ..server.overload import MODE_NAMES

        body = {
            "error": "server overloaded",
            "Mode": MODE_NAMES[mode],
            "RetryAfter": retry_after_s,
        }
        hint = None
        fed = getattr(self.server_ref, "federation", None)
        if fed is not None:
            try:
                hint = fed.nearest_healthy_region()
            except Exception:  # noqa: BLE001 — hint is best-effort
                hint = None
        if hint is not None:
            region, http_addr = hint
            body["RetryRegion"] = region
            body["RetryRegionAddr"] = http_addr
            metrics = getattr(self.server_ref, "metrics", None)
            if metrics is not None:
                metrics.incr("federation.shed_redirects")
        data = json.dumps(body).encode()
        self.send_response(429)
        self.send_header("Content-Type", "application/json")
        self.send_header(
            "Retry-After", str(max(1, int(round(retry_after_s))))
        )
        if hint is not None:
            self.send_header("X-Nomad-Retry-Region", hint[0])
            if hint[1]:
                self.send_header(
                    "X-Nomad-Retry-Region-Addr", hint[1]
                )
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _dispatch(self, method: str) -> None:
        url = urlparse(self.path)
        path = url.path.rstrip("/")
        query = {k: v[0] for k, v in parse_qs(url.query).items()}
        try:
            self._consume_body()
            # ingress backpressure (server/overload.py): admission by
            # priority class BEFORE any state read or body parse —
            # heartbeats > plan/blocking queries > job submissions.
            # Shed requests cost the server one classify + one
            # counter, which is the whole point.
            ctl = getattr(self.server_ref, "overload", None)
            if ctl is not None:
                from ..server.overload import classify_request

                admitted, retry_after = ctl.admit(
                    classify_request(method, path)
                )
                if not admitted:
                    self._shed(retry_after, ctl.mode)
                    return
            # the ?region= escape hatch: reads stay region-local by
            # default; an EXPLICIT foreign region proxies the GET to
            # that region's advertised HTTP address and counts a
            # federation.wan_reads.  /v1/cluster/* keeps its own
            # transport-level forward (works without remote HTTP
            # listeners), so it is excluded here.
            region = query.get("region")
            srv = self.server_ref
            if (
                method == "GET"
                and region
                and region != getattr(srv, "region", region)
                and getattr(srv, "federation", None) is not None
                and not path.startswith("/v1/cluster")
            ):
                self._proxy_region(region, path, query)
                return
            # blocking queries (reference rpc.go:780 blockingRPC): a GET
            # with ?index=N long-polls until the state advances past N
            # (or the wait expires), then responds with fresh data; the
            # X-Nomad-Index response header feeds the next poll.
            # Restricted to known data endpoints, and — with ACLs on —
            # to requests whose token resolves, so unauthenticated or
            # bogus requests can't pin server threads for the wait.
            if (
                method == "GET"
                and "index" in query
                and path.startswith(_BLOCKING_PREFIXES)
            ):
                acls = getattr(self.server_ref, "acls", None)
                authed = not (acls is not None and acls.enabled) or (
                    acls.resolve(
                        self.headers.get("X-Nomad-Token", "")
                    )
                    is not None
                )
                try:
                    min_index = int(query["index"]) + 1
                    wait_s = min(
                        float(query.get("wait", "5")), 60.0
                    )
                except ValueError:
                    raise HTTPError(400, "bad index/wait")
                if authed and ctl is not None:
                    # degradation rung between "served" and "shed":
                    # at SHEDDING+, long-polls answer immediately
                    # (current state, X-Nomad-Index intact) instead
                    # of pinning a server thread for the wait
                    wait_s = ctl.blocking_wait_budget(wait_s)
                if authed and wait_s > 0:
                    self.server_ref.store.wait_for_index(
                        min_index, timeout=wait_s
                    )
            # capture the reply index BEFORE the handler reads state:
            # a concurrent write between read and respond must re-wake
            # the next poll rather than be skipped past
            try:
                self._reply_index = (
                    self.server_ref.store.latest_index()
                )
            except Exception:  # noqa: BLE001
                self._reply_index = None
            handled = self._route(method, path, query)
            if not handled:
                self._error(404, f"no handler for {method} {path}")
        except HTTPError as exc:
            self._error(exc.code, str(exc))
        except (KeyError, ValueError) as exc:
            self._error(400, str(exc))
        except Exception as exc:  # noqa: BLE001
            self._error(500, f"{type(exc).__name__}: {exc}")

    def _proxy_region(
        self, region: str, path: str, query: Dict[str, str]
    ) -> None:
        """Forward one GET to ``region``'s advertised HTTP address
        (learned through WAN gossip) and relay the answer verbatim —
        the explicit WAN read the federation.wan_reads counter
        accounts for."""
        import urllib.error
        import urllib.request

        srv = self.server_ref
        target = srv.federation.http_addr_in(region)
        if target is None:
            raise HTTPError(
                502, f"no HTTP address known in region {region!r}"
            )
        metrics = getattr(srv, "metrics", None)
        if metrics is not None:
            metrics.incr("federation.wan_reads")
        qs = urlencode(
            {k: v for k, v in query.items() if k != "region"}
        )
        url = f"http://{target}{path}" + (f"?{qs}" if qs else "")
        req = urllib.request.Request(url, method="GET")
        token = self.headers.get("X-Nomad-Token")
        if token:
            req.add_header("X-Nomad-Token", token)
        try:
            with urllib.request.urlopen(
                req, timeout=FED_PROXY_TIMEOUT_S
            ) as resp:
                code = resp.status
                ctype = resp.headers.get(
                    "Content-Type", "application/json"
                )
                data = resp.read()
        except urllib.error.HTTPError as exc:
            code = exc.code
            ctype = exc.headers.get(
                "Content-Type", "application/json"
            )
            data = exc.read()
        except (OSError, urllib.error.URLError) as exc:
            raise HTTPError(
                502, f"region {region!r} proxy failed: {exc}"
            )
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("X-Nomad-Proxied-Region", region)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    # -- routes (reference http.go registerHandlers) --------------------

    def _route(self, method: str, path: str, q: Dict[str, str]) -> bool:
        srv = self.server_ref
        store = srv.store
        ns = q.get("namespace", "default")

        if path in ("/ui", "/ui/index.html", "") and method == "GET":
            # built-in single-page UI (the reference ships an Ember
            # app under ui/; same /v1 data)
            from .ui import UI_HTML

            body = UI_HTML.encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/html; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return True

        if path == "/v1/jobs":
            if method == "GET":
                self._check_acl("read-job", ns)
                prefix = q.get("prefix", "")
                jobs = [
                    {
                        "ID": j.id,
                        "Name": j.name,
                        "Type": j.type,
                        "Priority": j.priority,
                        "Status": store.derive_job_status(j.namespace, j.id),
                        "Namespace": j.namespace,
                    }
                    for j in store.iter_jobs()
                    if j.id.startswith(prefix)
                ]
                self._respond(jobs)
                return True
            if method in ("POST", "PUT"):
                self._check_acl("submit-job", ns)
                body = self._body()
                # flight recorder: the eval's trace begins here, where
                # the request is parsed (`ingress.register` ends where
                # the broker takes the eval)
                with TRACE.ingress():
                    raw_job = body.get("Job") or body.get("job") or body
                    job = job_from_dict(raw_job)
                    ev = srv.register_job(job)
                self._respond(
                    {"EvalID": ev.id if ev else "", "JobModifyIndex": job.modify_index}
                )
                return True

        m = re.fullmatch(r"/v1/job/([^/]+)", path)
        if m:
            job_id = m.group(1)
            if method == "GET":
                self._check_acl("read-job", ns)
                job = store.job_by_id(ns, job_id)
                if job is None:
                    raise HTTPError(404, "job not found")
                d = job_to_dict(job)
                d["status"] = store.derive_job_status(ns, job_id)
                self._respond(d)
                return True
            if method in ("POST", "PUT"):
                self._check_acl("submit-job", ns)
                body = self._body()
                with TRACE.ingress():
                    raw_job = body.get("Job") or body.get("job") or body
                    job = job_from_dict(raw_job)
                    job.id = job_id
                    ev = srv.register_job(job)
                self._respond({"EvalID": ev.id if ev else ""})
                return True
            if method == "DELETE":
                self._check_acl("submit-job", ns)
                purge = q.get("purge", "false") == "true"
                ev = srv.deregister_job(ns, job_id, purge=purge)
                self._respond({"EvalID": ev.id if ev else ""})
                return True

        if path == "/v1/jobs/parse" and method in ("POST", "PUT"):
            # HCL -> canonical JSON job (reference jobs_endpoint.go
            # /v1/jobs/parse)
            self._check_acl("submit-job", ns)
            from ..jobspec import ParseError, parse as parse_hcl

            body = self._body()
            try:
                job = parse_hcl(body.get("JobHCL", ""))
            except ParseError as exc:
                raise HTTPError(400, str(exc))
            self._respond(job_to_dict(job))
            return True

        if path == "/v1/validate/job" and method in ("POST", "PUT"):
            self._check_acl("submit-job", ns)
            body = self._body()
            raw_job = body.get("Job") or body.get("job") or body
            try:
                job = job_from_dict(raw_job)
                srv.validate_job(job)
            except (ValueError, KeyError) as exc:
                self._respond(
                    {
                        "Error": str(exc),
                        "ValidationErrors": [str(exc)],
                        "Warnings": "",
                    }
                )
                return True
            self._respond({"ValidationErrors": [], "Warnings": ""})
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/versions", path)
        if m and method == "GET":
            self._check_acl("read-job", ns)
            versions = store.versions_of_job(ns, m.group(1))
            if not versions:
                raise HTTPError(404, "job not found")
            self._respond(
                {
                    "Versions": [job_to_dict(j) for j in versions],
                    "Diffs": [],
                }
            )
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/revert", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("submit-job", ns)
            body = self._body()
            try:
                ev = srv.revert_job(
                    ns,
                    m.group(1),
                    int(body.get("JobVersion", 0)),
                    enforce_prior_version=body.get(
                        "EnforcePriorVersion"
                    ),
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            except ValueError as exc:
                raise HTTPError(400, str(exc))
            self._respond({"EvalID": ev.id if ev else ""})
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/stable", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("submit-job", ns)
            body = self._body()
            try:
                srv.set_job_stability(
                    ns,
                    m.group(1),
                    int(body.get("JobVersion", 0)),
                    bool(body.get("Stable", True)),
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._respond({"Index": store.latest_index()})
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/summary", path)
        if m and method == "GET":
            self._check_acl("read-job", ns)
            try:
                self._respond(srv.job_summary(ns, m.group(1)))
            except KeyError:
                raise HTTPError(404, "job not found")
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/federation", path)
        if m and method == "GET":
            # per-region registration/placement status of a federated
            # job: the local region answers from local state, every
            # other region in the job's Multiregion block is asked
            # live over region_call
            self._check_acl("read-job", ns)
            fed = getattr(srv, "federation", None)
            if fed is None:
                raise HTTPError(
                    400, "server is not federation-capable"
                )
            try:
                self._respond(fed.federation_status(ns, m.group(1)))
            except KeyError:
                raise HTTPError(404, "job not found")
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/evaluations", path)
        if m and method == "GET":
            self._check_acl("read-job", ns)
            self._respond(
                [eval_to_dict(e) for e in store.evals_by_job(ns, m.group(1))]
            )
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/allocations", path)
        if m and method == "GET":
            self._check_acl("read-job", ns)
            self._respond(
                [
                    alloc_to_dict(a)
                    for a in store.allocs_by_job(ns, m.group(1))
                ]
            )
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/deployments", path)
        if m and method == "GET":
            self._check_acl("read-job", ns)
            self._respond(
                [
                    deployment_to_dict(d)
                    for d in store.deployments_by_job(ns, m.group(1))
                ]
            )
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/plan", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("submit-job", ns)
            body = self._body()
            raw_job = body.get("Job") or body.get("job") or body
            job = job_from_dict(raw_job)
            job.id = m.group(1)
            self._respond(
                srv.plan_job(job, diff=body.get("Diff", True))
            )
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/dispatch", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("dispatch-job", ns)
            body = self._body()
            # Payload arrives base64-encoded (api.Job Payload contract)
            import base64

            # tolerate line-wrapped base64 (Go's decoder skips \r\n)
            raw_payload = "".join((body.get("Payload") or "").split())
            try:
                payload = (
                    base64.b64decode(raw_payload, validate=True) or None
                )
            except (ValueError, TypeError):
                raise HTTPError(400, "Payload must be base64")
            child = srv.dispatch_job(
                ns,
                m.group(1),
                meta=body.get("Meta") or body.get("meta"),
                payload=payload,
            )
            self._respond({"DispatchedJobID": child.id})
            return True

        m = re.fullmatch(r"/v1/client/fs/logs/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("read-logs", ns)
            task = q.get("task", "")
            kind = q.get("type", "stdout")
            if q.get("follow") == "true":
                # chunked live tail (reference client fs streaming
                # for `alloc logs -f`); raw bytes, ends when the
                # consumer disconnects.  Validate BEFORE the 200 —
                # a typo'd alloc must 404, not stream emptiness
                alloc_id = m.group(1)
                try:
                    first, cursor0 = srv.tail_task_log(
                        alloc_id, task, kind, None
                    )
                except KeyError as exc:
                    raise HTTPError(404, str(exc))

                def frames():
                    import time as _time

                    cursor = cursor0
                    if first:
                        yield first
                    idle = 0.0
                    max_idle = float(q.get("max_idle", "3600"))
                    while idle < max_idle:
                        try:
                            data, cursor = srv.tail_task_log(
                                alloc_id, task, kind, cursor
                            )
                        except KeyError:
                            return
                        if data:
                            idle = 0.0
                            yield data
                        else:
                            idle += 0.25
                            _time.sleep(0.25)
                            yield b""  # liveness probe tick

                self._stream_chunked(
                    frames(), "application/octet-stream"
                )
                return True
            try:
                data = srv.read_task_log(m.group(1), task, kind)
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._respond({"Data": data.decode("utf-8", "replace")})
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/evaluate", path)
        if m and method in ("POST", "PUT"):
            # force a fresh evaluation (reference nomad/job_endpoint.go
            # Job.Evaluate; command/job_eval.go)
            self._check_acl("submit-job", ns)
            job = store.job_by_id(ns, m.group(1))
            if job is None:
                raise HTTPError(404, "job not found")
            if job.is_periodic() or job.is_parameterized():
                # templates never evaluate directly (reference
                # job_endpoint.go Evaluate rejects both)
                raise HTTPError(
                    400,
                    "can't evaluate periodic/parameterized job",
                )
            from ..structs import Evaluation

            ev = Evaluation(
                namespace=job.namespace,
                priority=job.priority,
                type=job.type,
                triggered_by="job-eval",
                job_id=job.id,
                status="pending",
            )
            store.upsert_evals([ev])
            srv.on_eval_update(ev)
            self._respond({"EvalID": ev.id})
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/periodic/force", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("submit-job", ns)
            job = store.job_by_id(ns, m.group(1))
            if job is None or not job.is_periodic():
                raise HTTPError(404, "periodic job not found")
            child = srv.periodic.force_launch(job)
            self._respond({"JobID": child.id})
            return True

        m = re.fullmatch(r"/v1/job/([^/]+)/scale", path)
        if m and method in ("POST", "PUT"):
            # reference nomad/job_endpoint.go Job.Scale; count=None is
            # the autoscaler status-report path (event only)
            self._check_acl_any(("scale-job", "submit-job"), ns)
            body = self._body()
            target = body.get("Target", {}) or {}
            group = target.get("Group") or body.get("group")
            count = body.get("Count", body.get("count"))
            try:
                ev, _event = srv.scale_job(
                    ns,
                    m.group(1),
                    group,
                    count=count,
                    message=body.get("Message", ""),
                    error=bool(body.get("Error", False)),
                    meta=body.get("Meta") or {},
                    policy_override=bool(body.get("PolicyOverride", False)),
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._respond({"EvalID": ev.id if ev else ""})
            return True

        if m and method == "GET":
            # JobScaleStatusResponse (reference job_endpoint.go
            # ScaleStatus): per-group desired/placed/running counts +
            # retained scaling events
            self._check_acl_any(("read-job-scaling", "read-job"), ns)
            job = store.job_by_id(ns, m.group(1))
            if job is None:
                raise HTTPError(404, "job not found")
            events = store.scaling_events_for_job(ns, job.id)
            live_by_group: Dict[str, list] = {}
            for a in store.allocs_by_job(ns, job.id):
                if not a.terminal_status():
                    live_by_group.setdefault(a.task_group, []).append(a)
            groups = {}
            for tg in job.task_groups:
                allocs = live_by_group.get(tg.name, [])
                groups[tg.name] = {
                    "Desired": tg.count,
                    "Placed": len(allocs),
                    "Running": sum(
                        1 for a in allocs if a.client_status == "running"
                    ),
                    "Events": [
                        scaling_event_to_dict(e)
                        for e in events.get(tg.name, [])
                    ],
                }
            self._respond(
                {
                    "JobID": job.id,
                    "Namespace": job.namespace,
                    "JobStopped": job.stop,
                    "TaskGroups": groups,
                }
            )
            return True

        if path == "/v1/scaling/policies" and method == "GET":
            # listing is scoped to the ACL-checked namespace; no
            # cross-namespace enumeration
            self._check_acl("list-scaling-policies", ns)
            pols = store.iter_scaling_policies(
                namespace=ns, job_id=q.get("job")
            )
            self._respond(
                [scaling_policy_stub(p) for p in pols]
            )
            return True

        m = re.fullmatch(r"/v1/scaling/policy/([^/]+)", path)
        if m and method == "GET":
            pol = store.scaling_policy_by_id(m.group(1))
            if pol is None:
                raise HTTPError(404, "scaling policy not found")
            # authorize against the namespace the policy lives in
            self._check_acl(
                "read-scaling-policy", pol.target_tuple()[0] or ns
            )
            self._respond(scaling_policy_to_dict(pol))
            return True

        if path == "/v1/nodes" and method == "GET":
            self._check_acl("node:read")
            prefix = q.get("prefix", "")
            self._respond(
                [
                    {
                        "ID": n.id,
                        "Name": n.name,
                        "Datacenter": n.datacenter,
                        "Status": n.status,
                        "SchedulingEligibility": n.scheduling_eligibility,
                        "Drain": n.drain,
                    }
                    for n in store.iter_nodes()
                    if n.id.startswith(prefix)
                ]
            )
            return True

        m = re.fullmatch(r"/v1/node/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("node:read")
            node = store.node_by_id(m.group(1))
            if node is None:
                raise HTTPError(404, "node not found")
            self._respond(node_to_dict(node))
            return True

        m = re.fullmatch(r"/v1/node/([^/]+)/allocations", path)
        if m and method == "GET":
            self._check_acl("node:read")
            self._respond(
                [alloc_to_dict(a) for a in store.allocs_by_node(m.group(1))]
            )
            return True

        if path == "/v1/node/register" and method in ("POST", "PUT"):
            # remote node registration (reference Node.Register RPC;
            # lets client agents attach to a networked cluster over
            # the HTTP surface — forwarding routes it to the leader)
            self._check_acl("node:write")
            from .codec import node_from_dict

            node = node_from_dict(
                self._body().get("Node") or self._body()
            )
            if not node.id:
                raise HTTPError(400, "missing node id")
            srv.register_node(node)
            self._respond(
                {"HeartbeatTTL": getattr(srv, "heartbeat_ttl", 0)}
            )
            return True

        if path == "/v1/client/register" and method in (
            "POST", "PUT",
        ):
            # a REMOTE client announces its callback endpoint; the
            # server proxies fs/exec/logs for its allocs through it
            # (reference nomad/client_rpc.go NodeRpc topology)
            self._check_acl("node:write")
            body = self._body()
            node_id = body.get("NodeID") or body.get("node_id", "")
            addr = body.get("Addr") or body.get("addr", "")
            if not node_id or not addr:
                raise HTTPError(400, "NodeID and Addr required")
            from ..client.remote import HTTPClientProxy

            srv.register_client(node_id, HTTPClientProxy(addr))
            self._respond({})
            return True

        m = re.fullmatch(r"/v1/node/([^/]+)/heartbeat", path)
        if m and method in ("POST", "PUT"):
            # (reference Node.UpdateStatus keepalive)
            self._check_acl("node:write")
            try:
                srv.heartbeat(m.group(1))
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._respond({})
            return True

        m = re.fullmatch(r"/v1/node/([^/]+)/allocs", path)
        if m and method in ("POST", "PUT"):
            # client pushes alloc status transitions (reference
            # Node.UpdateAlloc)
            self._check_acl("node:write")
            body = self._body()
            updates = []
            for raw in body.get("Allocs") or []:
                if "task_states" in raw or (
                    "allocated_resources" in raw
                ):
                    # full wire-form update from a remote client.
                    # Merge ONLY the client-owned fields onto the
                    # server's canonical alloc: the client's copy of
                    # desired_status/desired_transition/deployment_id
                    # is stale by construction (a drain/preempt/stop
                    # staged since its last pull must not be
                    # reverted by a task-state push) — reference
                    # Node.UpdateAlloc persists client state, never
                    # scheduler intent
                    from .codec import alloc_from_dict

                    full = alloc_from_dict(raw)
                    existing = store.alloc_by_id(full.id)
                    if existing is None:
                        continue
                    updates.append(
                        dc_replace(
                            existing,
                            client_status=full.client_status,
                            client_description=(
                                full.client_description
                            ),
                            task_states=full.task_states,
                            deployment_status=(
                                full.deployment_status
                            ),
                            modify_time=full.modify_time,
                        )
                    )
                    continue
                alloc = store.alloc_by_id(
                    raw.get("ID") or raw.get("id", "")
                )
                if alloc is None:
                    continue
                status = raw.get("ClientStatus") or raw.get(
                    "client_status"
                )
                # Never mutate the store's canonical object: the upsert
                # computes was_live from the *existing* entry, so an
                # in-place status write would make a live->terminal
                # transition invisible (node usage keeps counting the
                # dead alloc). Send a copy carrying the new status.
                if status:
                    alloc = dc_replace(alloc, client_status=status)
                updates.append(alloc)
            if updates:
                srv.update_allocs_from_client(updates)
            self._respond({"Updated": len(updates)})
            return True

        m = re.fullmatch(r"/v1/node/([^/]+)/drain", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("node:write")
            body = self._body()
            enable = bool(
                body.get("DrainSpec") or body.get("drain", False)
            )
            strategy = None
            if enable:
                import time as _t

                spec = body.get("DrainSpec") or {}
                deadline_s = float(
                    spec.get("Deadline", 3600e9) / 1e9
                    if spec.get("Deadline")
                    else 3600.0
                )
                strategy = DrainStrategy(
                    ignore_system_jobs=bool(
                        spec.get("IgnoreSystemJobs", False)
                    ),
                    force_deadline_unix=_t.time() + deadline_s,
                )
            srv.update_node_drain(m.group(1), enable, strategy)
            self._respond({})
            return True

        m = re.fullmatch(r"/v1/node/([^/]+)/eligibility", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("node:write")
            body = self._body()
            elig = body.get("Eligibility") or body.get("eligibility")
            srv.update_node_eligibility(m.group(1), elig)
            self._respond({})
            return True

        if path == "/v1/allocations" and method == "GET":
            self._check_acl("read-job", ns)
            prefix = q.get("prefix", "")
            self._respond(
                [
                    alloc_to_dict(a)
                    for a in store.allocs.values()
                    if a.id.startswith(prefix)
                ]
            )
            return True

        m = re.fullmatch(r"/v1/allocation/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("read-job", ns)
            alloc = store.alloc_by_id(m.group(1))
            if alloc is None:
                raise HTTPError(404, "alloc not found")
            self._respond(alloc_to_dict(alloc))
            return True

        m = re.fullmatch(r"/v1/allocation/([^/]+)/stop", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("submit-job", ns)
            try:
                ev = srv.stop_alloc(m.group(1))
            except KeyError:
                raise HTTPError(404, "alloc not found")
            self._respond({"EvalID": ev.id if ev else ""})
            return True

        m = re.fullmatch(
            r"/v1/client/allocation/([^/]+)/restart", path
        )
        if m and method in ("POST", "PUT"):
            self._check_acl("alloc-lifecycle", ns)
            body = self._body()
            try:
                srv.restart_alloc(
                    m.group(1), body.get("TaskName", "")
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._respond({})
            return True

        m = re.fullmatch(r"/v1/client/allocation/([^/]+)/exec", path)
        if (
            m
            and method == "GET"
            and "websocket"
            in self.headers.get("Upgrade", "").lower()
        ):
            # interactive exec over a websocket (reference
            # command/alloc_exec.go + api/allocations_exec.go frame
            # shapes: stdin/stdout/stderr data b64, tty_size, exited)
            self._check_acl("alloc-exec", ns)
            task = q.get("task", "")
            try:
                argv = json.loads(q.get("command", "[]"))
            except ValueError:
                raise HTTPError(400, "bad command encoding")
            if not argv:
                raise HTTPError(400, "missing command")
            try:
                handle = srv.exec_alloc_stream(
                    m.group(1), task, argv
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._serve_exec_websocket(handle)
            return True

        if m and method in ("POST", "PUT"):
            # one-shot exec in the task context (reference
            # command/alloc_exec.go; the reference streams over a
            # websocket, this returns the collected output)
            self._check_acl("alloc-exec", ns)
            body = self._body()
            argv = body.get("Cmd") or body.get("Command") or []
            if isinstance(argv, str):
                argv = [argv]
            if not argv:
                raise HTTPError(400, "missing command")
            try:
                code, output = srv.exec_alloc(
                    m.group(1),
                    body.get("Task", body.get("TaskName", "")),
                    argv,
                    timeout=float(body.get("Timeout", 30.0)),
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._respond(
                {
                    "ExitCode": code,
                    "Output": output.decode("utf-8", "replace"),
                }
            )
            return True

        m = re.fullmatch(r"/v1/client/fs/ls/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("read-fs", ns)
            try:
                self._respond(
                    srv.list_alloc_files(
                        m.group(1), q.get("path", "")
                    )
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            return True

        m = re.fullmatch(r"/v1/client/fs/cat/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("read-fs", ns)
            try:
                data, truncated = srv.read_alloc_file(
                    m.group(1), q.get("path", "")
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._respond(
                {
                    "Data": data.decode("utf-8", "replace"),
                    "Truncated": truncated,
                }
            )
            return True

        m = re.fullmatch(r"/v1/node/([^/]+)/purge", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("node:write")
            try:
                evals = srv.purge_node(m.group(1))
            except KeyError:
                raise HTTPError(404, "node not found")
            self._respond(
                {"EvalIDs": [e.id for e in evals]}
            )
            return True

        m = re.fullmatch(
            r"/v1/client/allocation/([^/]+)/signal", path
        )
        if m and method in ("POST", "PUT"):
            self._check_acl("alloc-lifecycle", ns)
            body = self._body()
            try:
                srv.signal_alloc(
                    m.group(1),
                    body.get("Signal", "SIGTERM"),
                    body.get("TaskName", body.get("Task", "")),
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            except ValueError as exc:
                raise HTTPError(400, str(exc))
            self._respond({})
            return True

        if path == "/v1/evaluations" and method == "GET":
            self._check_acl("read-job", ns)
            self._respond(
                [eval_to_dict(e) for e in store.evals.values()]
            )
            return True

        # placement explainability: the eval's retained per-TG score
        # decomposition + filter attribution from the explain ring
        # (cross-linked with /v1/traces/<eval_id>)
        m = re.fullmatch(r"/v1/evaluation/([^/]+)/placement", path)
        if m and method == "GET":
            self._check_acl("read-job", ns)
            from ..explain import EXPLAIN

            record = EXPLAIN.get(m.group(1))
            if record is None and hasattr(srv, "cluster_query"):
                # follower-planned eval: the explain record lives on
                # whichever server ran the scheduler — fan the lookup
                # out so the operator never has to know which one
                merged = self._cluster_obs(
                    srv, "explain", {"eval_id": m.group(1)}
                )
                for addr, result in merged["servers"].items():
                    if result.get("unreachable"):
                        continue
                    found = result.get("explain")
                    if found is not None:
                        record = dict(found)
                        record["served_by"] = addr
                        break
            if record is None:
                raise HTTPError(404, "no placement explanation retained")
            self._respond(record)
            return True

        if path == "/v1/placements" and method == "GET":
            # recent placement explanations (newest first) — the
            # operator debug bundle's capture surface
            self._check_acl("read-job", ns)
            from ..explain import EXPLAIN

            try:
                limit = int(q.get("limit", "64"))
            except ValueError:
                raise HTTPError(400, "bad limit")
            self._respond(EXPLAIN.recent(limit=min(limit, 1024)))
            return True

        m = re.fullmatch(r"/v1/evaluation/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("read-job", ns)
            ev = store.eval_by_id(m.group(1))
            if ev is None:
                raise HTTPError(404, "eval not found")
            payload = eval_to_dict(ev)
            if ev.failed_tg_allocs:
                # mirror the plan API's full Nomad shape (snake_case
                # struct fields stay for existing consumers)
                from ..explain import alloc_metric_to_api

                payload["FailedTGAllocs"] = {
                    tg: alloc_metric_to_api(metric)
                    for tg, metric in ev.failed_tg_allocs.items()
                }
            self._respond(payload)
            return True

        if path == "/v1/deployments" and method == "GET":
            self._check_acl("read-job", ns)
            self._respond(
                [deployment_to_dict(d) for d in store.deployments.values()]
            )
            return True

        m = re.fullmatch(r"/v1/deployment/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("read-job", ns)
            d = store.deployment_by_id(m.group(1))
            if d is None:
                raise HTTPError(404, "deployment not found")
            self._respond(deployment_to_dict(d))
            return True

        m = re.fullmatch(r"/v1/deployment/promote/([^/]+)", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("submit-job", ns)
            srv.deployment_watcher.promote(m.group(1))
            self._respond({})
            return True

        m = re.fullmatch(r"/v1/deployment/fail/([^/]+)", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("submit-job", ns)
            srv.deployment_watcher.fail(m.group(1))
            self._respond({})
            return True

        m = re.fullmatch(r"/v1/deployment/pause/([^/]+)", path)
        if m and method in ("POST", "PUT"):
            self._check_acl("submit-job", ns)
            body = self._body()
            srv.deployment_watcher.pause(
                m.group(1), bool(body.get("Pause", True))
            )
            self._respond({})
            return True

        # -- CSI volumes (reference command/agent/csi_endpoint.go) -----

        if path == "/v1/volumes" and method == "GET":
            self._check_acl("csi-list-volume", ns)
            vols = store.iter_csi_volumes(namespace=ns)
            self._respond([csi_volume_stub(v) for v in vols])
            return True

        m = re.fullmatch(r"/v1/volume/csi/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("csi-read-volume", ns)
            vol = store.csi_volume_by_id(ns, m.group(1))
            if vol is None:
                raise HTTPError(404, "volume not found")
            self._respond(csi_volume_to_dict(vol))
            return True

        if m and method in ("POST", "PUT"):
            self._check_acl("csi-write-volume", ns)
            body = self._body()
            batch = body.get("Volumes")
            for raw in batch or [body]:
                vol = csi_volume_from_dict(raw)
                if not vol.id:
                    if batch:
                        # the path id can only name ONE volume
                        raise HTTPError(
                            400, "volumes in a batch require an ID"
                        )
                    vol.id = m.group(1)
                if not vol.plugin_id:
                    raise HTTPError(400, "volume requires PluginID")
                vol.namespace = vol.namespace or ns
                store.upsert_csi_volume(vol)
            self._respond({})
            return True

        if m and method == "DELETE":
            self._check_acl("csi-write-volume", ns)
            try:
                store.deregister_csi_volume(
                    ns, m.group(1), force=q.get("force") == "true"
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._respond({})
            return True

        if path == "/v1/plugins" and method == "GET":
            self._check_acl("csi-list-volume", ns)
            self._respond(
                [
                    csi_plugin_to_dict(p)
                    for p in store.csi_plugins().values()
                ]
            )
            return True

        m = re.fullmatch(r"/v1/plugin/csi/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("csi-read-volume", ns)
            p = store.csi_plugins().get(m.group(1))
            if p is None:
                raise HTTPError(404, "plugin not found")
            self._respond(csi_plugin_to_dict(p))
            return True

        if path == "/v1/operator/scheduler/configuration":
            if method == "GET":
                cfg = store.get_scheduler_config()
                self._respond(
                    {
                        "SchedulerAlgorithm": cfg.scheduler_algorithm,
                        "TPUSchedulerEnabled": cfg.tpu_scheduler_enabled,
                        "PreemptionConfig": {
                            "SystemSchedulerEnabled": cfg.preemption_config.system_scheduler_enabled,
                            "BatchSchedulerEnabled": cfg.preemption_config.batch_scheduler_enabled,
                            "ServiceSchedulerEnabled": cfg.preemption_config.service_scheduler_enabled,
                        },
                    }
                )
                return True
            if method in ("POST", "PUT"):
                self._check_acl("operator:write")
                body = self._body()
                pre = body.get("PreemptionConfig", {})
                cfg = SchedulerConfiguration(
                    scheduler_algorithm=body.get(
                        "SchedulerAlgorithm", "binpack"
                    ),
                    tpu_scheduler_enabled=bool(
                        body.get("TPUSchedulerEnabled", False)
                    ),
                    preemption_config=PreemptionConfig(
                        system_scheduler_enabled=pre.get(
                            "SystemSchedulerEnabled", True
                        ),
                        batch_scheduler_enabled=pre.get(
                            "BatchSchedulerEnabled", False
                        ),
                        service_scheduler_enabled=pre.get(
                            "ServiceSchedulerEnabled", False
                        ),
                    ),
                )
                store.set_scheduler_config(cfg)
                self._respond({"Updated": True})
                return True

        if path == "/v1/catalog/services" and method == "GET":
            self._respond(srv.catalog.services())
            return True

        m = re.fullmatch(r"/v1/catalog/service/([^/]+)", path)
        if m and method == "GET":
            healthy = q.get("passing", "false") == "true"
            self._respond(
                [
                    {
                        "Service": i.service,
                        "AllocID": i.alloc_id,
                        "NodeID": i.node_id,
                        "Task": i.task,
                        "Address": i.address,
                        "Port": i.port,
                        "Tags": i.tags,
                        "Healthy": i.healthy,
                    }
                    for i in srv.catalog.instances(
                        m.group(1), healthy_only=healthy
                    )
                ]
            )
            return True

        if path == "/v1/status/leader" and method == "GET":
            raft = getattr(srv, "raft", None)
            self._respond(
                raft.leader_hint() if raft is not None else "local"
            )
            return True

        if path == "/v1/agent/join" and method in ("POST", "PUT"):
            # runtime cluster join (reference command/agent
            # /v1/agent/join -> srv.Join via serf)
            self._check_acl("agent:write")
            addr = q.get("address") or (self._body() or {}).get(
                "address", ""
            )
            if not addr:
                raise HTTPError(400, "missing address")
            join = getattr(srv, "join", None)
            if join is None:
                raise HTTPError(
                    400, "this agent is not a cluster server"
                )
            try:
                n = join(addr)
            except Exception as exc:  # noqa: BLE001
                raise HTTPError(500, f"join failed: {exc}")
            self._respond({"num_joined": int(n or 0)})
            return True

        if path == "/v1/agent/members" and method == "GET":
            gossip = getattr(srv, "gossip", None)
            self._respond(
                {
                    "ServerName": getattr(srv, "addr", "local"),
                    "ServerRegion": getattr(srv, "region", "global"),
                    "Members": gossip.member_list() if gossip else [
                        {"Name": "local", "Addr": "local",
                         "Status": "alive", "Region": "global",
                         "Role": "server", "Incarnation": 0}
                    ],
                }
            )
            return True

        if path == "/v1/agent/force-leave" and method in (
            "POST",
            "PUT",
        ):
            # evict a failed server from gossip (reference
            # agent_endpoint.go ForceLeave / `server force-leave`)
            self._check_acl("agent:write")
            name = q.get("node", "")
            if not name:
                raise HTTPError(400, "missing node")
            gossip = getattr(srv, "gossip", None)
            if gossip is None:
                raise HTTPError(
                    400, "agent is not running gossip"
                )
            gossip.force_leave(name)
            self._respond({})
            return True

        m = re.fullmatch(r"/v1/volume/csi/([^/]+)/detach", path)
        if m and method in ("POST", "PUT"):
            # release a node's claims on a volume (reference
            # csi_endpoint.go Unpublish / `volume detach`)
            self._check_acl("csi-write-volume", ns)
            node_id = q.get("node", "")
            if not node_id:
                raise HTTPError(400, "missing node")
            try:
                count = store.detach_csi_volume(
                    ns, m.group(1), node_id
                )
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            self._respond({"DetachedClaims": count})
            return True

        if path == "/v1/operator/raft/peer" and method == "DELETE":
            # remove a raft peer (reference operator_endpoint.go
            # RaftRemovePeerByAddress / `operator raft remove-peer`)
            # — through the REPLICATED config change so every server
            # agrees on the new membership, never the local-only
            # remove_peer
            self._check_acl("operator:write")
            address = q.get("address", "")
            if not address:
                raise HTTPError(400, "missing address")
            if hasattr(srv, "broadcast_peer_removal"):
                if not srv.broadcast_peer_removal(address):
                    raise HTTPError(
                        500, "peer removal not acknowledged"
                    )
            else:
                raft = getattr(srv, "raft", None)
                if raft is None or not hasattr(
                    raft, "remove_server"
                ):
                    raise HTTPError(
                        400, "server is not running raft"
                    )
                raft.remove_server(address)
            self._respond({})
            return True

        if path == "/v1/operator/license" and method == "GET":
            # OSS parity: the license surface exists but the feature
            # is Enterprise (reference OSS returns an error here)
            raise HTTPError(
                501, "license is a Nomad Enterprise feature"
            )
        if path == "/v1/operator/license" and method in (
            "POST",
            "PUT",
        ):
            raise HTTPError(
                501, "license is a Nomad Enterprise feature"
            )
        if path.startswith("/v1/sentinel") or path.startswith(
            "/v1/quota"
        ):
            # OSS parity (reference OSS: endpoints registered,
            # feature gated to Enterprise)
            raise HTTPError(
                501,
                "sentinel policies and quotas are Nomad "
                "Enterprise features",
            )

        if path == "/v1/operator/keyring" and method == "GET":
            self._check_acl("agent:read")
            self._respond(srv.keyring.list())
            return True
        if path == "/v1/operator/keyring" and method in (
            "POST",
            "PUT",
        ):
            self._check_acl("agent:write")
            body = self._body()
            op = body.get("Operation", "install")
            key = body.get("Key", "")
            try:
                if op == "install":
                    srv.keyring.install(key)
                elif op == "use":
                    srv.keyring.use(key)
                elif op == "remove":
                    srv.keyring.remove(key)
                else:
                    raise HTTPError(400, f"unknown op {op!r}")
            except ValueError as exc:
                raise HTTPError(400, str(exc))
            self._respond(srv.keyring.list())
            return True

        if path == "/v1/regions" and method == "GET":
            gossip = getattr(srv, "gossip", None)
            if gossip is None:
                self._respond([getattr(srv, "region", "global")])
            else:
                self._respond(
                    sorted({m.region for m in gossip.alive_members()})
                )
            return True

        if path == "/v1/agent/self" and method == "GET":
            self._respond(
                {
                    "member": {"Name": "local", "Status": "alive"},
                    "stats": {
                        "broker": srv.broker.stats,
                        "blocked": srv.blocked.stats,
                        "plan_queue": srv.plan_queue.stats,
                    },
                }
            )
            return True

        if path == "/v1/agent/monitor" and method == "GET":
            self._check_acl("agent:read")
            if q.get("follow") == "true":
                # chunked live stream of agent log lines (reference
                # command/agent/monitor websocket stream); one JSON
                # line per log record
                def monitor_frames():
                    import time as _time

                    seq = int(q.get("index", "-1"))
                    deadline = _time.monotonic() + float(
                        q.get("max_s", "3600")
                    )
                    while _time.monotonic() < deadline:
                        lines, seq = srv.log_monitor.tail(
                            after=seq, wait=1.0
                        )
                        if not lines:
                            yield b""  # liveness probe tick
                        for line in lines:
                            yield (
                                json.dumps({"Line": line}) + "\n"
                            ).encode("utf-8")

                self._stream_chunked(
                    monitor_frames(), "application/json"
                )
                return True
            # log tail with a resumable cursor (reference
            # command/agent/monitor streaming; poll with ?index=<seq>)
            after = int(q.get("index", "-1"))
            wait_s = min(float(q.get("wait", "0")), 10.0)
            lines, seq = srv.log_monitor.tail(after=after, wait=wait_s)
            self._respond({"Lines": lines, "Index": seq})
            return True

        m = re.fullmatch(r"/v1/agent/pprof/([a-z]+)", path)
        if m and method == "GET":
            # python analogs of the go pprof profiles
            # (command/agent/http.go:303)
            self._check_acl("agent:read")
            from ..monitor import runtime_profile, thread_dump

            profile = m.group(1)
            if profile in ("goroutine", "threadcreate"):
                self._respond({"Profile": thread_dump()})
                return True
            if profile in ("heap", "allocs"):
                self._respond(runtime_profile())
                return True
            raise HTTPError(404, f"unknown profile {profile!r}")

        if path == "/v1/operator/autopilot/configuration":
            self._check_acl("operator:read")
            ap = getattr(srv, "autopilot", None)
            if ap is None:
                raise HTTPError(
                    404, "autopilot requires a clustered server"
                )
            if method == "GET":
                c = ap.config
                self._respond(
                    {
                        "CleanupDeadServers": c.cleanup_dead_servers,
                        "LastContactThreshold": (
                            c.last_contact_threshold_s
                        ),
                        "MaxTrailingLogs": c.max_trailing_logs,
                        "ServerStabilizationTime": (
                            c.server_stabilization_time_s
                        ),
                    }
                )
                return True
            if method in ("POST", "PUT"):
                # replicated write (raft), like scheduler config
                self._check_acl("operator:write")
                body = self._body()
                import dataclasses as _dc

                new_cfg = _dc.replace(ap.config)
                if "CleanupDeadServers" in body:
                    new_cfg.cleanup_dead_servers = bool(
                        body["CleanupDeadServers"]
                    )
                if "MaxTrailingLogs" in body:
                    new_cfg.max_trailing_logs = int(
                        body["MaxTrailingLogs"]
                    )
                if "LastContactThreshold" in body:
                    new_cfg.last_contact_threshold_s = float(
                        body["LastContactThreshold"]
                    )
                if "ServerStabilizationTime" in body:
                    new_cfg.server_stabilization_time_s = float(
                        body["ServerStabilizationTime"]
                    )
                store.set_autopilot_config(new_cfg)
                self._respond({"Updated": True})
                return True

        if path == "/v1/operator/autopilot/health" and method == "GET":
            self._check_acl("operator:read")
            ap = getattr(srv, "autopilot", None)
            if ap is None:
                raise HTTPError(
                    404, "autopilot requires a clustered server"
                )
            stats = ap.stats()
            self._respond(
                {
                    **stats,
                    "Servers": [
                        {
                            "ID": h.id,
                            "Name": h.name,
                            "Address": h.address,
                            "Healthy": h.healthy,
                            "Voter": h.voter,
                        }
                        for h in ap.server_health()
                    ],
                }
            )
            return True

        if path == "/v1/operator/raft/configuration" and method == "GET":
            self._check_acl("operator:read")
            raft = getattr(srv, "raft", None)
            if raft is None:
                # single-process server: itself is the whole config
                self._respond(
                    {"Servers": [], "Index": store.latest_index()}
                )
                return True
            leader_addr = (
                raft.addr if raft.is_leader() else raft.leader_hint()
            )
            self._respond(
                {
                    "Servers": [
                        {
                            "ID": addr,
                            "Address": addr,
                            "Leader": addr == leader_addr,
                            "Voter": True,
                        }
                        for addr in [raft.addr] + list(raft.peers)
                    ],
                    "Index": store.latest_index(),
                }
            )
            return True

        if path == "/v1/metrics" and method == "GET":
            metrics = getattr(srv, "metrics", None)
            if q.get("format") == "prometheus":
                # scrape format (reference /v1/metrics?format=prometheus)
                body = (
                    metrics.prometheus_text() if metrics else ""
                ).encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return True
            self._respond(metrics.dump() if metrics else {})
            return True

        # metric time-series history: the retained snapshot windows
        # (NOMAD_TPU_OBS_HISTORY_N windows x 10 s), or one
        # metric's series with ?name=.  Unauthenticated and never
        # shed, like /v1/metrics — it shares the prefix on purpose.
        if path == "/v1/metrics/history" and method == "GET":
            history = getattr(srv, "metrics_history", None)
            if history is None:
                self._respond({"enabled": False, "windows": []})
                return True
            name = q.get("name")
            if name:
                self._respond(
                    {"name": name, "series": history.series(name)}
                )
            else:
                self._respond(history.to_dict())
            return True

        # -- accelerator supervisor status ------------------------------
        # unauthenticated like /v1/metrics: this is the first endpoint
        # an operator polls when the device wedges, and it must answer
        # even when ACL state is part of what's broken
        if path == "/v1/device" and method == "GET":
            sup = getattr(srv, "device_supervisor", None)
            if sup is None:
                self._respond({"enabled": False, "state": "NONE"})
            else:
                self._respond(sup.status())
            return True

        # -- overload / degradation ladder ------------------------------
        # unauthenticated and NEVER shed, like /v1/metrics: the first
        # endpoint an operator (or a backing-off client) polls when
        # the server starts answering 429s
        if path == "/v1/overload" and method == "GET":
            ctl = getattr(srv, "overload", None)
            if ctl is None:
                self._respond({"enabled": False, "mode": 0})
            else:
                self._respond(ctl.status())
            return True

        # -- SLO burn-rate status --------------------------------------
        # unauthenticated and never shed, like /v1/overload: "are we
        # meeting our objectives" must answer exactly when we aren't
        if path == "/v1/slo" and method == "GET":
            slo = getattr(srv, "slo", None)
            if slo is None:
                self._respond(
                    {"enabled": False, "objectives": [], "worst": "OK"}
                )
            else:
                self._respond(slo.status())
            return True

        # -- adaptive-decision ledger ----------------------------------
        # agent:read like /v1/traces: decision inputs carry job ids,
        # node counts and backlog shapes across every namespace
        if path == "/v1/decisions" and method == "GET":
            self._check_acl("agent:read")
            from ..decisions import DECISIONS

            try:
                limit = int(q.get("limit", "64"))
            except ValueError:
                raise HTTPError(400, "bad limit")
            self._respond(
                DECISIONS.to_dict(
                    site=q.get("site"),
                    outcome=q.get("outcome"),
                    trace=q.get("trace"),
                    limit=max(1, min(limit, 1024)),
                )
            )
            return True

        # -- eval flight recorder (per-eval span traces) ----------------
        # agent:read like the other debug surfaces (monitor, pprof):
        # traces carry job ids and node ids across every namespace
        if path == "/v1/traces" and method == "GET":
            self._check_acl("agent:read")
            slow_ms = None
            if "slow_ms" in q:
                try:
                    slow_ms = float(q["slow_ms"])
                except ValueError:
                    raise HTTPError(400, "bad slow_ms")
            try:
                limit = int(q.get("limit", "64"))
            except ValueError:
                raise HTTPError(400, "bad limit")
            self._respond(
                TRACE.recent(
                    slow_ms=slow_ms,
                    outcome=q.get("outcome"),
                    limit=max(1, min(limit, 1024)),
                    full=q.get("full") == "1",
                )
            )
            return True

        m = re.fullmatch(r"/v1/traces/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("agent:read")
            trace = TRACE.get(m.group(1))
            if trace is None:
                raise HTTPError(404, "trace not found")
            self._respond(trace)
            return True

        # -- cluster-scope observability (leader fan-in) ----------------
        # the serving server fans the query out to every known peer
        # over the cluster transport (bounded by
        # NOMAD_TPU_OBS_FANIN_TIMEOUT_S); peers that time out are
        # marked unreachable in `servers`, never a failed query.  On a
        # single-process Server the same endpoints answer with the
        # local share only.
        if path == "/v1/cluster/traces" and method == "GET":
            self._check_acl("agent:read")
            params = {
                "limit": q.get("limit", "64"),
                "outcome": q.get("outcome"),
                "full": q.get("full") == "1",
            }
            if "slow_ms" in q:
                params["slow_ms"] = q["slow_ms"]
            merged = self._cluster_obs(
                srv, "traces", params, region=q.get("region")
            )
            traces = []
            status = {}
            seen = set()
            for addr, result in merged["servers"].items():
                if result.get("unreachable"):
                    status[addr] = "unreachable"
                    continue
                status[addr] = "ok"
                for entry in result.get("traces", []):
                    # dedup by trace id: with a shared in-process
                    # tracer (TestCluster) every server reports the
                    # same traces; first reporter wins the "server"
                    # attribution (local share is queried first)
                    tid = entry.get("trace_id") or entry.get("eval_id")
                    if tid in seen:
                        continue
                    seen.add(tid)
                    entry["server"] = addr
                    traces.append(entry)
            traces.sort(key=lambda t: t.get("start", 0), reverse=True)
            try:
                limit = int(params["limit"])
            except ValueError:
                raise HTTPError(400, "bad limit")
            self._respond(
                {
                    "traces": traces[: max(1, min(limit, 1024))],
                    "servers": status,
                    "unreachable": merged["unreachable"],
                }
            )
            return True

        m = re.fullmatch(r"/v1/cluster/traces/([^/]+)", path)
        if m and method == "GET":
            self._check_acl("agent:read")
            merged = self._cluster_obs(
                srv, "trace", {"ref": m.group(1)},
                region=q.get("region"),
            )
            best = None
            best_server = None
            status = {}
            for addr, result in merged["servers"].items():
                if result.get("unreachable"):
                    status[addr] = "unreachable"
                    continue
                status[addr] = "ok"
                trace = result.get("trace")
                if trace is None:
                    continue
                # the stitched whole lives on the server that rooted
                # the trace (the leader at dequeue time) — prefer the
                # most complete copy: finished beats in flight, more
                # spans beats fewer
                key = (
                    1 if trace.get("complete") else 0,
                    len(trace.get("spans") or ()),
                )
                if best is None or key > best_key:
                    best, best_key, best_server = trace, key, addr
            if best is None:
                raise HTTPError(404, "trace not found on any server")
            best["server"] = best_server
            best["servers"] = status
            self._respond(best)
            return True

        if path == "/v1/cluster/metrics" and method == "GET":
            self._check_acl("agent:read")
            merged = self._cluster_obs(
                srv, "metrics", {}, region=q.get("region")
            )
            servers = {
                addr: (
                    {"unreachable": True}
                    if result.get("unreachable")
                    else result.get("metrics", {})
                )
                for addr, result in merged["servers"].items()
            }
            self._respond(
                {
                    "servers": servers,
                    "unreachable": merged["unreachable"],
                }
            )
            return True

        if path == "/v1/cluster/metrics/history" and method == "GET":
            self._check_acl("agent:read")
            merged = self._cluster_obs(
                srv, "metrics_history", {}, region=q.get("region")
            )
            servers = {
                addr: (
                    {"unreachable": True}
                    if result.get("unreachable")
                    else result.get("history", {})
                )
                for addr, result in merged["servers"].items()
            }
            self._respond(
                {
                    "servers": servers,
                    "unreachable": merged["unreachable"],
                }
            )
            return True

        if path == "/v1/cluster/slo" and method == "GET":
            self._check_acl("agent:read")
            merged = self._cluster_obs(
                srv, "slo", {}, region=q.get("region")
            )
            servers = {
                addr: (
                    {"unreachable": True}
                    if result.get("unreachable")
                    else result.get("slo", {})
                )
                for addr, result in merged["servers"].items()
            }
            self._respond(
                {
                    "servers": servers,
                    "unreachable": merged["unreachable"],
                }
            )
            return True

        if path == "/v1/cluster/decisions" and method == "GET":
            self._check_acl("agent:read")
            params = {
                "limit": q.get("limit", "64"),
                "site": q.get("site"),
                "outcome": q.get("outcome"),
                "trace": q.get("trace"),
            }
            merged = self._cluster_obs(
                srv, "decisions", params, region=q.get("region")
            )
            decisions = []
            status = {}
            seen = set()
            for addr, result in merged["servers"].items():
                if result.get("unreachable"):
                    status[addr] = "unreachable"
                    continue
                status[addr] = "ok"
                share = result.get("decisions", {})
                for rec in share.get("decisions", []):
                    # dedup by ledger seq: with a shared in-process
                    # ledger (TestCluster) every server reports the
                    # same records; first reporter wins attribution
                    if rec.get("seq") in seen:
                        continue
                    seen.add(rec.get("seq"))
                    rec["server"] = addr
                    decisions.append(rec)
            decisions.sort(
                key=lambda r: r.get("seq", 0), reverse=True
            )
            try:
                limit = int(params["limit"])
            except ValueError:
                raise HTTPError(400, "bad limit")
            self._respond(
                {
                    "decisions": decisions[: max(1, min(limit, 1024))],
                    "servers": status,
                    "unreachable": merged["unreachable"],
                }
            )
            return True

        if path == "/v1/search" and method in ("POST", "PUT", "GET"):
            body = self._body() if method != "GET" else q
            prefix = body.get("Prefix") or body.get("prefix", "")
            context = body.get("Context") or body.get("context", "all")
            self._respond(self._search(store, prefix, context))
            return True

        # -- ACLs (reference nomad/acl_endpoint.go) ---------------------
        if path == "/v1/acl/bootstrap" and method in ("POST", "PUT"):
            acls = srv.acls
            if acls.tokens_by_secret:
                raise HTTPError(400, "ACL bootstrap already done")
            token = acls.bootstrap()
            self._respond(
                {
                    "AccessorID": token.accessor_id,
                    "SecretID": token.secret_id,
                    "Type": token.type,
                }
            )
            return True

        if path == "/v1/acl/policies" and method == "GET":
            self._check_acl("operator:read")
            self._respond(
                [
                    {"Name": p.name}
                    for p in srv.acls.policies.values()
                ]
            )
            return True

        m = re.fullmatch(r"/v1/acl/policy/([^/]+)", path)
        if m:
            from ..acl import Policy

            name = m.group(1)
            if method == "GET":
                self._check_acl("operator:read")
                policy = srv.acls.policies.get(name)
                if policy is None:
                    raise HTTPError(404, "policy not found")
                self._respond(
                    {
                        "Name": policy.name,
                        "Namespaces": {
                            ns: {
                                "Policy": np.policy,
                                "Capabilities": sorted(np.capabilities),
                            }
                            for ns, np in policy.namespaces.items()
                        },
                        "Node": policy.node,
                        "Operator": policy.operator,
                    }
                )
                return True
            if method in ("POST", "PUT"):
                self._check_acl("operator:write")
                body = self._body()
                rules = body.get("Rules") or body.get("rules") or body
                if isinstance(rules, str):
                    rules = json.loads(rules)
                srv.acls.upsert_policy(Policy.from_dict(name, rules))
                self._respond({})
                return True
            if method == "DELETE":
                self._check_acl("operator:write")
                srv.acls.delete_policy(name)
                self._respond({})
                return True

        if path == "/v1/acl/tokens":
            if method == "GET":
                self._check_acl("operator:read")
                self._respond(
                    [
                        {
                            "AccessorID": t.accessor_id,
                            "Name": t.name,
                            "Type": t.type,
                            "Policies": t.policies,
                        }
                        for t in srv.acls.tokens_by_accessor.values()
                    ]
                )
                return True
            if method in ("POST", "PUT"):
                self._check_acl("operator:write")
                from ..acl import Token

                body = self._body()
                token = Token(
                    name=body.get("Name", ""),
                    type=body.get("Type", "client"),
                    policies=body.get("Policies") or [],
                )
                srv.acls.create_token(token)
                self._respond(
                    {
                        "AccessorID": token.accessor_id,
                        "SecretID": token.secret_id,
                    }
                )
                return True

        if path == "/v1/acl/token/self" and method == "GET":
            token = srv.acls.tokens_by_secret.get(
                self.headers.get("X-Nomad-Token", "")
            )
            if token is None:
                raise HTTPError(403, "no token supplied or unknown")
            self._respond(
                {
                    "AccessorID": token.accessor_id,
                    "Name": token.name,
                    "Type": token.type,
                    "Policies": token.policies,
                }
            )
            return True

        m = re.fullmatch(r"/v1/acl/token/([^/]+)", path)
        if m and method == "DELETE":
            self._check_acl("operator:write")
            srv.acls.delete_token(m.group(1))
            self._respond({})
            return True
        if m and method == "GET":
            self._check_acl("operator:read")
            token = srv.acls.tokens_by_accessor.get(m.group(1))
            if token is None:
                raise HTTPError(404, "token not found")
            self._respond(
                {
                    "AccessorID": token.accessor_id,
                    "Name": token.name,
                    "Type": token.type,
                    "Policies": token.policies,
                }
            )
            return True
        if m and method in ("POST", "PUT"):
            self._check_acl("operator:write")
            token = srv.acls.tokens_by_accessor.get(m.group(1))
            if token is None:
                raise HTTPError(404, "token not found")
            body = self._body()
            import copy as _copy

            updated = _copy.copy(token)
            if "Name" in body:
                updated.name = body["Name"]
            if "Policies" in body:
                updated.policies = body["Policies"] or []
            if "Type" in body:
                updated.type = body["Type"]
            # create_token upserts by accessor/secret id and routes
            # through raft on replicated clusters
            try:
                srv.acls.create_token(updated)
            except ValueError as exc:
                raise HTTPError(400, str(exc))
            self._respond({"AccessorID": updated.accessor_id})
            return True

        if path == "/v1/operator/snapshot/save" and method in ("POST", "PUT"):
            self._check_acl("operator:write")
            body = self._body()
            from ..server.snapshot import save_snapshot

            save_snapshot(srv, body["Path"])
            self._respond({"Saved": body["Path"]})
            return True

        if path == "/v1/operator/snapshot/restore" and method in ("POST", "PUT"):
            self._check_acl("operator:write")
            body = self._body()
            from ..server.snapshot import restore_snapshot

            index = restore_snapshot(srv, body["Path"])
            srv.restore_evals()
            self._respond({"Index": index})
            return True

        if path == "/v1/system/gc" and method in ("POST", "PUT"):
            self._check_acl("operator:write")
            srv.force_gc()
            self._respond({})
            return True

        if path == "/v1/system/reconcile/summaries" and method in (
            "POST",
            "PUT",
        ):
            # recompute every job's derived status/summary (reference
            # nomad/system_endpoint.go ReconcileJobSummaries); routes
            # through the store (raft on replicated clusters) so all
            # replicas converge and blocking queries wake
            self._check_acl("operator:write")
            store.reconcile_job_summaries()
            self._respond({})
            return True

        # -- namespaces (reference nomad/namespace_endpoint +
        # state table; OSS'd in 1.0) --------------------------------

        if path == "/v1/namespaces" and method == "GET":
            # filtered by the token's per-namespace capabilities
            # (reference namespace_endpoint.go ListNamespaces): a
            # token scoped to one namespace must not learn the
            # names/descriptions of the others; management sees all
            acls = getattr(srv, "acls", None)
            token_raw = self.headers.get("X-Nomad-Token", "")
            acl = (
                acls.resolve(token_raw)
                if acls is not None and acls.enabled
                else None
            )

            def ns_visible(name: str) -> bool:
                if acls is None or not acls.enabled:
                    return True
                if acl is None:
                    return False
                return any(
                    acl.allow_namespace_operation(name, c)
                    for c in ("read-job", "list-jobs")
                )

            visible = [
                n for n in store.iter_namespaces()
                if ns_visible(n.name)
            ]
            # A *resolved* token with zero visible namespaces gets [],
            # not 403 (reference ListNamespaces only denies anonymous/
            # invalid tokens) — narrowly-scoped automation must not see
            # an error where an empty list is the honest answer.
            if (
                acls is not None
                and acls.enabled
                and (not token_raw or acl is None)
            ):
                raise HTTPError(403, "Permission denied")
            self._respond(
                [
                    {
                        "Name": n.name,
                        "Description": n.description,
                        "CreateIndex": n.create_index,
                        "ModifyIndex": n.modify_index,
                    }
                    for n in visible
                ]
            )
            return True

        if path in ("/v1/namespaces", "/v1/namespace") and method in (
            "POST",
            "PUT",
        ):
            self._check_acl("operator:write")
            body = self._body()
            from ..structs import Namespace

            namespace = Namespace(
                name=body.get("Name", ""),
                description=body.get("Description", ""),
            )
            try:
                index = store.upsert_namespace(namespace)
            except ValueError as exc:
                raise HTTPError(400, str(exc))
            self._respond({"Index": index})
            return True

        m = re.fullmatch(r"/v1/namespace/([^/]+)", path)
        if m and method == "GET":
            self._check_acl_any(("read-job", "list-jobs"), m.group(1))
            n = store.namespace_by_name(m.group(1))
            if n is None:
                raise HTTPError(404, "namespace not found")
            self._respond(
                {
                    "Name": n.name,
                    "Description": n.description,
                    "CreateIndex": n.create_index,
                    "ModifyIndex": n.modify_index,
                }
            )
            return True

        if m and method == "DELETE":
            self._check_acl("operator:write")
            try:
                index = store.delete_namespace(m.group(1))
            except KeyError as exc:
                raise HTTPError(404, str(exc))
            except ValueError as exc:
                raise HTTPError(400, str(exc))
            self._respond({"Index": index})
            return True

        return False

    @staticmethod
    def _search(store, prefix: str, context: str) -> Dict:
        """Prefix search over the main tables
        (reference nomad/search_endpoint.go)."""
        out: Dict[str, list] = {"Matches": {}, "Truncations": {}}
        limit = 20

        def matches(items):
            hits = [i for i in items if i.startswith(prefix)]
            return hits[:limit], len(hits) > limit

        if context in ("jobs", "all"):
            hits, trunc = matches([j.id for j in store.iter_jobs()])
            out["Matches"]["jobs"] = hits
            out["Truncations"]["jobs"] = trunc
        if context in ("nodes", "all"):
            hits, trunc = matches([n.id for n in store.iter_nodes()])
            out["Matches"]["nodes"] = hits
            out["Truncations"]["nodes"] = trunc
        if context in ("allocs", "all"):
            hits, trunc = matches(list(store.allocs))
            out["Matches"]["allocs"] = hits
            out["Truncations"]["allocs"] = trunc
        if context in ("evals", "all"):
            hits, trunc = matches(list(store.evals))
            out["Matches"]["evals"] = hits
            out["Truncations"]["evals"] = trunc
        if context in ("deployment", "all"):
            hits, trunc = matches(list(store.deployments))
            out["Matches"]["deployment"] = hits
            out["Truncations"]["deployment"] = trunc
        return out


class HTTPServer:
    def __init__(self, server, host: str = "127.0.0.1", port: int = 4646):
        handler = type("BoundHandler", (APIHandler,), {"server_ref": server})
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, name="http-api", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)


def start_http_server(server, host="127.0.0.1", port=0) -> HTTPServer:
    http = HTTPServer(server, host, port)
    http.start()
    # gossip the bound HTTP address (cluster servers only): other
    # regions learn where to send redirected traffic — the shed
    # retry-region hint and the ?region= proxy both resolve through
    # these advertised addresses
    advertise = getattr(server, "advertise_http", None)
    if advertise is not None:
        advertise(f"{host}:{http.port}")
    return http
