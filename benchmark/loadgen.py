"""The load generator: registrations over HTTP from a few sender
threads, completions learnt by ONE watcher.

A registration is ``POST /v1/jobs`` on a kept-alive connection.  It is
done at the instant a client waiting on it would learn that its
evaluation is ``complete``.  One watcher thread learns that for every
outstanding evaluation: it sleeps on the store's index (the wake-up an
HTTP long-poll ``?index=N`` gets, ``api/http.py`` blocking queries) and
on each wake-up looks the outstanding evaluation ids up.  One poller
per job would put thousands of wake-ups a second under the same GIL as
the system under test, and the long-poll endpoints wait on the store's
global index, so one watcher per job would be woken by every commit of
every job.

Nothing here imports the program: the watcher is handed two callables.
"""
from __future__ import annotations

import http.client
import json
import queue
import threading
import time
from dataclasses import dataclass, field

COMPLETE = "complete"
TERMINAL = ("complete", "failed", "canceled")


@dataclass
class Request:
    index: int
    placements: int
    due: float = 0.0  # open loop: scheduled send; closed loop: slot free
    sent: float = 0.0
    acked: float = 0.0
    done: float = 0.0  # completion observed; 0 = never
    eval_id: str = ""
    http_status: int = 0
    eval_status: str = ""

    @property
    def ok(self) -> bool:
        return self.done > 0.0 and self.eval_status == COMPLETE


class Client:
    """One kept-alive HTTP/1.1 connection."""

    def __init__(self, port: int) -> None:
        self.port = port
        self.conn = None

    def request(self, method: str, path: str, body: bytes | None = None):
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=60
                )
            try:
                self.conn.request(
                    method, path, body=body,
                    headers={"Content-Type": "application/json"},
                )
                resp = self.conn.getresponse()
                data = resp.read()
                return resp.status, data
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def get_json(self, path: str):
        status, data = self.request("GET", path)
        if status != 200:
            raise RuntimeError(f"GET {path}: HTTP {status}")
        return json.loads(data)

    def post_job(self, body: bytes):
        """(HTTP status, evaluation id or "")."""
        status, data = self.request("POST", "/v1/jobs", body)
        if status != 200:
            return status, ""
        return status, json.loads(data).get("EvalID", "")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


@dataclass
class LoadGen:
    """Drives one stream.  ``wait_index(i, timeout)`` blocks until the
    store passed index ``i``; ``eval_status(id)`` is the evaluation's
    status or None."""

    port: int
    stream: object  # JobStream
    traffic: dict
    wait_index: object
    latest_index: object
    eval_status: object
    prefix: str = "job"
    requests: list = field(default_factory=list)

    def __post_init__(self) -> None:
        self.lock = threading.Lock()
        self.outstanding: dict = {}  # eval id -> Request
        self.next_index = 0
        self.stop = threading.Event()
        self.senders_done = threading.Event()
        self.completed = 0
        self.free_slots: queue.SimpleQueue = queue.SimpleQueue()
        self.threads: list = []
        self.send_until = float("inf")
        self.t_start = 0.0
        self.errors: list = []
        self.cpu: dict = {}  # thread name -> CPU seconds, as last noted

    def _note_cpu(self) -> None:
        self.cpu[threading.current_thread().name] = time.thread_time()

    # -- the watcher ---------------------------------------------------

    def _watch(self) -> None:
        index = self.latest_index()
        while not (self.senders_done.is_set() and not self.outstanding):
            self.wait_index(index + 1, 0.1)
            index = self.latest_index()
            with self.lock:
                pending = list(self.outstanding.items())
            for eval_id, req in pending:
                status = self.eval_status(eval_id)
                if status in TERMINAL:
                    req.done = time.monotonic()
                    req.eval_status = status
                    with self.lock:
                        del self.outstanding[eval_id]
                        self.completed += 1
                    self.free_slots.put(req.done)
            self._note_cpu()

    # -- senders -------------------------------------------------------

    def _take(self) -> int:
        with self.lock:
            i = self.next_index
            self.next_index += 1
            return i

    def _send(self, client: Client, i: int, due: float) -> None:
        req = Request(index=i, placements=self.stream.placements(i), due=due)
        body = self.stream.body(i, self.prefix)
        req.sent = time.monotonic()
        try:
            req.http_status, req.eval_id = client.post_job(body)
        except (http.client.HTTPException, OSError) as exc:
            req.http_status = -1
            self.errors.append(repr(exc))
        req.acked = time.monotonic()
        with self.lock:
            self.requests.append(req)
            if req.eval_id:
                self.outstanding[req.eval_id] = req
        if not req.eval_id:
            # refused or shed: the slot is free again at once
            self.free_slots.put(req.acked)
        self._note_cpu()

    def _closed_sender(self) -> None:
        client = Client(self.port)
        try:
            while True:
                freed = self.free_slots.get()
                if freed is None or self.stop.is_set():
                    return
                self._send(client, self._take(), freed)
        finally:
            client.close()

    def due_offset(self, i: int) -> float:
        """Seconds after the stream's start at which registration ``i``
        is due: evenly spaced at ``rate_per_s``, or, for a sweep, at each
        of ``rate_steps`` = [[rate, seconds], ...] in turn."""
        steps = self.traffic.get("rate_steps")
        if not steps:
            return i / float(self.traffic["rate_per_s"])
        t = 0.0
        for rate, secs in steps:
            n = int(rate * secs)
            if i < n:
                return t + i / float(rate)
            i -= n
            t += float(secs)
        return float("inf")

    def _open_sender(self) -> None:
        client = Client(self.port)
        try:
            while not self.stop.is_set():
                i = self._take()
                due = self.t_start + self.due_offset(i)
                if due >= self.send_until:
                    return
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                self._send(client, i, due)
        finally:
            client.close()

    # -- life cycle ----------------------------------------------------

    def start(self, send_for_s: float = float("inf")) -> None:
        self.t_start = time.monotonic()
        self.send_until = self.t_start + send_for_s
        closed = self.traffic["loop"] == "closed"
        if closed:
            for _ in range(int(self.traffic["in_flight"])):
                self.free_slots.put(self.t_start)
        watcher = threading.Thread(
            target=self._watch, name="bench-watcher", daemon=True
        )
        self.threads = [watcher] + [
            threading.Thread(
                target=self._closed_sender if closed else self._open_sender,
                name=f"bench-sender-{k}",
                daemon=True,
            )
            for k in range(int(self.traffic.get("senders", 4)))
        ]
        for t in self.threads:
            t.start()

    def cpu_s(self) -> float:
        """CPU seconds the generator's threads have used so far."""
        return sum(list(self.cpu.values()))

    def in_flight(self) -> int:
        with self.lock:
            return len(self.outstanding)

    def finish(self, grace_s: float) -> bool:
        """Stop sending, then wait for what is outstanding.  True when
        everything sent was seen to end."""
        self.stop.set()
        for _ in self.threads:
            self.free_slots.put(None)
        t_end = time.monotonic() + grace_s
        for t in self.threads[1:]:
            t.join(max(0.1, t_end - time.monotonic()))
        self.senders_done.set()
        self.threads[0].join(max(0.1, t_end - time.monotonic()))
        drained = not self.outstanding and not any(
            t.is_alive() for t in self.threads
        )
        if not drained:
            # let the watcher go even if evaluations never ended
            with self.lock:
                self.outstanding.clear()
            self.threads[0].join(2.0)
        return drained
