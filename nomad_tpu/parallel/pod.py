"""Pod composition: a live worker HEADS a multi-process mesh.

PR 11's multi-host path assumed lockstep SPMD: every process runs the
same worker over the same inputs in the same order (the dist_smoke
harness drives both ranks synchronously).  A fan-out follower cannot
lockstep — it leases evals from the leader's broker at its own pace,
so no peer process could independently reproduce its launch sequence.

This module makes the follower's worker process the HEAD of its
`jax.distributed` world and streams the launch sequence to the other
world members (PEERS) over an ordered TCP channel:

* the head sends each mesh operation (mirror full/bulk/delta sync,
  chain launch, storm solve) as one framed message, THEN executes it;
* each peer executes messages strictly in receive order.

TCP FIFO delivery makes the collective launch sequences identical by
construction — the multi-controller contract — while everything
non-collective (``mesh_put`` / ``make_array_from_callback`` staging)
stays process-local.  Mirror deltas re-run PR 11's per-host flush
protocol on the peer: the head ships only the SORTED dirty rows and
their three value columns (O(dirty rows) bytes on the wire), and the
peer rebuilds its own shard-local ``[D, w]`` staging from them.

Device-resident operands never cross the wire: the chain's usage
columns come from the peer's own mirror registry ("mirror") or its
own previous launch's carry ("carry"), which track the head's
bit-for-bit because both sides applied the same update stream.

``NOMAD_TPU_POD_PORT`` (head listen port) turns the head side on;
peers run ``python -m nomad_tpu.parallel.pod`` with the same
``NOMAD_TPU_DIST*`` world knobs and a nonzero ``NOMAD_TPU_DIST_ID``.
``NOMAD_TPU_POD_CHECK=1`` makes every chain/storm launch round-trip a
result digest from every peer — the parity gate the bigworld smoke
asserts (head and peers realize identical replicated outputs).
"""
from __future__ import annotations

import argparse
import os
import pickle
import socket
import struct
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

_LEN = struct.Struct(">Q")


def send_msg(sock: socket.socket, obj) -> None:
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(_LEN.pack(len(data)) + data)


def recv_msg(sock: socket.socket):
    head = _recv_exact(sock, _LEN.size)
    (n,) = _LEN.unpack(head)
    return pickle.loads(_recv_exact(sock, n))


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("pod channel closed mid-message")
        buf.extend(chunk)
    return bytes(buf)


def pod_check_enabled() -> bool:
    return os.environ.get("NOMAD_TPU_POD_CHECK") == "1"


def result_digest(*arrays) -> str:
    """Order-stable digest of realized (replicated) outputs, shared by
    head and peer for the POD_CHECK parity gate."""
    import hashlib

    h = hashlib.sha256()
    for a in arrays:
        arr = np.ascontiguousarray(np.asarray(a))
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


class PodService:
    """Head side: accepts the world's peer connections and broadcasts
    the mesh-operation stream in FIFO order.  All sends serialize
    behind one lock — interleaved messages from two threads would
    diverge the peers' collective order from the head's."""

    def __init__(self, port: int, n_peers: int) -> None:
        self.n_peers = n_peers
        self._srv = socket.socket(
            socket.AF_INET, socket.SOCK_STREAM
        )
        self._srv.setsockopt(
            socket.SOL_SOCKET, socket.SO_REUSEADDR, 1
        )
        self._srv.bind(("127.0.0.1", port))
        self.port = self._srv.getsockname()[1]
        self._srv.listen(max(1, n_peers))
        self._peers: List[socket.socket] = []
        self._lock = threading.Lock()
        self._accept_cond = threading.Condition(self._lock)
        self._closed = False
        self.check = pod_check_enabled()
        t = threading.Thread(
            target=self._accept_loop, name="pod-accept", daemon=True
        )
        t.start()

    def _accept_loop(self) -> None:
        while True:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return  # listener closed
            conn.setsockopt(
                socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
            )
            with self._lock:
                self._peers.append(conn)
                self._accept_cond.notify_all()
                if len(self._peers) >= self.n_peers:
                    return

    def wait_peers(self, timeout: float = 120.0) -> None:
        deadline = time.monotonic() + timeout
        with self._lock:
            while len(self._peers) < self.n_peers:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"pod head: {len(self._peers)}/"
                        f"{self.n_peers} peers connected"
                    )
                self._accept_cond.wait(remaining)

    def send(self, kind: str, *payload) -> None:
        """Broadcast one operation.  Blocks until the full world is
        connected — executing a collective before every member can
        follow would deadlock the pod at rendezvous."""
        self.wait_peers()
        with self._lock:
            if self._closed:
                raise RuntimeError("pod service closed")
            for sock in self._peers:
                send_msg(sock, (kind,) + payload)

    def check_results(self, digest: str) -> None:
        """POD_CHECK parity gate: collect one digest per peer for the
        launch just executed and require equality with the head's."""
        if not self.check:
            return
        with self._lock:
            for sock in self._peers:
                got = recv_msg(sock)
                if got != ("digest", digest):
                    raise AssertionError(
                        f"pod parity: peer digest {got!r} != head "
                        f"{digest!r}"
                    )

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            for sock in self._peers:
                try:
                    send_msg(sock, ("bye",))
                except OSError:
                    pass
                try:
                    sock.close()
                except OSError:
                    pass
            try:
                self._srv.close()
            except OSError:
                pass


def build_worker_mesh():
    """The worker's mesh bring-up, shared verbatim by head (via
    BatchWorker._make_mesh) and peer: join the NOMAD_TPU_DIST* world,
    then lay every visible device along the node axis.  Identical env
    -> identical mesh on every member, which the collective programs
    require."""
    import jax

    from .mesh import distributed_init, make_mesh

    distributed_init()
    n = len(jax.devices())
    if n <= 1:
        return None
    return make_mesh(n_devices=n, eval_axis=1)


class PodPeer:
    """Peer side: one registry of device-resident state (the sharded
    usage mirror and the running chain carry) plus the message loop
    that replays the head's operation stream against it."""

    def __init__(self, mesh) -> None:
        self.mesh = mesh
        self.mirror: Optional[tuple] = None
        self.carry = None
        self._runners: Dict[tuple, object] = {}
        self._storm_fns: Dict[tuple, object] = {}
        self.check = pod_check_enabled()

    # -- registry ops (one per head-side message kind) ------------------

    def mirror_full(self, host_cols) -> None:
        from jax.sharding import PartitionSpec as P

        from .mesh import mesh_put

        self.mirror = tuple(
            mesh_put(self.mesh, col, P("nodes"))
            for col in host_cols
        )

    def mirror_bulk(self, host_used) -> None:
        from jax.sharding import PartitionSpec as P

        from .mesh import mesh_put

        assert self.mirror is not None, "bulk before full sync"
        self.mirror = self.mirror[:3] + tuple(
            mesh_put(self.mesh, col, P("nodes"))
            for col in host_used
        )

    def mirror_delta(self, idx, vals3, capacity) -> None:
        """Replay PR 11's per-host flush: rebuild the shard-local
        [D, w] staging from the (sorted) global dirty rows, gathering
        THIS process's rows from the wire values."""
        from jax.sharding import PartitionSpec as P

        from ..ops.batch import (
            hostlocal_staging,
            patch_rows_hostlocal,
        )
        from .mesh import local_device_positions, mesh_put

        assert self.mirror is not None, "delta before full sync"
        idx = np.asarray(idx, dtype=np.int32)
        idx_stack, per_dev, width = hostlocal_staging(
            self.mesh, idx, capacity
        )
        idx_dev = mesh_put(self.mesh, idx_stack, P("nodes"))
        n_dev = self.mesh.devices.size
        local_pos = local_device_positions(self.mesh)
        patch = patch_rows_hostlocal(self.mesh, donate=False)
        patched = []
        for col, vals in zip(self.mirror[3:], vals3):
            vals = np.asarray(vals)
            vals_stack = np.zeros((n_dev, width), dtype=vals.dtype)
            for d in local_pos:
                sel = per_dev[d]
                # wire values are aligned with the sorted idx; the
                # shard's rows map back via binary search
                pos = np.searchsorted(idx, np.asarray(sel))
                vals_stack[d, : len(sel)] = vals[pos]
            vals_dev = mesh_put(
                self.mesh, vals_stack, P("nodes")
            )
            patched.append(
                patch(col, idx_dev, vals_dev)  # nomadlint: disable=donation-safety -- patch is built with donate=False above; col is read-only here and the mirror slot is rebound right after the loop
            )
        self.mirror = self.mirror[:3] + tuple(patched)

    def chain(self, meta: dict, args_tail: tuple) -> Optional[str]:
        from .mesh import place_chain_inputs, sharded_chained_plan

        assert self.mirror is not None, "chain before mirror sync"
        used = (
            self.carry
            if meta["used"] == "carry"
            else self.mirror[3:6]
        )
        assert used is not None, "carry chain before any chunk"
        key = (
            meta["n_picks"], meta["spread_fit"],
            meta["with_spread"], meta["spread_even"],
        )
        runner = self._runners.get(key)
        if runner is None:
            runner = sharded_chained_plan(
                self.mesh, meta["n_picks"], meta["spread_fit"],
                with_spread=meta["with_spread"],
                spread_even=meta["spread_even"],
                return_carry=True,
            )
            self._runners[key] = runner
        args = self.mirror[:3] + tuple(used) + tuple(args_tail)
        args = place_chain_inputs(
            self.mesh, args,
            with_spread=meta["with_spread"],
            spread_even=meta["spread_even"],
        )
        rows_j, pulls_j, used_out = runner(*args)
        self.carry = used_out
        if self.check:
            return result_digest(rows_j, pulls_j)
        return None

    def storm(
        self, inputs_host, spread_fit: bool, max_rounds: int
    ) -> Optional[str]:
        from ..ops.solve import (
            StormInputs,
            storm_assignment_sharded,
        )
        from ..sched.storm import stage_for_mesh

        assert self.mirror is not None, "storm before mirror sync"
        inputs = StormInputs(*inputs_host)
        weighted = inputs.policy_tput_term is not None
        key = (spread_fit, max_rounds, weighted)
        fn = self._storm_fns.get(key)
        if fn is None:
            fn = storm_assignment_sharded(
                self.mesh, spread_fit=spread_fit,
                max_rounds=max_rounds, weighted=weighted,
            )
            self._storm_fns[key] = fn
        inp = stage_for_mesh(inputs, self.mesh)
        out = fn(inp, self.mirror)
        if self.check:
            return result_digest(*out)
        # realize anyway: an error inside the solve must surface on
        # the peer too, not linger as a poisoned future
        for x in out:
            np.asarray(x)
        return None

    def reset(self) -> None:
        self.mirror = None
        self.carry = None

    # -- message loop ---------------------------------------------------

    def serve(self, sock: socket.socket) -> None:
        while True:
            msg = recv_msg(sock)
            kind = msg[0]
            if kind == "bye":
                return
            digest = None
            if kind == "mirror_full":
                self.mirror_full(msg[1])
            elif kind == "mirror_bulk":
                self.mirror_bulk(msg[1])
            elif kind == "mirror_delta":
                self.mirror_delta(msg[1], msg[2], msg[3])
            elif kind == "chain":
                digest = self.chain(msg[1], msg[2])
            elif kind == "storm":
                digest = self.storm(msg[1], msg[2], msg[3])
            elif kind == "reset":
                self.reset()
            else:
                raise ValueError(f"unknown pod message {kind!r}")
            if digest is not None:
                send_msg(sock, ("digest", digest))


def run_peer(head_port: int, connect_timeout: float = 120.0) -> None:
    """Peer process entrypoint: join the world, build the mesh, dial
    the head and replay its stream until ``bye``."""
    mesh = build_worker_mesh()
    if mesh is None:
        raise RuntimeError(
            "pod peer: no multi-device mesh (check XLA_FLAGS / "
            "NOMAD_TPU_DIST* env)"
        )
    deadline = time.monotonic() + connect_timeout
    sock = None
    while sock is None:
        try:
            sock = socket.create_connection(
                ("127.0.0.1", head_port), timeout=5.0
            )
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.2)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.settimeout(None)
    print(f"POD_PEER_READY port={head_port}", flush=True)
    PodPeer(mesh).serve(sock)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="nomad-tpu pod peer (mesh world member)"
    )
    parser.add_argument(
        "--head-port", type=int, required=True,
        help="head worker's NOMAD_TPU_POD_PORT",
    )
    parser.add_argument(
        "--connect-timeout", type=float, default=120.0
    )
    args = parser.parse_args(argv)
    run_peer(args.head_port, args.connect_timeout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
