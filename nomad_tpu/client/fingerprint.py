"""Host fingerprinting (reference client/fingerprint/): detect resources,
attributes and devices and fold them into the Node.

TPU-native: accelerators are fingerprinted through JAX (`jax.devices()`)
into the node's device inventory — the TPU equivalent of the reference's
nvml-based GPU fingerprinter (devices/gpu/nvidia/device.go:88)."""
from __future__ import annotations

import logging
import os
import platform
import socket
from typing import Dict, List

from ..structs import Node, NodeDeviceResource, NodeResources

LOG = logging.getLogger("nomad_tpu.client.fingerprint")


def fingerprint_arch(node: Node) -> None:
    node.attributes["cpu.arch"] = platform.machine()
    node.attributes["kernel.name"] = platform.system().lower()
    node.attributes["kernel.version"] = platform.release()
    node.attributes["os.name"] = platform.system().lower()


def fingerprint_cpu(node: Node) -> None:
    ncores = os.cpu_count() or 1
    node.attributes["cpu.numcores"] = str(ncores)
    mhz = 2400  # conservative default when frequency is unavailable
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("cpu mhz"):
                    mhz = int(float(line.split(":")[1]))
                    break
    except OSError:
        pass
    node.attributes["cpu.frequency"] = str(mhz)
    total = ncores * mhz
    node.attributes["cpu.totalcompute"] = str(total)
    if node.node_resources.cpu <= 0:
        node.node_resources.cpu = total


def fingerprint_memory(node: Node) -> None:
    total_mb = 1024
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal"):
                    total_mb = int(line.split()[1]) // 1024
                    break
    except OSError:
        pass
    node.attributes["memory.totalbytes"] = str(total_mb * 1024 * 1024)
    if node.node_resources.memory_mb <= 0:
        node.node_resources.memory_mb = total_mb


def fingerprint_storage(node: Node, path: str = "/") -> None:
    try:
        stat = os.statvfs(path)
        free_mb = stat.f_bavail * stat.f_frsize // (1024 * 1024)
    except OSError:
        free_mb = 10 * 1024
    node.attributes["unique.storage.volume"] = path
    node.attributes["unique.storage.bytesfree"] = str(
        free_mb * 1024 * 1024
    )
    if node.node_resources.disk_mb <= 0:
        node.node_resources.disk_mb = free_mb


def fingerprint_host(node: Node) -> None:
    node.attributes["unique.hostname"] = socket.gethostname()
    if not node.name:
        node.name = socket.gethostname()


# bounded TPU device-probe deadline during fingerprinting
FINGERPRINT_TIMEOUT_S = 20.0


def bounded_jax_devices():
    """`jax.devices()` with a deadline: callers (node fingerprint, TPU
    device plugin) must not wedge the client agent on backend init.
    Returns None on timeout/failure — a node that registers CPU-only
    stays CPU-only until restart, which is the accepted trade for
    registering at all.

    One JAX process per chip: in ``agent -dev`` this runs inside the
    scheduler's process and sees its backend.  A SEPARATE client agent
    on the scheduler's host is a second claimant — libtpu refuses it
    (logged below) — so run it with ``include_tpu_fingerprint = false``
    or ``JAX_PLATFORMS=cpu``."""
    import threading

    box: Dict[str, List] = {}

    def enumerate_devices() -> None:
        try:
            import jax

            box["devices"] = jax.devices()
        except Exception as exc:  # noqa: BLE001 — register CPU-only
            LOG.warning(
                "accelerator fingerprint failed (%s); registering "
                "without accelerators", exc,
            )

    t = threading.Thread(target=enumerate_devices, daemon=True)
    t.start()
    t.join(FINGERPRINT_TIMEOUT_S)
    return box.get("devices")


def fingerprint_tpu(node: Node) -> None:
    """Detect attached accelerators via JAX; import is deferred and
    failures are non-fatal so CPU-only clients fingerprint cleanly."""
    devices = bounded_jax_devices()
    if devices is None:
        return
    by_kind: Dict[str, List] = {}
    for d in devices:
        if d.platform in ("cpu",):
            continue
        by_kind.setdefault(d.device_kind, []).append(d)
    for kind, devs in by_kind.items():
        node.node_resources.devices.append(
            NodeDeviceResource(
                vendor="google",
                type="tpu",
                name=kind.replace(" ", "-").lower(),
                instance_ids=[str(d.id) for d in devs],
                attributes={
                    "platform": devs[0].platform,
                    "count": str(len(devs)),
                },
            )
        )
        node.attributes["tpu.count"] = str(len(devs))
        node.attributes["tpu.kind"] = kind


def fingerprint_drivers(node: Node, drivers: Dict[str, object]) -> None:
    for name, driver in drivers.items():
        for key, value in driver.fingerprint().items():
            node.attributes[key] = value
        node.drivers[name] = True


ALL_FINGERPRINTERS = [
    fingerprint_arch,
    fingerprint_cpu,
    fingerprint_memory,
    fingerprint_storage,
    fingerprint_host,
    fingerprint_tpu,
]


def run_fingerprinters(node: Node, include_tpu: bool = True) -> Node:
    for fp in ALL_FINGERPRINTERS:
        if fp is fingerprint_tpu and not include_tpu:
            continue
        fp(node)
    return node
