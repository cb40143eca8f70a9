"""The fleet and its resident allocations, as plain arrays made from
``--seed``.  Both sides load these same arrays: the program's state
store (``system.load_world``) and the plain reference
(``reference.RefCluster``).  Nothing here imports the program or JAX.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class World:
    """Node ``i`` is the ``i``-th node registered; the scheduler visits
    nodes in registration order before its seeded shuffle."""

    datacenters: tuple  # names
    node_dc: np.ndarray  # (N,) index into datacenters
    node_cpu: np.ndarray  # (N,) MHz
    node_mem: np.ndarray  # (N,) MB
    node_disk: np.ndarray  # (N,) MB
    reserved: tuple  # (cpu, mem, disk) held back on every node
    alloc_node: np.ndarray  # (A,) node index of each resident alloc
    alloc_cpu: np.ndarray  # (A,)
    alloc_mem: np.ndarray  # (A,)
    alloc_disk: int  # MB of shared disk per resident alloc

    @property
    def n_nodes(self) -> int:
        return int(self.node_cpu.shape[0])

    @property
    def n_allocs(self) -> int:
        return int(self.alloc_node.shape[0])

    def node_id(self, i: int) -> str:
        return f"node-{i:05d}"


def make_world(config: dict, seed: int) -> World:
    """Draw the fleet from the configuration's shapes.  The seed picks
    which node gets which shape and where each resident alloc sits; it
    never changes how many there are."""
    fleet = config["fleet"]
    rng = np.random.default_rng([int(seed), 0x0F1EE7])
    n = int(fleet["nodes"])
    a = int(fleet["resident_allocs"])
    dcs = tuple(fleet["datacenters"])
    return World(
        datacenters=dcs,
        node_dc=rng.integers(0, len(dcs), n).astype(np.int64),
        node_cpu=rng.choice(np.asarray(fleet["node_cpu"]), n).astype(np.int64),
        node_mem=rng.choice(np.asarray(fleet["node_memory_mb"]), n).astype(
            np.int64
        ),
        node_disk=np.full(n, int(fleet["node_disk_mb"]), np.int64),
        reserved=(
            int(fleet["reserved_cpu"]),
            int(fleet["reserved_memory_mb"]),
            int(fleet["reserved_disk_mb"]),
        ),
        alloc_node=rng.integers(0, n, a).astype(np.int64),
        alloc_cpu=rng.choice(np.asarray(fleet["alloc_cpu"]), a).astype(
            np.int64
        ),
        alloc_mem=rng.choice(np.asarray(fleet["alloc_memory_mb"]), a).astype(
            np.int64
        ),
        alloc_disk=int(fleet["alloc_disk_mb"]),
    )
