"""Test configuration: force the CPU backend with a virtual 8-device mesh
and 64-bit floats BEFORE jax is imported, so sharding tests run without
real multi-chip hardware and parity tests are bit-exact against the
float64 host oracle (SURVEY.md section 7.3)."""
import os

# hard-set (not setdefault): a shell that exports JAX_PLATFORMS for an
# accelerator must not leak into the test suite — the suite's parity
# contract is the x64 CPU backend with a virtual 8-device mesh
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")
# cold kernel compiles block (instead of falling back to the
# sequential path while compiling in the background) so prescore-rate
# assertions are deterministic
os.environ["NOMAD_TPU_SYNC_COMPILE"] = "1"
# this sandbox's scheduler can park a timed wait far past its timeout;
# the broker's opt-in notify watchdog bounds the damage
os.environ["NOMAD_TPU_BROKER_WATCHDOG"] = "1"
# hermetic: the suite neither reads nor fills the persistent compile
# cache the kernels otherwise keep in <checkout>/.jax_cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

# the same pins through jax.config, which also hold if something
# imported jax before the env vars above took effect
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import random  # noqa: E402

import pytest  # noqa: E402

from nomad_tpu import mock  # noqa: E402
from nomad_tpu.sched.testing import Harness  # noqa: E402
from nomad_tpu.structs import compute_node_class  # noqa: E402


@pytest.fixture
def harness():
    return Harness()


def heterogeneous_cluster(
    harness: Harness,
    n_nodes: int,
    seed: int = 0,
    datacenters=("dc1", "dc2"),
    racks: int = 5,
):
    rng = random.Random(seed)
    nodes = []
    for _ in range(n_nodes):
        n = mock.node()
        n.node_resources.cpu = rng.choice([2000, 4000, 8000])
        n.node_resources.memory_mb = rng.choice([4096, 8192, 16384])
        n.datacenter = rng.choice(list(datacenters))
        n.attributes["rack"] = f"r{rng.randint(0, racks - 1)}"
        n.attributes["driver.docker"] = rng.choice(["1", "1", "1", "0"])
        n.attributes["os.version"] = rng.choice(["20.04", "22.04", "24.04"])
        n.computed_class = compute_node_class(n)
        harness.store.upsert_node(n)
        nodes.append(n)
    return nodes

