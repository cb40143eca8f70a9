"""Which JAX backend this process is on, and the process-level JAX
settings that go with it.

A chip belongs to one process: libtpu refuses a second claimant with a
prompt ``RuntimeError`` ("Unable to initialize backend 'tpu'"), so there
is no cross-process lock here — one JAX process per chip is the rule,
and a co-located client agent runs with its TPU fingerprint off or with
``JAX_PLATFORMS=cpu``.

Nothing in this module reads how ``JAX_PLATFORMS`` is spelt to decide
whether an accelerator is present: ``resolve_backend`` asks the
initialised backend.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

# default persistent compile cache, beside the package: a fixed path
# (the path is part of the cache key) that works in a plain copy of the
# tree as well as in a git checkout
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


class Backend(NamedTuple):
    """The default JAX backend as the runtime reports it."""

    platform: str  # jax.devices()[0].platform: "tpu" | "cpu" | ...
    device_kind: str  # jax.devices()[0].device_kind
    device_count: int  # len(jax.devices())

    @property
    def accelerated(self) -> bool:
        return self.platform != "cpu"


@functools.cache
def resolve_backend() -> Backend:
    """Initialise JAX's default backend (the first call pays PJRT
    init — seconds on a TPU) and report it.  Raises what JAX raises
    when an explicitly requested platform cannot initialise; memoized,
    like the backend itself, for the life of the process.

    A multi-host world (NOMAD_TPU_DIST_*) must be joined before the
    backend exists, so that happens here first (no-op when unset)."""
    import jax

    from .parallel.mesh import distributed_init

    distributed_init()
    devices = jax.devices()
    return Backend(
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
        device_count=len(devices),
    )


def ensure_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable and
    return the directory.  ``JAX_COMPILATION_CACHE_DIR`` is the
    operator's choice and JAX reads it by itself — then nothing is
    touched; otherwise the cache lives in ``<checkout>/.jax_cache``.
    JAX's own thresholds (minimum compile time / entry size) stay."""
    configured = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if configured:
        return configured
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR


def scrub_accelerator_env(base: Optional[dict] = None) -> dict:
    """Environment for task-runtime subprocesses (executors, sidecar
    proxies, logmon): pinned to the CPU backend, so a helper that
    imports JAX can never claim the scheduler's chip."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    return env
