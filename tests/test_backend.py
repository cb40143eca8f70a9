"""Device selection and launch plumbing (nomad_tpu/backend.py, the
supervisor's view of the resolved backend, the compile-failure
counter) and chip_smoke.py's platform gate.

What decides is what JAX resolved — faked here both ways — never how
``JAX_PLATFORMS`` is spelt.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import pytest

from nomad_tpu import backend as backend_mod
from nomad_tpu import mock
from nomad_tpu.backend import Backend
from nomad_tpu.device import CPU_ONLY, HEALTHY, DeviceSupervisor
from nomad_tpu.server import Server
from nomad_tpu.telemetry import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPU = Backend(platform="tpu", device_kind="TPU v5 lite", device_count=1)
CPU = Backend(platform="cpu", device_kind="cpu", device_count=8)


# -- resolve_backend -----------------------------------------------------


def test_resolve_backend_reports_what_jax_initialised():
    got = backend_mod.resolve_backend()
    devices = jax.devices()
    assert got == Backend(
        devices[0].platform, devices[0].device_kind, len(devices)
    )
    assert got.platform == "cpu" and not got.accelerated
    assert TPU.accelerated


# -- supervision follows the resolved backend ----------------------------


@pytest.mark.parametrize("spelling", [None, "cpu", "tpu", "tpu,cpu"])
def test_supervision_follows_resolved_backend_not_env(
    monkeypatch, spelling
):
    """A resolved accelerator turns supervision on and a resolved CPU
    leaves it idle, whatever JAX_PLATFORMS holds (on a TPU host JAX
    picks the chip with the variable unset)."""
    if spelling is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", spelling)
    on = DeviceSupervisor(metrics=Metrics(), backend=TPU)
    assert on.expected and on.state() == HEALTHY
    status = on.status()
    assert status["enabled"] is True
    assert status["platform"] == "tpu"
    assert status["device_kind"] == "TPU v5 lite"
    assert status["device_count"] == 1
    assert status["backend"] == "tpu"

    off = DeviceSupervisor(metrics=Metrics(), backend=CPU)
    assert not off.expected and off.state() == CPU_ONLY
    status = off.status()
    assert status["enabled"] is False
    assert status["platform"] == "cpu"
    assert status["device_count"] == 8
    assert status["backend"] == "cpu"


def test_supervisor_without_a_kernel_path_names_no_backend():
    """A sequential-oracle server never resolves a backend: the
    payload must not claim a device."""
    sup = DeviceSupervisor(metrics=Metrics())
    assert not sup.expected
    status = sup.status()
    assert status["platform"] is None
    assert status["device_count"] == 0
    assert status["backend"] == "none"


def test_supervisor_knob_and_fault_plan_keep_their_meaning(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_SUPERVISOR", "0")
    assert not DeviceSupervisor(metrics=Metrics(), backend=TPU).expected
    monkeypatch.setenv("NOMAD_TPU_SUPERVISOR", "1")
    assert DeviceSupervisor(metrics=Metrics(), backend=CPU).expected
    monkeypatch.delenv("NOMAD_TPU_SUPERVISOR")
    monkeypatch.setenv("NOMAD_TPU_FAULT", "flaky:1")
    sup = DeviceSupervisor(metrics=Metrics(), backend=CPU)
    assert sup.expected
    sup.stop()


def test_failed_over_backend_reads_cpu():
    sup = DeviceSupervisor(
        metrics=Metrics(), backend=TPU, canary=lambda: 1.0
    )
    sup.trip("launch")
    status = sup.status()
    assert status["backend"] == "cpu" and status["platform"] == "tpu"
    assert status["failover_count"] == 1
    sup.stop()


def test_server_resolves_backend_only_for_the_batch_pipeline():
    bat = Server(num_schedulers=1, batch_pipeline=True)
    seq = Server(num_schedulers=1, batch_pipeline=False)
    try:
        assert bat.device_supervisor.backend == (
            backend_mod.resolve_backend()
        )
        assert bat.device_supervisor.status()["platform"] == "cpu"
        assert seq.device_supervisor.backend is None
    finally:
        bat.stop()
        seq.stop()


def test_device_endpoint_names_the_resolved_backend():
    from nomad_tpu.api import start_http_server

    server = Server(num_schedulers=1, batch_pipeline=True)
    server.start()
    http = start_http_server(server, port=0)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{http.port}/v1/device"
        ) as resp:
            body = json.loads(resp.read())
        assert body["platform"] == "cpu"
        assert body["device_kind"] == jax.devices()[0].device_kind
        assert body["device_count"] == len(jax.devices())
        assert body["backend"] == "cpu"
        assert body["enabled"] is False
    finally:
        http.stop()
        server.stop()


def test_donation_follows_resolved_backend(monkeypatch):
    server = Server(num_schedulers=1, batch_pipeline=True)
    try:
        worker = server.workers[0]
        assert worker._donation_enabled() is False
        worker._donate_carries = None
        monkeypatch.setattr(backend_mod, "resolve_backend", lambda: TPU)
        assert worker._donation_enabled() is True
    finally:
        server.stop()


# -- compile cache -------------------------------------------------------


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_a_set_variable_alone(
    monkeypatch, restore_cache_dir
):
    jax.config.update("jax_compilation_cache_dir", "/operator/choice")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/operator/choice")
    assert backend_mod.ensure_compile_cache() == "/operator/choice"
    assert jax.config.jax_compilation_cache_dir == "/operator/choice"


def test_compile_cache_defaults_beside_the_checkout(
    monkeypatch, restore_cache_dir, tmp_path
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)  # independent of cwd
    want = os.path.join(REPO, ".jax_cache")
    assert backend_mod.ensure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_kernel_import_places_the_compile_cache():
    """Importing the kernel package — what every jitting path does
    first — is what places the cache, from any working directory."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = (
        "import nomad_tpu.ops, jax; "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd="/",
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == os.path.join(REPO, ".jax_cache")
    env["JAX_COMPILATION_CACHE_DIR"] = "/operator/choice"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd="/",
        capture_output=True, text=True, timeout=120,
    )
    assert out.stdout.strip() == "/operator/choice"


# -- task helpers never claim the chip ------------------------------------


def test_task_env_is_pinned_to_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = backend_mod.scrub_accelerator_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"  # a copy, not a mutation
    base = {"PATH": "/bin"}
    assert backend_mod.scrub_accelerator_env(base) == {
        "PATH": "/bin", "JAX_PLATFORMS": "cpu",
    }
    assert base == {"PATH": "/bin"}


# -- the shield's failed compiles are countable ---------------------------


def test_background_compile_failure_is_counted(monkeypatch):
    """A kernel the compiler refuses parks its launch shape on the
    host path for good; that must show on /v1/metrics, not only as one
    log line."""
    monkeypatch.delenv("NOMAD_TPU_SYNC_COMPILE", raising=False)
    server = Server(num_schedulers=1, seed=3, batch_pipeline=True)
    try:
        server.register_node(mock.node())
        worker = server.workers[0]

        def refuses(*_a, **_k):
            raise RuntimeError("compiler says no")

        refuses.__name__ = "refused_kernel"
        assert not worker._launch_ready((1,), {}, fn=refuses)
        deadline = time.monotonic() + 30.0
        while worker._compiling and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not worker._compiling
        assert worker.compile_failures == 1
        assert len(worker._compile_failed) == 1
        counters = server.metrics.dump()["counters"]
        assert counters["batch_worker.compile_failures"] == 1.0
        # parked: asking again neither retries nor recounts
        assert not worker._launch_ready((1,), {}, fn=refuses)
        assert worker.compile_failures == 1
        assert not any(
            t.name == "kernel-compile" for t in threading.enumerate()
        )
    finally:
        server.stop()


# -- the mesh that does not form says so ----------------------------------


def test_requested_mesh_that_fails_is_logged(monkeypatch, caplog):
    import nomad_tpu.parallel.mesh as mesh_mod

    monkeypatch.setenv("NOMAD_TPU_MESH", "1")

    def boom(*_a, **_k):
        raise RuntimeError("no mesh for you")

    monkeypatch.setattr(mesh_mod, "make_mesh", boom)
    with caplog.at_level("WARNING", logger="nomad_tpu.server.batch_worker"):
        server = Server(num_schedulers=1, batch_pipeline=True)
    try:
        assert server.workers[0]._mesh is None
        assert any(
            "mesh did not form" in r.getMessage()
            and r.exc_info is not None
            for r in caplog.records
        )
    finally:
        server.stop()


def test_make_mesh_substitutes_no_other_backend():
    from nomad_tpu.parallel.mesh import make_mesh

    have = len(jax.devices())
    mesh = make_mesh(have * 4, eval_axis=1)
    assert mesh.devices.size == have
    assert {d.platform for d in mesh.devices.flat} == {"cpu"}


# -- chip_smoke.py refuses the CPU ----------------------------------------


def test_chip_smoke_exits_nonzero_without_a_tpu():
    """Wherever JAX finds no TPU the smoke fails fast — before seeding
    anything — says what it found, and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode not in (0, None)
    assert time.monotonic() - t0 < 60.0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
    assert "JAX_PLATFORMS seen='cpu'" in out.stderr
    assert "world:" not in out.stderr  # nothing was seeded
