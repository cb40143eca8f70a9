"""Task runner: drive one task through start/wait/restart
(reference client/allocrunner/taskrunner/task_runner.go:62, restart
policy logic in taskrunner/restarts/).
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

from ..structs import (
    RestartPolicy,
    Task,
    TaskState,
)
from .drivers import DriverPlugin, new_driver
from .drivers.base import RecoverableError, TaskConfig, TaskExitResult

TASK_STATE_PENDING = "pending"
TASK_STATE_RUNNING = "running"
TASK_STATE_DEAD = "dead"


class RestartTracker:
    """(reference client/allocrunner/taskrunner/restarts/restarts.go)"""

    def __init__(self, policy: RestartPolicy, batch: bool) -> None:
        self.policy = policy
        self.batch = batch
        self.count = 0
        self.start_time = time.time()

    def next_restart(self, result: TaskExitResult) -> Optional[float]:
        """Returns the delay before restarting, or None to stop."""
        now = time.time()
        if now - self.start_time > self.policy.interval_s:
            self.count = 0
            self.start_time = now
        # successful batch tasks never restart; services restart on any
        # exit per their policy
        if self.batch and result.successful():
            return None
        self.count += 1
        if self.count > self.policy.attempts:
            if self.policy.mode == "delay":
                self.count = 0
                self.start_time = now + self.policy.interval_s
                return self.policy.interval_s
            return None
        return self.policy.delay_s


class TaskRunner:
    def __init__(
        self,
        alloc_id: str,
        task: Task,
        restart_policy: RestartPolicy,
        batch: bool,
        alloc_dir: str = "",
        env: Optional[Dict[str, str]] = None,
        on_state_change: Optional[Callable[[str, TaskState], None]] = None,
        driver: Optional[DriverPlugin] = None,
        secrets=None,
        catalog=None,
        task_dir=None,
        task_env=None,
        payload: bytes = b"",
        extra_env: Optional[Dict[str, str]] = None,
    ) -> None:
        self.secrets = secrets
        self.catalog = catalog
        self.alloc_id = alloc_id
        self.task = task
        self.alloc_dir = alloc_dir
        # allocdir layout (client/allocdir) + resolved env
        # (client/taskenv); optional — tests drive runners bare
        self.task_dir = task_dir
        self.task_env = task_env
        # dispatch payload blob (structs.go DispatchPayloadConfig) +
        # env injected by device reservations (devices.py)
        self.payload = payload
        self.extra_env = extra_env or {}
        self.env = env or {}
        self.driver = driver or new_driver(task.driver)
        self.restarts = RestartTracker(restart_policy, batch)
        self.state = TaskState(state=TASK_STATE_PENDING)
        self.on_state_change = on_state_change
        self.task_id = f"{alloc_id[:8]}-{task.name}"
        self._kill = threading.Event()
        # user-initiated restart in flight: the next task exit loops
        # straight back to start without charging the restart policy
        # (reference taskrunner Restart() vs. restart tracker)
        self._user_restart = threading.Event()
        self._done = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.exit_result: Optional[TaskExitResult] = None

    # ------------------------------------------------------------------

    def _set_state(self, state: str, failed: bool = False, event: str = ""):
        self.state.state = state
        self.state.failed = self.state.failed or failed
        if state == TASK_STATE_RUNNING and not self.state.started_at:
            self.state.started_at = time.time()
        if state == TASK_STATE_DEAD:
            self.state.finished_at = time.time()
        if event:
            self.state.events.append(
                {"type": event, "time": time.time()}
            )
        if self.on_state_change is not None:
            self.on_state_change(self.task.name, self.state)

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.run, name=f"task-{self.task_id}", daemon=True
        )
        self._thread.start()

    def run(self) -> None:
        """Start/wait/restart loop (reference task_runner.go:446 Run)."""
        try:
            # pre-start hooks, in the reference's taskrunner hook order:
            # dispatch_payload -> artifacts -> template
            if not self._prestart_hooks():
                return
            # render template blocks into the alloc dir before the first
            # start (reference taskrunner/template hook)
            if self.task.templates and self.alloc_dir:
                from .templates import render_task_templates

                try:
                    render_task_templates(
                        self.task.templates,
                        self.alloc_dir,
                        env={**self.env, **self.task.env},
                        meta=self.task.meta,
                        secrets=self.secrets,
                        catalog=self.catalog,
                    )
                except Exception as exc:  # noqa: BLE001
                    self.exit_result = TaskExitResult(
                        exit_code=-1, err=str(exc)
                    )
                    self._set_state(
                        TASK_STATE_DEAD, failed=True,
                        event="Template Failed",
                    )
                    return
            while not self._kill.is_set():
                config = dict(self.task.config)
                env = {**self.env, **self.task.env, **self.extra_env}
                if self.task_env is not None:
                    # ${...} interpolation over driver config
                    # (reference taskenv ParseAndReplace on the config);
                    # builder values win over the legacy flat env —
                    # they carry the allocdir-layout paths
                    config = self.task_env.replace_all(config)
                    env = {**env, **self.task_env.all()}
                # connect sidecar: resolve upstream targets from the
                # service catalog at launch (reference: upstreams are
                # rendered into the Envoy bootstrap at sidecar start)
                if config.get("connect_upstreams") is not None:
                    # the in-tree proxy runs `python -m
                    # nomad_tpu.client.connect` from the task dir:
                    # use THIS client's interpreter + package (the
                    # server that injected the task may live on a
                    # different host/venv in networked clusters)
                    import os as _os
                    import sys as _sys

                    import nomad_tpu as _pkg

                    config["command"] = _sys.executable
                    _root = _os.path.dirname(
                        _os.path.dirname(_pkg.__file__)
                    )
                    _prev = env.get(
                        "PYTHONPATH",
                        _os.environ.get("PYTHONPATH", ""),
                    )
                    env["PYTHONPATH"] = (
                        f"{_root}{_os.pathsep}{_prev}"
                        if _prev
                        else _root
                    )
                    # sidecar proxies never need an accelerator: a
                    # task helper must never claim the scheduler's chip
                    from ..backend import scrub_accelerator_env

                    env = scrub_accelerator_env(env)
                for item in config.get("connect_upstreams") or []:
                    dest, _port = item[0], item[1]
                    # brief launch-time wait: the upstream's alloc is
                    # usually seconds behind; blocking here beats
                    # bouncing the proxy through restart backoff
                    deadline = time.time() + 10.0
                    target = self._resolve_upstream(dest)
                    while not target and time.time() < deadline:
                        if self._kill.wait(0.25):
                            break
                        target = self._resolve_upstream(dest)
                    if target:
                        from .connect import env_key

                        env[
                            f"NOMAD_CONNECT_TARGET_{env_key(dest)}"
                        ] = target
                cfg = TaskConfig(
                    id=self.task_id,
                    name=self.task.name,
                    alloc_id=self.alloc_id,
                    config=config,
                    env=env,
                    alloc_dir=self.alloc_dir,
                    task_dir=(
                        self.task_dir.local_dir if self.task_dir else ""
                    ),
                    logs_dir=(
                        self.task_dir.log_dir if self.task_dir else ""
                    ),
                    log_max_files=self.task.log_max_files,
                    log_max_file_size_mb=self.task.log_max_file_size_mb,
                    resources=self.task.resources,
                )
                try:
                    handle = self.driver.start_task(cfg)
                except RecoverableError as exc:
                    result = TaskExitResult(exit_code=-1, err=str(exc))
                    self._set_state(
                        TASK_STATE_PENDING, event="Driver Failure"
                    )
                    if not self._maybe_restart(result):
                        return
                    continue
                except Exception as exc:  # noqa: BLE001
                    self.exit_result = TaskExitResult(
                        exit_code=-1, err=str(exc)
                    )
                    self._set_state(
                        TASK_STATE_DEAD, failed=True,
                        event="Driver Failure",
                    )
                    return

                self._set_state(TASK_STATE_RUNNING, event="Started")

                # wait for exit or kill
                result = None
                while result is None and not self._kill.is_set():
                    result = self.driver.wait_task(self.task_id, timeout=0.1)
                if self._kill.is_set():
                    self.driver.stop_task(
                        self.task_id, timeout=self.task.kill_timeout_s
                    )
                    result = self.driver.wait_task(self.task_id, 1.0)
                    self.exit_result = result
                    self._set_state(TASK_STATE_DEAD, event="Killed")
                    return

                self.exit_result = result
                if self._user_restart.is_set():
                    self._user_restart.clear()
                    self._set_state(
                        TASK_STATE_PENDING, event="Restart Signaled"
                    )
                    continue
                if not self._maybe_restart(result):
                    return
        finally:
            # terminal teardown: release driver-side task resources
            # (reference taskrunner DestroyTask in the cleanup hooks);
            # executor-backed drivers shut their per-task executor here
            try:
                self.driver.destroy_task(self.task_id, force=True)
            except Exception:  # noqa: BLE001
                pass
            self._done.set()

    def _resolve_upstream(self, dest: str) -> str:
        """First healthy instance of a service, as host:port (reference
        resolves upstreams through Consul's catalog)."""
        if self.catalog is None:
            return ""
        try:
            instances = self.catalog.instances(dest, healthy_only=True)
        except Exception:  # noqa: BLE001
            return ""
        for inst in instances:
            if inst.port:
                return f"{inst.address or '127.0.0.1'}:{inst.port}"
        return ""

    def _prestart_hooks(self) -> bool:
        """Dispatch-payload + artifact hooks (reference
        taskrunner/dispatch_hook.go, artifact_hook.go).  Returns False
        when setup failed and the task must not start."""
        base = (
            self.task_dir.local_dir
            if self.task_dir is not None
            else self.alloc_dir
        )
        if self.payload and self.task.dispatch_payload_file and base:
            import os

            from .getter import contained_path

            try:
                path = contained_path(
                    base, self.task.dispatch_payload_file
                )
            except ValueError:
                self.exit_result = TaskExitResult(
                    exit_code=-1,
                    err="dispatch_payload_file escapes the task dir",
                )
                self._set_state(
                    TASK_STATE_DEAD, failed=True,
                    event="Failed Payload Write",
                )
                return False
            os.makedirs(os.path.dirname(path) or base, exist_ok=True)
            with open(path, "wb") as f:
                f.write(self.payload)
        if self.task.artifacts and base:
            from .getter import ArtifactError, fetch_all

            artifacts = self.task.artifacts
            if self.task_env is not None:
                artifacts = self.task_env.replace_all(artifacts)
            try:
                fetch_all(artifacts, base)
            except ArtifactError as exc:
                self.exit_result = TaskExitResult(
                    exit_code=-1, err=str(exc)
                )
                self._set_state(
                    TASK_STATE_DEAD, failed=True,
                    event="Failed Artifact Download",
                )
                return False
        return True

    def _maybe_restart(self, result: TaskExitResult) -> bool:
        delay = self.restarts.next_restart(result)
        if delay is None:
            self._set_state(
                TASK_STATE_DEAD,
                failed=not result.successful(),
                event="Terminated",
            )
            return False
        self._set_state(
            TASK_STATE_PENDING, event="Restarting"
        )
        # interruptible sleep
        if self._kill.wait(delay):
            self._set_state(TASK_STATE_DEAD, event="Killed")
            return False
        return True

    # ------------------------------------------------------------------

    def restart(self) -> None:
        """User-initiated in-place restart: stop the process; the run
        loop relaunches it without consuming restart-policy attempts."""
        if not self.is_running():
            return
        self._user_restart.set()
        self.driver.stop_task(
            self.task_id, timeout=self.task.kill_timeout_s
        )

    def kill(self) -> None:
        self._kill.set()
        # a runner killed before start() would otherwise never signal
        # done and wedge anything waiting on it
        if self._thread is None:
            self._done.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def is_running(self) -> bool:
        return self.state.state == TASK_STATE_RUNNING
