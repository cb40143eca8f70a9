"""Task executor: an isolated-process runtime for exec/raw_exec tasks
(reference drivers/shared/executor — the exec driver runs every task
under a *separate executor process* with libcontainer isolation
(executor_linux.go: chroot, namespaces, cgroups), speaking gRPC over
the go-plugin seam, and reattaches to it across client restarts).

This is the TPU-build equivalent over our framed wire protocol
(nomad_tpu/wire.py, the seam native/wire.cpp implements natively):

* **Executor process** — ``python -m nomad_tpu.client.executor`` binds
  a unix socket, prints the go-plugin-style handshake line
  ``1|1|unix|<socket>|wire`` and serves Launch/Wait/Signal/Stop/
  Destroy/Stats/ListTasks/Shutdown.  It owns the task subprocesses, so
  a driver (or whole client) restart cannot kill them.
* **Isolation** (applied in the child between fork and exec, the same
  window libcontainer uses):
    - private mount namespace (``unshare(CLONE_NEWNS)``),
    - ``chroot`` into the task sandbox, populated by hardlink (no data
      copied) from either a directory map (reference chroot_env) or
      the command's ldd closure (``link_command_env``),
    - cgroup cpu/memory limits — v1 and v2 hierarchies supported; the
      child enrolls *itself* before exec so no spawn escapes the
      limits,
    - own session (setsid) so stop/kill signals the whole tree.
  Each knob degrades gracefully (non-root, read-only cgroupfs): the
  task still runs, `launch` reports which isolations engaged.
* **Reattach** — the driver persists ``{socket, pid, task_id}`` per
  task (reference's ReattachConfig); `ExecutorClient.reconnect` dials
  the still-running executor after a restart and adopts the task.
"""
from __future__ import annotations

import json
import os
import shutil
import signal as _signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..wire import call, decode, encode, recv_frame, send_frame

HANDSHAKE = "1|1|unix|{path}|wire"

def _default_state_dir() -> str:
    """Reattach-record dir (reference: client state DB's driver handle
    blobs).  Never a predictable world-writable path: root uses /run,
    everyone else their home dir, both created 0700 and ownership-
    checked before any record is trusted."""
    if os.geteuid() == 0 and os.path.isdir("/run"):
        return "/run/nomad-tpu/executors"
    return os.path.join(
        os.path.expanduser("~"), ".nomad_tpu", "executors"
    )


STATE_DIR = os.environ.get(
    "NOMAD_TPU_EXECUTOR_STATE", _default_state_dir()
)


def _state_dir_trusted(path: str) -> bool:
    """Reject a records dir another user could have planted: it must
    belong to us and admit no group/other writes."""
    try:
        st = os.stat(path)
    except OSError:
        return False
    return st.st_uid == os.geteuid() and not (st.st_mode & 0o022)

CGROUP_ROOT = "/sys/fs/cgroup"
CGROUP_PARENT = "nomad_tpu"

# mount(2) flags for the bind-mounted sandbox
MS_RDONLY = 0x1
MS_REMOUNT = 0x20
MS_BIND = 0x1000
MS_REC = 0x4000
MS_PRIVATE = 0x40000

# system dirs bind-mounted read-only into a "bind"-populated sandbox
# (reference executor's default chroot env: /bin /etc /lib /lib64
# /sbin /usr — here as private bind mounts instead of file copies)
BIND_DIRS = ("/usr", "/etc", "/bin", "/sbin", "/lib", "/lib64")


def _libc():
    import ctypes

    return ctypes.CDLL(None, use_errno=True)


def _mount(source: bytes, target: bytes, fstype: bytes,
           flags: int) -> int:
    import ctypes

    libc = _libc()
    res = libc.mount(source, target, fstype, flags, None)
    return 0 if res == 0 else ctypes.get_errno()


# ---------------------------------------------------------------------------
# chroot population
# ---------------------------------------------------------------------------


def _link_tree(src: str, dest: str) -> None:
    """Mirror src into dest by hardlink (fallback: copy), preserving
    symlinks — the no-data-copied analog of the reference's chroot dir
    copy (client/allocdir/task_dir_linux.go)."""
    if os.path.islink(src):
        target = os.readlink(src)
        os.makedirs(os.path.dirname(dest), exist_ok=True)
        if not os.path.lexists(dest):
            os.symlink(target, dest)
        return
    if os.path.isfile(src):
        os.makedirs(os.path.dirname(dest), exist_ok=True)
        if os.path.lexists(dest):
            return
        try:
            os.link(src, dest)
        except OSError:
            shutil.copy2(src, dest)
        return
    for root, dirs, files in os.walk(src):
        rel = os.path.relpath(root, src)
        troot = dest if rel == "." else os.path.join(dest, rel)
        os.makedirs(troot, exist_ok=True)
        for d in list(dirs):
            sp = os.path.join(root, d)
            if os.path.islink(sp):
                dirs.remove(d)
                tp = os.path.join(troot, d)
                if not os.path.lexists(tp):
                    os.symlink(os.readlink(sp), tp)
        for f in files:
            sp, tp = os.path.join(root, f), os.path.join(troot, f)
            if os.path.lexists(tp):
                continue
            try:
                if os.path.islink(sp):
                    os.symlink(os.readlink(sp), tp)
                else:
                    os.link(sp, tp)
            except OSError:
                try:
                    shutil.copy2(sp, tp, follow_symlinks=False)
                except OSError:
                    pass


def prepare_bind_sandbox(dest: str) -> List[str]:
    """Create mount points mirroring the host's top-level layout
    (merged-usr symlinks preserved) and return the real dirs to
    bind-mount.  The mounts themselves happen in the child's private
    mount namespace (`_enter_bind_sandbox`), so nothing leaks to the
    host and teardown is automatic when the task's namespace dies —
    the reference gets the same from libcontainer's rootfs setup."""
    os.makedirs(dest, exist_ok=True)
    binds: List[str] = []
    for d in BIND_DIRS:
        if not os.path.exists(d):
            continue
        name = d.lstrip("/")
        target = os.path.join(dest, name)
        if os.path.islink(d):
            # e.g. /bin -> usr/bin: replicate the symlink; the /usr
            # bind covers its content
            if not os.path.lexists(target):
                os.symlink(os.readlink(d), target)
            continue
        os.makedirs(target, exist_ok=True)
        binds.append(d)
    for d in ("tmp", "dev", "proc", "alloc", "local", "secrets"):
        os.makedirs(os.path.join(dest, d), exist_ok=True)
    return binds


def _mount_task_dirs(
    chroot: str, mounts: List[Tuple[str, str]]
) -> None:
    """Bind the task-dir contract dirs (shared alloc, local, secrets)
    read-write into the sandbox so NOMAD_ALLOC_DIR/NOMAD_TASK_DIR/
    NOMAD_SECRETS_DIR resolve in-chroot (the reference bind-mounts the
    alloc dir into the chroot — alloc_dir_linux.go mountSharedDir).
    A failed bind ABORTS the launch: the parent already remapped the
    env vars to the in-chroot paths, so proceeding would silently
    write shared data into a private dir."""
    for host, rel in mounts:
        target = os.path.join(chroot, rel).encode()
        err = _mount(host.encode(), target, b"", MS_BIND)
        if err != 0:
            raise OSError(
                err, f"bind {host} -> /{rel} failed", host
            )


def _enter_bind_sandbox(
    chroot: str,
    binds: List[str],
    task_mounts: Optional[List[Tuple[str, str]]] = None,
) -> None:
    """Child-side (post-unshare(NEWNS), pre-exec): make mounts
    private, bind the system dirs read-only, mount /proc, chroot."""
    _mount(b"none", b"/", b"", MS_REC | MS_PRIVATE)
    for d in binds:
        target = os.path.join(chroot, d.lstrip("/")).encode()
        if _mount(d.encode(), target, b"", MS_BIND | MS_REC) == 0:
            # best-effort read-only remount of the bind
            _mount(b"none", target, b"",
                   MS_BIND | MS_REMOUNT | MS_RDONLY | MS_REC)
    # tasks need real device nodes (/dev/null, /dev/urandom, ...):
    # bind the host /dev read-write (reference libcontainer creates
    # the default device set in the rootfs)
    _mount(b"/dev", os.path.join(chroot, "dev").encode(), b"",
           MS_BIND | MS_REC)
    _mount(b"proc", os.path.join(chroot, "proc").encode(), b"proc", 0)
    if task_mounts:
        _mount_task_dirs(chroot, task_mounts)
    os.chroot(chroot)
    os.chdir("/")


def build_chroot(dest: str, env: Dict[str, str]) -> None:
    """Populate a chroot from a {source: dest-rel} map (reference
    executor's chroot_env / drivers.exec `chroot_env` config)."""
    os.makedirs(dest, exist_ok=True)
    for src, rel in env.items():
        if not os.path.lexists(src):
            continue
        target = os.path.join(dest, rel.lstrip("/"))
        _link_tree(src, target)
    for d in ("tmp", "dev", "proc"):
        os.makedirs(os.path.join(dest, d), exist_ok=True)


def link_command_env(dest: str, argv0: str) -> Dict[str, str]:
    """Minimal chroot env for one command: the binary plus its ldd
    closure (dynamic loader included).  Returns the map passed to
    build_chroot — a TPU-build refinement over copying whole /bin:/lib
    trees; callers wanting the reference's full default can pass their
    own map."""
    def chain(path: str) -> List[str]:
        # a path plus every hop of its symlink chain, so the chroot
        # reproduces e.g. /bin/sh -> dash -> (hardlinked file)
        out, p, hops = [], path, 0
        while hops < 16:
            out.append(p)
            if not os.path.islink(p):
                break
            p = os.path.normpath(
                os.path.join(os.path.dirname(p), os.readlink(p))
            )
            hops += 1
        return out

    env: Dict[str, str] = {}
    for p in chain(argv0):
        env[p] = p
    try:
        out = subprocess.run(
            ["ldd", argv0], capture_output=True, text=True, timeout=10
        ).stdout
    except (OSError, subprocess.TimeoutExpired):
        out = ""
    for line in out.splitlines():
        for tok in line.split():
            if tok.startswith("/") and os.path.exists(tok):
                for p in chain(tok):
                    env[p] = p
    return env


# ---------------------------------------------------------------------------
# cgroups (v1 + v2)
# ---------------------------------------------------------------------------


class CgroupSlice:
    """Per-task cgroup with cpu/memory limits.  The child writes its
    own pid into cgroup.procs pre-exec, so the whole task tree is
    enrolled from the first instruction (reference executor_linux.go
    configureCgroups via libcontainer)."""

    def __init__(self, task_id: str, cpu_shares: int = 0,
                 memory_mb: int = 0) -> None:
        self.task_id = task_id
        self.cpu_shares = int(cpu_shares)
        self.memory_mb = int(memory_mb)
        self.paths: List[str] = []
        self.v2 = os.path.exists(
            os.path.join(CGROUP_ROOT, "cgroup.controllers")
        )

    @staticmethod
    def _enable_v2_controllers() -> None:
        """cgroup v2 leaves only expose memory.max/cpu.weight when every
        ancestor delegates the controllers via cgroup.subtree_control."""
        for parent in (
            CGROUP_ROOT,
            os.path.join(CGROUP_ROOT, CGROUP_PARENT),
        ):
            ctl = os.path.join(parent, "cgroup.subtree_control")
            try:
                with open(ctl, "w") as f:
                    f.write("+memory +cpu")
            except OSError:
                pass

    def create(self) -> bool:
        try:
            if self.v2:
                os.makedirs(
                    os.path.join(CGROUP_ROOT, CGROUP_PARENT),
                    exist_ok=True,
                )
                self._enable_v2_controllers()
                path = os.path.join(
                    CGROUP_ROOT, CGROUP_PARENT, self.task_id
                )
                os.makedirs(path, exist_ok=True)
                if self.memory_mb:
                    self._write(
                        os.path.join(path, "memory.max"),
                        str(self.memory_mb * 1024 * 1024),
                    )
                if self.cpu_shares:
                    # v2 weight 1..10000; map shares/1024 -> 100
                    weight = max(
                        1, min(10000, self.cpu_shares * 100 // 1024)
                    )
                    self._write(
                        os.path.join(path, "cpu.weight"), str(weight)
                    )
                self.paths = [path]
                return True
            ok = False
            if self.memory_mb:
                path = os.path.join(
                    CGROUP_ROOT, "memory", CGROUP_PARENT, self.task_id
                )
                os.makedirs(path, exist_ok=True)
                self._write(
                    os.path.join(path, "memory.limit_in_bytes"),
                    str(self.memory_mb * 1024 * 1024),
                )
                self.paths.append(path)
                ok = True
            if self.cpu_shares:
                path = os.path.join(
                    CGROUP_ROOT, "cpu", CGROUP_PARENT, self.task_id
                )
                os.makedirs(path, exist_ok=True)
                self._write(
                    os.path.join(path, "cpu.shares"),
                    str(self.cpu_shares),
                )
                self.paths.append(path)
                ok = True
            return ok
        except OSError:
            self.destroy()
            return False

    @staticmethod
    def _write(path: str, value: str) -> None:
        with open(path, "w") as f:
            f.write(value)

    def enroll_self(self) -> None:
        """Called in the child pre-exec."""
        pid = str(os.getpid())
        for path in self.paths:
            try:
                self._write(os.path.join(path, "cgroup.procs"), pid)
            except OSError:
                pass

    def stats(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for path in self.paths:
            for fname, key, scale in (
                ("memory.current", "memory_rss_bytes", 1.0),
                ("memory.usage_in_bytes", "memory_rss_bytes", 1.0),
                ("cpuacct.usage", "cpu_total_ns", 1.0),
            ):
                fp = os.path.join(path, fname)
                if os.path.exists(fp):
                    try:
                        with open(fp) as f:
                            out[key] = float(f.read().strip())
                    except (OSError, ValueError):
                        pass
            stat = os.path.join(path, "cpu.stat")
            if self.v2 and os.path.exists(stat):
                try:
                    with open(stat) as f:
                        for line in f:
                            k, _, v = line.partition(" ")
                            if k == "usage_usec":
                                out["cpu_total_ns"] = float(v) * 1e3
                except (OSError, ValueError):
                    pass
        return out

    def destroy(self) -> None:
        for path in self.paths:
            procs = os.path.join(path, "cgroup.procs")
            try:
                with open(procs) as f:
                    for pid in f.read().split():
                        try:
                            os.kill(int(pid), _signal.SIGKILL)
                        except (ProcessLookupError, ValueError):
                            pass
            except OSError:
                pass
            for _ in range(10):
                try:
                    os.rmdir(path)
                    break
                except OSError:
                    time.sleep(0.05)
        self.paths = []


# ---------------------------------------------------------------------------
# the executor core
# ---------------------------------------------------------------------------


class _Task:
    def __init__(self, task_id: str, proc: subprocess.Popen,
                 cgroup: Optional[CgroupSlice], isolation: Dict) -> None:
        self.task_id = task_id
        self.proc = proc
        self.cgroup = cgroup
        self.isolation = isolation
        self.logmon = None
        self.exit: Optional[Dict] = None
        self.done = threading.Event()


class Executor:
    """In-process core; `serve` exposes it over the wire seam."""

    def __init__(self) -> None:
        self.tasks: Dict[str, _Task] = {}
        self._lock = threading.Lock()

    # -- launch --------------------------------------------------------

    def launch(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        task_id = spec["task_id"]
        argv = list(spec["argv"])
        cwd = spec.get("cwd") or None
        env = dict(spec.get("env") or {})
        isolation: Dict[str, Any] = {
            "chroot": False, "cgroups": False, "mount_ns": False,
        }

        can_unshare = os.geteuid() == 0 and hasattr(os, "unshare")
        chroot = spec.get("chroot") or ""
        binds: List[str] = []
        if chroot and os.geteuid() == 0:
            populate = spec.get("chroot_populate")
            if populate == "bind" or populate is None:
                if not can_unshare:
                    # without a private mount namespace the binds would
                    # land in the HOST mount table and outlive the
                    # task: refuse the sandbox rather than pollute
                    chroot = ""
                else:
                    binds = prepare_bind_sandbox(chroot)
            elif populate == "auto":
                build_chroot(chroot, link_command_env(chroot, argv[0]))
            elif isinstance(populate, dict) and populate:
                build_chroot(chroot, populate)
            isolation["chroot"] = True
        else:
            chroot = ""

        # task-dir contract: bind the shared alloc/local/secrets dirs
        # into the sandbox and remap the NOMAD_*_DIR env vars to the
        # in-chroot paths, so artifacts/templates/shared-data work
        # under the default chroot (reference alloc_dir_linux.go
        # mountSharedDir + taskenv's in-chroot paths)
        task_mounts: List[Tuple[str, str]] = []
        if (
            chroot
            and can_unshare
            and bool(spec.get("mount_ns", True))
        ):
            env_for_rel = {
                "alloc": "NOMAD_ALLOC_DIR",
                "local": "NOMAD_TASK_DIR",
                "secrets": "NOMAD_SECRETS_DIR",
            }
            for host, rel in spec.get("task_mounts") or []:
                rel = str(rel).strip("/")
                if not host or not os.path.isdir(host):
                    continue
                os.makedirs(os.path.join(chroot, rel), exist_ok=True)
                task_mounts.append((host, rel))
                var = env_for_rel.get(rel)
                if var and var in env:
                    env[var] = "/" + rel

        cgroup: Optional[CgroupSlice] = None
        if spec.get("use_cgroups", True) and (
            spec.get("cpu_shares") or spec.get("memory_mb")
        ):
            cgroup = CgroupSlice(
                task_id,
                cpu_shares=spec.get("cpu_shares", 0),
                memory_mb=spec.get("memory_mb", 0),
            )
            if cgroup.create():
                isolation["cgroups"] = True
            else:
                cgroup = None

        want_mnt_ns = bool(spec.get("mount_ns", True)) and can_unshare
        isolation["mount_ns"] = want_mnt_ns

        stdout = stderr = subprocess.DEVNULL
        use_logmon = bool(spec.get("logs_dir"))
        if use_logmon:
            # size-rotated logs, pumped by the executor itself — the
            # reference's executor pipes task output to logmon FIFOs
            # (drivers/shared/executor; client/logmon)
            stdout = stderr = subprocess.PIPE
        else:
            if spec.get("stdout_path"):
                os.makedirs(
                    os.path.dirname(spec["stdout_path"]), exist_ok=True
                )
                stdout = open(spec["stdout_path"], "ab")
            if spec.get("stderr_path"):
                os.makedirs(
                    os.path.dirname(spec["stderr_path"]), exist_ok=True
                )
                stderr = open(spec["stderr_path"], "ab")

        def pre_exec() -> None:
            # fork→exec window, the libcontainer init analog
            if cgroup is not None:
                cgroup.enroll_self()
            in_ns = False
            if want_mnt_ns:
                # fail closed for bind sandboxes: if we can't enter a
                # private namespace the binds would pollute the host,
                # so the raise below aborts the launch instead
                os.unshare(os.CLONE_NEWNS)
                in_ns = True
            if chroot:
                if binds:
                    if not in_ns:
                        raise OSError(
                            "bind sandbox requires a private mount "
                            "namespace"
                        )
                    _enter_bind_sandbox(chroot, binds, task_mounts)
                else:
                    if task_mounts and in_ns:
                        _mount(b"none", b"/", b"", MS_REC | MS_PRIVATE)
                        _mount_task_dirs(chroot, task_mounts)
                    os.chroot(chroot)
                    os.chdir("/")
            lim = spec.get("rlimit_nofile")
            if lim:
                import resource

                resource.setrlimit(
                    resource.RLIMIT_NOFILE, (int(lim), int(lim))
                )

        if cwd and not chroot:
            os.makedirs(cwd, exist_ok=True)
        try:
            proc = subprocess.Popen(
                argv,
                cwd=None if chroot else cwd,
                env=env,
                stdout=stdout,
                stderr=stderr,
                start_new_session=True,
                preexec_fn=pre_exec,
            )
        except OSError as exc:
            if cgroup is not None:
                cgroup.destroy()
            raise RuntimeError(f"launch failed: {exc}") from exc
        finally:
            for fh in (stdout, stderr):
                if fh not in (subprocess.DEVNULL, subprocess.PIPE):
                    fh.close()

        logmon = None
        if use_logmon:
            from .logmon import LogMon

            logmon = LogMon(
                spec["logs_dir"],
                spec.get("log_name") or task_id,
                max_files=int(spec.get("log_max_files", 10)),
                max_file_size_mb=int(
                    spec.get("log_max_file_size_mb", 10)
                ),
            )
            logmon.pump(proc.stdout, "stdout")
            logmon.pump(proc.stderr, "stderr")

        task = _Task(task_id, proc, cgroup, isolation)
        task.logmon = logmon
        with self._lock:
            self.tasks[task_id] = task

        def waiter() -> None:
            code = proc.wait()
            if task.logmon is not None:
                task.logmon.wait(2.0)
                task.logmon.close()
            if code < 0:
                task.exit = {"exit_code": 0, "signal": -code}
            else:
                task.exit = {"exit_code": code, "signal": 0}
            if task.cgroup is not None:
                # OOM kill shows up as SIGKILL + memory events
                task.exit["oom_killed"] = self._was_oom(task)
            # persist the exit beside the reattach record BEFORE
            # signalling completion: if the executor self-reaps while
            # the client is down, recovery still reports the real
            # status instead of 'lost'
            save_exit_record(task_id, task.exit)
            task.done.set()

        threading.Thread(target=waiter, daemon=True).start()
        return {"pid": proc.pid, "isolation": isolation}

    @staticmethod
    def _was_oom(task: _Task) -> bool:
        for path in task.cgroup.paths if task.cgroup else ():
            for fname in ("memory.events", "memory.oom_control"):
                fp = os.path.join(path, fname)
                try:
                    with open(fp) as f:
                        for line in f:
                            k, _, v = line.strip().partition(" ")
                            if k in ("oom_kill", "oom_kill_disable"):
                                if k == "oom_kill" and v and int(v) > 0:
                                    return True
                except (OSError, ValueError):
                    continue
        return False

    # -- lifecycle -----------------------------------------------------

    def wait(self, task_id: str, timeout: Optional[float]) -> Optional[Dict]:
        task = self.tasks.get(task_id)
        if task is None:
            return {"exit_code": 0, "err": "unknown task"}
        if not task.done.wait(timeout):
            return None
        return task.exit

    def signal(self, task_id: str, sig: str) -> None:
        task = self.tasks.get(task_id)
        if task is None or task.done.is_set():
            return
        name = sig if sig.startswith("SIG") else f"SIG{sig}"
        signum = _signal.Signals[name]
        try:
            os.killpg(os.getpgid(task.proc.pid), signum)
        except ProcessLookupError:
            pass

    def stop(self, task_id: str, timeout: float, sig: str) -> None:
        task = self.tasks.get(task_id)
        if task is None:
            return
        self.signal(task_id, sig)
        if not task.done.wait(timeout):
            try:
                os.killpg(os.getpgid(task.proc.pid), _signal.SIGKILL)
            except ProcessLookupError:
                pass
            task.done.wait(2.0)

    def destroy(self, task_id: str, force: bool) -> None:
        task = self.tasks.get(task_id)
        if task is None:
            return
        if not task.done.is_set():
            if not force:
                raise RuntimeError("task is still running")
            self.stop(task_id, 0.5, "SIGKILL")
        if task.cgroup is not None:
            task.cgroup.destroy()
        with self._lock:
            self.tasks.pop(task_id, None)

    def stats(self, task_id: str) -> Dict[str, float]:
        task = self.tasks.get(task_id)
        if task is None:
            return {}
        if task.cgroup is not None:
            out = task.cgroup.stats()
            if out:
                return out
        # /proc fallback
        try:
            with open(f"/proc/{task.proc.pid}/statm") as f:
                rss_pages = int(f.read().split()[1])
            return {
                "memory_rss_bytes": float(
                    rss_pages * os.sysconf("SC_PAGE_SIZE")
                )
            }
        except (OSError, IndexError, ValueError):
            return {}

    def list_tasks(self) -> List[Dict[str, Any]]:
        return [
            {
                "task_id": t.task_id,
                "pid": t.proc.pid,
                "running": not t.done.is_set(),
                "isolation": t.isolation,
            }
            for t in self.tasks.values()
        ]


# ---------------------------------------------------------------------------
# wire serving (plugin side)
# ---------------------------------------------------------------------------


def serve(socket_path: str = "") -> None:
    socket_path = socket_path or os.path.join(
        tempfile.mkdtemp(prefix="nomad-executor-"), "executor.sock"
    )
    ex = Executor()
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(socket_path)
    srv.listen(8)
    print(HANDSHAKE.format(path=socket_path), flush=True)
    shutdown = threading.Event()

    def dispatch(method: str, body: Dict) -> Any:
        if method == "Launch":
            return ex.launch(body)
        if method == "Wait":
            return ex.wait(body["task_id"], body.get("timeout"))
        if method == "Signal":
            ex.signal(body["task_id"], body.get("signal", "SIGTERM"))
            return {}
        if method == "Stop":
            ex.stop(
                body["task_id"],
                body.get("timeout", 5.0),
                body.get("signal", "SIGTERM"),
            )
            return {}
        if method == "Destroy":
            ex.destroy(body["task_id"], body.get("force", False))
            return {}
        if method == "Stats":
            return ex.stats(body["task_id"])
        if method == "ListTasks":
            return ex.list_tasks()
        if method == "Shutdown":
            shutdown.set()
            return {}
        raise ValueError(f"unknown method {method!r}")

    conns: set = set()

    def handle(conn: socket.socket) -> None:
        conns.add(conn)
        try:
            while True:
                frame = recv_frame(conn)
                if frame is None:
                    return
                method, body = decode(frame)
                try:
                    result = dispatch(method, body)
                except Exception as exc:  # noqa: BLE001
                    result = {"error": f"{type(exc).__name__}: {exc}"}
                send_frame(conn, encode(result))
        finally:
            conns.discard(conn)

    def acceptor() -> None:
        while not shutdown.is_set():
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            threading.Thread(
                target=handle, args=(conn,), daemon=True
            ).start()

    def idle_reaper() -> None:
        # self-exit when no driver is attached AND no task is running:
        # done tasks with a vanished client must not leak executor
        # processes, while a live task keeps the executor up for
        # reattach (reference: go-plugin kills executors whose tasks
        # died; reattach keeps them only while the task lives)
        idle_since: Optional[float] = None
        while not shutdown.is_set():
            time.sleep(2.0)
            busy = bool(conns) or any(
                not t.done.is_set() for t in list(ex.tasks.values())
            )
            if busy:
                idle_since = None
            elif idle_since is None:
                idle_since = time.monotonic()
            elif time.monotonic() - idle_since > 15.0:
                shutdown.set()

    threading.Thread(target=acceptor, daemon=True).start()
    threading.Thread(target=idle_reaper, daemon=True).start()
    while not shutdown.is_set():
        shutdown.wait(0.2)
    srv.close()


# ---------------------------------------------------------------------------
# host side
# ---------------------------------------------------------------------------


class ExecutorClient:
    """Driver-side proxy to one executor process (reference
    drivers/shared/executor grpc client + go-plugin ReattachConfig)."""

    def __init__(self, sock: socket.socket, socket_path: str,
                 proc: Optional[subprocess.Popen] = None) -> None:
        self.sock = sock
        self.socket_path = socket_path
        self.proc = proc
        self._lock = threading.Lock()

    @classmethod
    def spawn(cls) -> "ExecutorClient":
        # the supervisor itself never touches jax: a task helper must
        # never claim the scheduler's chip
        from ..backend import scrub_accelerator_env

        proc = subprocess.Popen(
            [sys.executable, "-m", "nomad_tpu.client.executor"],
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            text=True,
            env=scrub_accelerator_env(),
        )
        line = (proc.stdout.readline() or "").strip()
        parts = line.split("|")
        if len(parts) != 5 or parts[2] != "unix":
            proc.kill()
            raise RuntimeError(f"bad executor handshake: {line!r}")
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(60.0)
        sock.connect(parts[3])
        return cls(sock, parts[3], proc)

    @classmethod
    def reconnect(cls, socket_path: str) -> "ExecutorClient":
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(60.0)
        sock.connect(socket_path)
        return cls(sock, socket_path, None)

    def _call(self, method: str, body: Any,
              timeout: float = 30.0) -> Any:
        with self._lock:
            self.sock.settimeout(timeout + 10.0)
            resp = call(self.sock, method, body)
        if isinstance(resp, dict) and resp.get("error"):
            raise RuntimeError(resp["error"])
        return resp

    def launch(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        return self._call("Launch", spec)

    def wait(self, task_id: str,
             timeout: Optional[float] = None) -> Optional[Dict]:
        # bounded slices: single-in-flight wire (see ExternalDriver)
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            slice_ = 1.0
            if deadline is not None:
                slice_ = min(1.0, max(0.0, deadline - time.monotonic()))
            raw = self._call(
                "Wait", {"task_id": task_id, "timeout": slice_},
                timeout=slice_ + 5.0,
            )
            if raw is not None:
                return raw
            if deadline is not None and time.monotonic() >= deadline:
                return None

    def signal(self, task_id: str, sig: str = "SIGTERM") -> None:
        self._call("Signal", {"task_id": task_id, "signal": sig})

    def stop(self, task_id: str, timeout: float = 5.0,
             sig: str = "SIGTERM") -> None:
        self._call(
            "Stop",
            {"task_id": task_id, "timeout": timeout, "signal": sig},
            timeout=timeout + 10.0,
        )

    def destroy(self, task_id: str, force: bool = False) -> None:
        self._call("Destroy", {"task_id": task_id, "force": force})

    def stats(self, task_id: str) -> Dict[str, float]:
        return self._call("Stats", {"task_id": task_id}) or {}

    def list_tasks(self) -> List[Dict[str, Any]]:
        return self._call("ListTasks", {}) or []

    def shutdown(self) -> None:
        try:
            self._call("Shutdown", {}, timeout=5.0)
        except (RuntimeError, OSError):
            pass
        try:
            self.sock.close()
        except OSError:
            pass
        if self.proc is not None:
            try:
                self.proc.wait(5.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()


# -- reattach records -------------------------------------------------------


def save_reattach(task_id: str, socket_path: str, pid: int) -> None:
    os.makedirs(STATE_DIR, mode=0o700, exist_ok=True)
    if not _state_dir_trusted(STATE_DIR):
        return
    with open(os.path.join(STATE_DIR, f"{task_id}.json"), "w") as f:
        json.dump({"socket": socket_path, "pid": pid}, f)


def load_reattach(task_id: str) -> Optional[Dict[str, Any]]:
    if not _state_dir_trusted(STATE_DIR):
        return None
    try:
        with open(os.path.join(STATE_DIR, f"{task_id}.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def drop_reattach(task_id: str) -> None:
    try:
        os.unlink(os.path.join(STATE_DIR, f"{task_id}.json"))
    except OSError:
        pass
    drop_exit_record(task_id)


def save_exit_record(task_id: str, exit: Dict[str, Any]) -> None:
    """Persist a finished task's exit status beside the reattach
    record.  The executor self-reaps 15s after its last task finishes;
    a client restart slower than that must still report the REAL exit
    (a completed batch task re-run as 'lost' runs twice)."""
    os.makedirs(STATE_DIR, mode=0o700, exist_ok=True)
    if not _state_dir_trusted(STATE_DIR):
        return
    path = os.path.join(STATE_DIR, f"{task_id}.exit.json")
    tmp = f"{path}.tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(exit, f)
        os.replace(tmp, path)
    except OSError:
        pass


def load_exit_record(task_id: str) -> Optional[Dict[str, Any]]:
    if not _state_dir_trusted(STATE_DIR):
        return None
    try:
        with open(
            os.path.join(STATE_DIR, f"{task_id}.exit.json")
        ) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def drop_exit_record(task_id: str) -> None:
    try:
        os.unlink(os.path.join(STATE_DIR, f"{task_id}.exit.json"))
    except OSError:
        pass


if __name__ == "__main__":
    serve(sys.argv[1] if len(sys.argv) > 1 else "")
