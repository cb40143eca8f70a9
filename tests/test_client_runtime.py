"""Client runtime subsystems: allocdir layout, taskenv interpolation,
logmon rotation, alloc GC, heartbeat-stop, previous-alloc watcher
(reference client/allocdir, client/taskenv, client/logmon, client/gc.go,
client/heartbeatstop.go, client/allocwatcher).
"""
import os
import time

from nomad_tpu import mock
from nomad_tpu.client.allocdir import AllocDir, find_alloc_dir
from nomad_tpu.client.allocwatcher import (
    NoopPrevAlloc,
    PrevAllocWatcher,
    watcher_for_alloc,
)
from nomad_tpu.client.gc import AllocGarbageCollector
from nomad_tpu.client.heartbeatstop import HeartbeatStopper
from nomad_tpu.client.logmon import FileRotator, LogMon, read_task_log
from nomad_tpu.client.taskenv import Builder
from nomad_tpu.structs import Node


def wait_until(cond, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# allocdir
# ---------------------------------------------------------------------------


def test_allocdir_layout(tmp_path):
    ad = AllocDir(str(tmp_path), "alloc1")
    td = ad.new_task_dir("web")
    ad.build()
    assert os.path.isdir(ad.data_dir)
    assert os.path.isdir(ad.log_dir)
    assert os.path.isdir(td.local_dir)
    assert os.path.isdir(td.secrets_dir)
    assert td.shared_alloc_dir == ad.shared_dir

    with open(os.path.join(td.local_dir, "f.txt"), "w") as f:
        f.write("x" * 100)
    assert ad.disk_usage_bytes() >= 100
    assert any("web/local/f.txt" in p for p in ad.list_files())

    ad.destroy()
    assert not os.path.isdir(ad.alloc_dir)


def test_allocdir_move_from_migrates_sticky_dirs(tmp_path):
    prev = AllocDir(str(tmp_path), "prev")
    prev.new_task_dir("web")
    prev.build()
    with open(os.path.join(prev.data_dir, "db.sqlite"), "w") as f:
        f.write("data")
    with open(
        os.path.join(prev.task_dirs["web"].local_dir, "cache"), "w"
    ) as f:
        f.write("c")

    nxt = AllocDir(str(tmp_path), "next")
    nxt.new_task_dir("web")
    nxt.move_from(prev)
    assert os.path.exists(os.path.join(nxt.data_dir, "db.sqlite"))
    assert os.path.exists(
        os.path.join(nxt.task_dirs["web"].local_dir, "cache")
    )


def test_find_alloc_dir_reopens(tmp_path):
    ad = AllocDir(str(tmp_path), "a1")
    ad.new_task_dir("web")
    ad.build()
    reopened = find_alloc_dir(str(tmp_path), "a1")
    assert reopened is not None
    assert "web" in reopened.task_dirs
    assert find_alloc_dir(str(tmp_path), "missing") is None


# ---------------------------------------------------------------------------
# taskenv
# ---------------------------------------------------------------------------


def _env_fixture(tmp_path):
    job = mock.job()
    alloc = mock.alloc(job=job)
    tg = job.task_groups[0]
    task = tg.tasks[0]
    task.meta["owner"] = "team-a"
    node = Node(name="n1", datacenter="dc2")
    node.attributes["kernel.name"] = "linux"
    node.meta["rack"] = "r7"
    ad = AllocDir(str(tmp_path), alloc.id)
    td = ad.new_task_dir(task.name)
    b = (
        Builder()
        .set_alloc(alloc, job, tg)
        .set_node(node, region="global")
        .set_task(task, td)
        .set_ports({"http": 8080}, ip="10.0.0.5")
    )
    return b.build(), alloc, job, task, td


def test_taskenv_nomad_vars(tmp_path):
    env, alloc, job, task, td = _env_fixture(tmp_path)
    vals = env.all()
    assert vals["NOMAD_ALLOC_ID"] == alloc.id
    assert vals["NOMAD_JOB_ID"] == job.id
    assert vals["NOMAD_TASK_NAME"] == task.name
    assert vals["NOMAD_TASK_DIR"] == td.local_dir
    assert vals["NOMAD_SECRETS_DIR"] == td.secrets_dir
    assert vals["NOMAD_DC"] == "dc2"
    assert vals["NOMAD_META_owner"] == "team-a"
    assert vals["NOMAD_META_OWNER"] == "team-a"
    assert vals["NOMAD_ADDR_http"] == "10.0.0.5:8080"
    assert vals["NOMAD_PORT_http"] == "8080"
    assert vals["NOMAD_CPU_LIMIT"] == str(task.resources.cpu)


def test_taskenv_interpolation(tmp_path):
    env, alloc, _job, _task, _td = _env_fixture(tmp_path)
    s = env.replace(
        "id=${NOMAD_ALLOC_ID} dc=${node.datacenter} "
        "k=${attr.kernel.name} rack=${meta.rack} none=${meta.nope}"
    )
    assert s == f"id={alloc.id} dc=dc2 k=linux rack=r7 none="
    cfg = env.replace_all(
        {"args": ["--port", "${NOMAD_PORT_http}"], "n": 3}
    )
    assert cfg["args"] == ["--port", "8080"]
    assert cfg["n"] == 3


# ---------------------------------------------------------------------------
# logmon
# ---------------------------------------------------------------------------


def test_file_rotator_rotates_and_prunes(tmp_path):
    rot = FileRotator(
        str(tmp_path), "web.stdout", max_files=3, max_file_size_bytes=10
    )
    for _ in range(10):
        rot.write(b"0123456789")  # exactly one file each
    rot.close()
    files = rot.existing_files()
    assert len(files) <= 3
    # newest data survives
    data = read_task_log(str(tmp_path), "web", "stdout", max_bytes=1000)
    assert data.endswith(b"0123456789")


def test_logmon_pumps_streams(tmp_path):
    import io

    lm = LogMon(str(tmp_path), "web", max_file_size_mb=1)
    lm.pump(io.BytesIO(b"hello out\n"), "stdout")
    lm.pump(io.BytesIO(b"hello err\n"), "stderr")
    lm.wait(2.0)
    lm.close()
    assert b"hello out" in read_task_log(str(tmp_path), "web", "stdout")
    assert b"hello err" in read_task_log(str(tmp_path), "web", "stderr")


def test_exec_driver_rotated_logs(tmp_path):
    from nomad_tpu.client.drivers import RawExecDriver
    from nomad_tpu.client.drivers.base import TaskConfig

    d = RawExecDriver()
    logs = tmp_path / "logs"
    cfg = TaskConfig(
        id="t1",
        name="echo",
        config={"command": "/bin/sh", "args": ["-c", "echo rotated"]},
        alloc_dir=str(tmp_path),
        logs_dir=str(logs),
    )
    d.start_task(cfg)
    d.wait_task("t1", timeout=5)
    assert wait_until(
        lambda: b"rotated"
        in read_task_log(str(logs), "echo", "stdout")
    )


# ---------------------------------------------------------------------------
# gc
# ---------------------------------------------------------------------------


def test_gc_make_room_for_destroys_oldest(tmp_path):
    destroyed = []
    gc = AllocGarbageCollector(
        alloc_base_dir=str(tmp_path),
        max_allocs=3,
        destroy_fn=destroyed.append,
    )
    # make_room_for goes on to _gc_for_disk: a roomy disk, whatever
    # the machine's own is
    gc._disk_stats = lambda: (10.0, 1e6)
    gc.set_live_count(1)
    gc.mark_terminal("old1")
    gc.mark_terminal("old2")
    # 1 live + 2 terminal = 3; room for 1 more requires evicting 1
    gc.make_room_for(1)
    assert destroyed == ["old1"]
    assert gc.num_marked() == 1


def test_gc_collect_all_and_specific(tmp_path):
    for aid in ("a", "b"):
        os.makedirs(tmp_path / aid)
    gc = AllocGarbageCollector(alloc_base_dir=str(tmp_path))
    gc.mark_terminal("a")
    gc.mark_terminal("b")
    assert gc.collect("a") is True
    assert not os.path.isdir(tmp_path / "a")
    assert gc.collect_all() == 1
    assert not os.path.isdir(tmp_path / "b")
    assert gc.collect("a") is False


# ---------------------------------------------------------------------------
# heartbeatstop
# ---------------------------------------------------------------------------


def test_heartbeatstop_stops_after_disconnect():
    job = mock.job()
    job.task_groups[0].stop_after_client_disconnect_s = 0.1
    alloc = mock.alloc(job=job)

    stopped = []
    hs = HeartbeatStopper(stop_alloc_fn=stopped.append)
    hs.allocation_hook(alloc)
    hs.note_heartbeat_ok()
    assert hs.check_once() == 0  # fresh heartbeat: nothing stops
    time.sleep(0.15)  # no heartbeats arrive
    assert hs.check_once() == 1
    assert stopped == [alloc.id]
    # removed after stopping; doesn't fire twice
    assert hs.check_once() == 0


def test_heartbeatstop_ignores_opted_out_groups():
    alloc = mock.alloc()  # no stop_after_client_disconnect
    hs = HeartbeatStopper(stop_alloc_fn=lambda _x: None)
    hs.allocation_hook(alloc)
    time.sleep(0.05)
    assert hs.expired() == {}


# ---------------------------------------------------------------------------
# allocwatcher
# ---------------------------------------------------------------------------


def test_watcher_noop_without_previous():
    alloc = mock.alloc()
    w = watcher_for_alloc(alloc, {})
    assert isinstance(w, NoopPrevAlloc)
    assert w.wait(0.01) is True


class _FakeRunner:
    def __init__(self):
        self.done = False
        self.alloc_dir_obj = None

    def wait(self, timeout=None):
        return self.done


def test_watcher_local_waits_for_runner(tmp_path):
    prev = _FakeRunner()
    w = PrevAllocWatcher("prev1", prev_runner=prev, migrate=True)
    assert w.wait(0.05) is False
    prev.done = True
    assert w.wait(0.05) is True


def test_watcher_local_migration(tmp_path):
    prev_dir = AllocDir(str(tmp_path), "prev1")
    prev_dir.new_task_dir("web")
    prev_dir.build()
    with open(os.path.join(prev_dir.data_dir, "keep"), "w") as f:
        f.write("1")

    prev = _FakeRunner()
    prev.done = True
    prev.alloc_dir_obj = prev_dir
    w = PrevAllocWatcher(
        "prev1", migrate=True, prev_runner=prev,
        alloc_base_dir=str(tmp_path),
    )
    assert w.wait(1.0) is True
    dest = AllocDir(str(tmp_path), "next1")
    dest.new_task_dir("web")
    assert w.migrate(dest) is True
    assert os.path.exists(os.path.join(dest.data_dir, "keep"))


def test_watcher_remote_polls_server(tmp_path):
    terminal = {"v": False}
    w = PrevAllocWatcher(
        "prev1",
        migrate=True,
        poll_terminal=lambda _aid: terminal["v"],
        poll_interval=0.01,
    )
    assert w.wait(0.05) is False
    terminal["v"] = True
    assert w.wait(1.0) is True
    # remote with no snapshot transport: no data moved
    dest = AllocDir(str(tmp_path), "next1")
    assert w.migrate(dest) is False


def test_watcher_refuses_migration_before_wait(tmp_path):
    prev = _FakeRunner()
    w = PrevAllocWatcher("prev1", migrate=True, prev_runner=prev)
    dest = AllocDir(str(tmp_path), "next1")
    assert w.migrate(dest) is False
