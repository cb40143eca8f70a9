"""HTTP API, jobspec and CLI tests (reference model:
command/agent/http_test.go, jobspec/parse_test.go).
"""
import io
import json
import time
import urllib.request
from contextlib import redirect_stdout

import pytest

from nomad_tpu import jobspec, mock
from nomad_tpu.api import start_http_server
from nomad_tpu.api.codec import job_from_dict, job_to_dict
from nomad_tpu.server import Server


def wait_until(cond, timeout=10.0, interval=0.03):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


HCL_JOB = """
# a comment
job "web-app" {
  datacenters = ["dc1", "dc2"]
  type        = "service"
  priority    = 70

  constraint {
    attribute = "${attr.kernel.name}"
    value     = "linux"
  }

  update {
    max_parallel = 2
    canary       = 1
    auto_revert  = true
    min_healthy_time = "5s"
  }

  group "frontend" {
    count = 3

    spread {
      attribute = "${node.datacenter}"
      weight    = 60
      target "dc1" { percent = 70 }
      target "dc2" { percent = 30 }
    }

    restart {
      attempts = 2
      interval = "30m"
      delay    = "15s"
      mode     = "fail"
    }

    ephemeral_disk { size = 500 }

    task "server" {
      driver = "mock_driver"
      config {
        run_for = -1
      }
      env {
        PORT = "8080"
      }
      resources {
        cpu    = 500
        memory = 256
      }
    }
  }
}
"""


def test_jobspec_parse():
    job = jobspec.parse(HCL_JOB)
    assert job.id == "web-app"
    assert job.type == "service"
    assert job.priority == 70
    assert job.datacenters == ["dc1", "dc2"]
    assert len(job.constraints) == 1
    assert job.constraints[0].ltarget == "${attr.kernel.name}"
    assert job.update is not None and job.update.canary == 1
    assert job.update.min_healthy_time_s == 5.0
    tg = job.task_groups[0]
    assert tg.name == "frontend" and tg.count == 3
    assert tg.spreads[0].attribute == "${node.datacenter}"
    assert tg.spreads[0].targets[0].value == "dc1"
    assert tg.spreads[0].targets[0].percent == 70
    assert tg.restart_policy.interval_s == 1800.0
    assert tg.ephemeral_disk.size_mb == 500
    # job-level update propagates to groups
    assert tg.update is not None
    task = tg.tasks[0]
    assert task.driver == "mock_driver"
    assert task.config == {"run_for": -1}
    assert task.env == {"PORT": "8080"}
    assert task.resources.cpu == 500
    assert task.resources.memory_mb == 256


def test_job_json_roundtrip():
    job = jobspec.parse(HCL_JOB)
    d = job_to_dict(job)
    restored = job_from_dict(json.loads(json.dumps(d)))
    assert restored.id == job.id
    assert restored.task_groups[0].count == 3
    assert restored.task_groups[0].tasks[0].resources.cpu == 500
    assert restored.update.canary == 1


@pytest.fixture
def api():
    # two schedulers + a short nack timeout: the broker's at-least-once
    # redelivery and worker redundancy absorb a stuck worker thread
    # (this sandbox's scheduler has been observed to freeze a newly
    # created thread indefinitely — see eval_broker ticker note)
    server = Server(
        num_schedulers=2, heartbeat_ttl=60.0, seed=33, nack_timeout=5.0
    )
    server.start()
    http = start_http_server(server, port=0)
    base = f"http://127.0.0.1:{http.port}"
    yield server, base
    http.stop()
    server.stop()


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=10) as resp:
        return json.loads(resp.read())


def _post(base, path, body, method="POST"):
    req = urllib.request.Request(
        base + path,
        data=json.dumps(body).encode(),
        method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())


def test_http_job_lifecycle(api):
    server, base = api
    for _ in range(3):
        server.register_node(mock.node())

    job = jobspec.parse(HCL_JOB)
    job.task_groups[0].update = None
    job.update = None
    resp = _post(base, "/v1/jobs", {"Job": job_to_dict(job)})
    assert resp["EvalID"]

    jobs = _get(base, "/v1/jobs")
    assert [j["ID"] for j in jobs] == ["web-app"]

    detail = _get(base, "/v1/job/web-app")
    assert detail["priority"] == 70

    assert wait_until(
        lambda: len(_get(base, "/v1/job/web-app/allocations")) == 3,
        timeout=30,
    ), (
        f"evals={[(e.status, e.triggered_by) for e in server.store.evals_by_job('default', 'web-app')]} "
        f"broker={server.broker.stats} "
        # where the eval got to: its flight-recorder trace
        f"trace={_get(base, '/v1/traces/' + resp['EvalID'])}"
    )
    allocs = _get(base, "/v1/job/web-app/allocations")

    evals = _get(base, "/v1/job/web-app/evaluations")
    assert evals and evals[0]["status"] == "complete"

    alloc = _get(base, f"/v1/allocation/{allocs[0]['id']}")
    assert alloc["job_id"] == "web-app"

    # scale up
    resp = _post(
        base, "/v1/job/web-app/scale",
        {"Target": {"Group": "frontend"}, "Count": 5},
    )
    assert server.drain_to_idle(10)
    assert wait_until(
        lambda: len(
            [
                a
                for a in server.store.allocs_by_job("default", "web-app")
                if not a.terminal_status()
            ]
        )
        == 5
    )

    # stop
    _post(base, "/v1/job/web-app", {}, method="DELETE")
    assert server.drain_to_idle(10)
    assert wait_until(
        lambda: not [
            a
            for a in server.store.allocs_by_job("default", "web-app")
            if a.desired_status == "run"
        ]
    )


def test_http_nodes_and_search(api):
    server, base = api
    n = mock.node()
    server.register_node(n)
    nodes = _get(base, "/v1/nodes")
    assert nodes[0]["ID"] == n.id
    detail = _get(base, f"/v1/node/{n.id}")
    assert detail["datacenter"] == "dc1"

    # drain via API
    _post(base, f"/v1/node/{n.id}/drain",
          {"DrainSpec": {"Deadline": int(60e9)}})
    assert server.store.node_by_id(n.id).drain

    # search
    result = _post(
        base, "/v1/search", {"Prefix": n.id[:4], "Context": "nodes"}
    )
    assert n.id in result["Matches"]["nodes"]


def test_http_operator_scheduler_config(api):
    server, base = api
    cfg = _get(base, "/v1/operator/scheduler/configuration")
    assert cfg["SchedulerAlgorithm"] == "binpack"
    assert cfg["TPUSchedulerEnabled"] is False
    cfg["TPUSchedulerEnabled"] = True
    cfg["SchedulerAlgorithm"] = "spread"
    _post(base, "/v1/operator/scheduler/configuration", cfg)
    assert server.store.get_scheduler_config().tpu_scheduler_enabled
    assert (
        server.store.get_scheduler_config().scheduler_algorithm
        == "spread"
    )


def test_http_404s(api):
    _server, base = api
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get(base, "/v1/job/nope")
    assert exc.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get(base, "/v1/bogus")
    assert exc.value.code == 404


def test_cli_against_live_agent(api, monkeypatch, tmp_path):
    server, base = api
    from nomad_tpu import cli

    monkeypatch.setenv("NOMAD_ADDR", base)
    server.register_node(mock.node())

    spec = tmp_path / "job.hcl"
    spec.write_text(HCL_JOB.replace('canary       = 1', 'canary = 0'))

    out = io.StringIO()
    with redirect_stdout(out):
        cli.main(["job", "run", str(spec)])
    assert "Evaluation" in out.getvalue()
    assert server.drain_to_idle(10)

    out = io.StringIO()
    with redirect_stdout(out):
        cli.main(["job", "status"])
    assert "web-app" in out.getvalue()

    out = io.StringIO()
    with redirect_stdout(out):
        cli.main(["job", "status", "web-app"])
    assert "Allocations" in out.getvalue()

    out = io.StringIO()
    with redirect_stdout(out):
        cli.main(["node", "status"])
    assert "dc1" in out.getvalue()

    out = io.StringIO()
    with redirect_stdout(out):
        cli.main(["version"])
    assert "nomad-tpu" in out.getvalue()
