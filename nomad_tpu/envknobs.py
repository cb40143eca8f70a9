"""Central registry of every ``NOMAD_TPU_*`` environment knob.

One row per knob: its default, the module that owns (reads) it, and a
one-line description.  This registry — together with the knob table
in docs/ARCHITECTURE.md — is enforced by the ``config-drift`` rule of
``python -m tools.nomadlint``: a knob read anywhere in ``nomad_tpu/``,
``bench.py`` or ``tests/`` must appear here AND in the docs table, a
registered knob must still be read somewhere, a documented knob
must still be registered, and a registered knob must be set by
something in the tree other than its read site (a test, a smoke,
``bench.py``, ``tools/ci_check.sh``) or be a deployment setting.  New
knobs therefore cannot ship undocumented, removed ones cannot haunt
the docs, and an option nothing sets becomes a constant.

The registry is data, not plumbing: call sites keep reading
``os.environ`` directly (many are hot-path or import-time reads with
bespoke parsing/clamping); this module exists so operators and the
lint have ONE place to look.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class EnvKnob(NamedTuple):
    default: str  # human-readable default ("" = unset)
    owner: str  # repo-relative owning module
    doc: str  # one-line description


# Deployment settings (addresses, ports, paths, pod identity): the only
# knobs that may stay registered with no setter in the tree
# (``config-drift``, direction 5).
DEPLOYMENT_KNOBS = frozenset({
    "NOMAD_TPU_EXECUTOR_STATE",
    # Not deployment settings: listed until each has its verdict
    # (ROADMAP C3).  Whether a server warms at all is ROADMAP A7 / D4.
    "NOMAD_TPU_WARM_ON_START",
    # Set from a shell only: the recorder's and the explainer's opt-outs
    # (tests reach the off side through ``set_enabled``), the chaos
    # smoke's fault plan and the soak tests' opt-in.
    "NOMAD_TPU_TRACE",
    "NOMAD_TPU_EXPLAIN",
    "NOMAD_TPU_CLUSTER_FAULT",
    "NOMAD_TPU_SOAK",
})

ENV_KNOBS: Dict[str, EnvKnob] = {
    # -- batch pipeline (server/batch_worker.py) ----------------------
    "NOMAD_TPU_BATCH_MAX": EnvKnob(
        "64", "nomad_tpu/server/batch_worker.py",
        "max evals per prescore gulp (clamped to [1, 64])",
    ),
    "NOMAD_TPU_PARALLEL_REPLAY": EnvKnob(
        "1", "nomad_tpu/server/batch_worker.py",
        "0 restores the serial replay loop",
    ),
    "NOMAD_TPU_REPLAY_STRICT": EnvKnob(
        "0", "nomad_tpu/server/batch_worker.py",
        "1 serializes every wave-contended eval (full score-metric "
        "bit-identity)",
    ),
    "NOMAD_TPU_LATENCY_BUDGET_MS": EnvKnob(
        "250", "nomad_tpu/server/batch_worker.py",
        "adaptive gulp cap: keep last-eval latency within this "
        "budget when the worker keeps up (0 disables)",
    ),
    "NOMAD_TPU_ADMIT": EnvKnob(
        "1", "nomad_tpu/server/batch_worker.py",
        "0 restores flush-boundary gulps (no mid-chain admission)",
    ),
    "NOMAD_TPU_MESH": EnvKnob(
        "0", "nomad_tpu/server/batch_worker.py",
        "1 shards prescore launches over the node-axis device mesh",
    ),
    "NOMAD_TPU_STORM": EnvKnob(
        "0", "nomad_tpu/server/batch_worker.py",
        "1 coalesces same-family eval storms into one global "
        "device assignment solve (serial equivalence explicitly "
        "relaxed; divergences audited via the explain ring)",
    ),
    "NOMAD_TPU_STORM_MIN": EnvKnob(
        "16", "nomad_tpu/server/batch_worker.py",
        "storm trigger threshold: minimum contiguous same-family "
        "broker backlog before a coalesced solve engages",
    ),
    "NOMAD_TPU_STORM_MAX": EnvKnob(
        "256", "nomad_tpu/server/batch_worker.py",
        "max evals drained into one storm solve (clamped to "
        "[STORM_MIN, 1024])",
    ),
    # -- policy-weighted scoring (sched/policy.py) --------------------
    "NOMAD_TPU_POLICY": EnvKnob(
        "1", "nomad_tpu/sched/policy.py",
        "0 disables the policy-weighted scoring layer (jobs carrying "
        "a policy stanza score as policy-less)",
    ),
    "NOMAD_TPU_POLICY_TPUT_COEF": EnvKnob(
        "", "nomad_tpu/sched/policy.py",
        "operator override for every job's throughput coefficient "
        "(unset = per-job spec value)",
    ),
    "NOMAD_TPU_POLICY_MIG_COEF": EnvKnob(
        "", "nomad_tpu/sched/policy.py",
        "operator override for every job's migration stickiness "
        "coefficient (unset = per-job spec value)",
    ),
    # -- multi-host mesh (nomad_tpu/parallel/mesh.py) -----------------
    "NOMAD_TPU_DIST": EnvKnob(
        "0", "nomad_tpu/parallel/mesh.py",
        "1 opts this process into the multi-host pod mesh "
        "(jax.distributed init; single-process stays the "
        "zero-config default)",
    ),
    "NOMAD_TPU_DIST_COORD": EnvKnob(
        "127.0.0.1:8476", "nomad_tpu/parallel/mesh.py",
        "coordinator address (process 0's host:port) for the "
        "distributed init",
    ),
    "NOMAD_TPU_DIST_PROCS": EnvKnob(
        "1", "nomad_tpu/parallel/mesh.py",
        "total processes in the multi-host world (<=1 keeps "
        "distributed init off)",
    ),
    "NOMAD_TPU_DIST_ID": EnvKnob(
        "0", "nomad_tpu/parallel/mesh.py",
        "this process's id in [0, NOMAD_TPU_DIST_PROCS)",
    ),
    "NOMAD_TPU_POD_PORT": EnvKnob(
        "", "nomad_tpu/server/batch_worker.py",
        "pod-head stream port: process 0 of a multi-host world "
        "serves the mesh-operation stream (parallel/pod.py) that "
        "peer processes replay in FIFO order",
    ),
    "NOMAD_TPU_POD_CHECK": EnvKnob(
        "0", "nomad_tpu/parallel/pod.py",
        "1 makes every pod chain/storm launch round-trip a result "
        "digest from every peer — the head/peer bit-parity gate",
    ),
    "NOMAD_TPU_TSAN": EnvKnob(
        "0", "nomad_tpu/tsan.py",
        "1 turns on the happens-before sanitizer: shared-singleton "
        "attribute accesses and lock ops are vector-clock logged, "
        "and the tier-1 soak asserts conflicts stay inside the "
        "static SHARED_STATE_ALLOWLIST",
    ),
    "NOMAD_TPU_SYNC_COMPILE": EnvKnob(
        "0", "nomad_tpu/server/batch_worker.py",
        "1 makes cold kernel compiles block (deterministic tests) "
        "instead of background-compiling behind the shield",
    ),
    # -- cluster / failover (server/cluster.py, raft/chaos.py) --------
    "NOMAD_TPU_FORWARD_RETRIES": EnvKnob(
        "4", "nomad_tpu/server/cluster.py",
        "leader-forward retry budget after the first attempt; each "
        "retry rediscovers the leader (command ids keep retries "
        "idempotent)",
    ),
    "NOMAD_TPU_CLUSTER_FAULT": EnvKnob(
        "", "nomad_tpu/raft/chaos.py",
        "deterministic cluster fault plan "
        "(leader_kill|partition[:a,b]|msg_drop[:pct]|slow_wire[:ms]) "
        "for the chaos harness",
    ),
    # -- follower scheduling fan-out (server/fanout.py) ---------------
    "NOMAD_TPU_FANOUT": EnvKnob(
        "0", "nomad_tpu/server/fanout.py",
        "1 turns followers into schedulers: each runs the full TPU "
        "batch pipeline against its local replicated state, leasing "
        "evals from the leader's broker over RPC with commit "
        "serialized on the leader's plan queue",
    ),
    "NOMAD_TPU_FANOUT_WORKERS": EnvKnob(
        "1", "nomad_tpu/server/fanout.py",
        "fan-out batch workers per follower server",
    ),
    "NOMAD_TPU_FANOUT_LEASE_N": EnvKnob(
        "8", "nomad_tpu/server/fanout.py",
        "max broker leases granted per remote dequeue RPC (the "
        "surplus buffers locally, so gulp fills are buffer pops, "
        "not round trips)",
    ),
    "NOMAD_TPU_FANOUT_MESH": EnvKnob(
        "0", "nomad_tpu/server/batch_worker.py",
        "1 reserves the device mesh (and the pod head) for the "
        "follower fan-out worker — main workers in the same process "
        "stay meshless instead of racing it for the world",
    ),
    # -- multi-region federation (server/federation.py) ---------------
    "NOMAD_TPU_REGION_PROBE_S": EnvKnob(
        "0.5", "nomad_tpu/server/federation.py",
        "federation router cadence: how often the gossip-derived "
        "region health/routing snapshot (and the federation.* "
        "gauges) refresh",
    ),
    # -- overload control plane (server/overload.py, server.py) -------
    "NOMAD_TPU_OVERLOAD": EnvKnob(
        "1", "nomad_tpu/server/overload.py",
        "0 disables ingress backpressure (every request admitted, "
        "mode pinned NORMAL)",
    ),
    "NOMAD_TPU_OVERLOAD_DEPTH": EnvKnob(
        "512", "nomad_tpu/server/overload.py",
        "broker pending-depth threshold for SHEDDING (EMERGENCY "
        "engages at 4x)",
    ),
    "NOMAD_TPU_OVERLOAD_AGE_S": EnvKnob(
        "30", "nomad_tpu/server/overload.py",
        "oldest-ready-eval age threshold for SHEDDING (EMERGENCY "
        "at 4x) — the measured commit-wave lag signal",
    ),
    "NOMAD_TPU_OVERLOAD_WAVE_MIN": EnvKnob(
        "8", "nomad_tpu/server/server.py",
        "TTL expiries per sweep that count as a correlated mass "
        "node-death (smaller waves transition immediately)",
    ),
    "NOMAD_TPU_OVERLOAD_WAVE_GATHER_S": EnvKnob(
        "auto", "nomad_tpu/server/server.py",
        "max time a detected mass-death wave gathers straggler TTL "
        "expiries before the batched down transition commits "
        "(auto = heartbeat_ttl/3 clamped to [2.5, 10]s, so the "
        "budget always exceeds the 2s quiet-stream settle)",
    ),
    # -- server / broker ----------------------------------------------
    "NOMAD_TPU_WARM_ON_START": EnvKnob(
        "0", "nomad_tpu/server/server.py",
        "1 pre-compiles prescore launch shapes off the scheduling "
        "path once the node-join wave settles",
    ),
    "NOMAD_TPU_BROKER_WATCHDOG": EnvKnob(
        "0", "nomad_tpu/server/eval_broker.py",
        "1 makes the broker sweeper notify_all() every tick "
        "(sandbox workaround for parked Condition waits)",
    ),
    # -- observability ------------------------------------------------
    "NOMAD_TPU_TRACE": EnvKnob(
        "1", "nomad_tpu/trace.py",
        "0 turns the eval flight recorder into no-ops",
    ),
    "NOMAD_TPU_EXPLAIN": EnvKnob(
        "1", "nomad_tpu/explain.py",
        "0 turns placement-explanation capture into no-ops",
    ),
    "NOMAD_TPU_OBS_HISTORY": EnvKnob(
        "1", "nomad_tpu/telemetry.py",
        "0 disables the periodic metric time-series history ring "
        "(snapshot thread never starts, /v1/metrics/history empty)",
    ),
    "NOMAD_TPU_OBS_HISTORY_N": EnvKnob(
        "60", "nomad_tpu/telemetry.py",
        "metric history depth: how many snapshot windows the ring "
        "retains (min 2)",
    ),
    "NOMAD_TPU_SLO": EnvKnob(
        "1", "nomad_tpu/slo.py",
        "0 disables SLO burn-rate grading (/v1/slo reports every "
        "objective OK with zero burn)",
    ),
    "NOMAD_TPU_SLO_FAST_N": EnvKnob(
        "6", "nomad_tpu/slo.py",
        "fast burn window: newest history snapshots graded for "
        "'is it happening now' (min 2)",
    ),
    "NOMAD_TPU_SLO_SLOW_N": EnvKnob(
        "30", "nomad_tpu/slo.py",
        "slow burn window: newest history snapshots graded for "
        "'is it material' (min 2)",
    ),
    "NOMAD_TPU_DECISIONS": EnvKnob(
        "1", "nomad_tpu/decisions.py",
        "0 turns the adaptive-decision ledger into no-ops (sites "
        "skip record assembly entirely)",
    ),
    "NOMAD_TPU_OBS_FANIN_TIMEOUT_S": EnvKnob(
        "2.0", "nomad_tpu/server/cluster.py",
        "per-query wall budget for the leader's /v1/cluster/* "
        "fan-in: peers not answered within it are marked "
        "unreachable in the merged (partial) result",
    ),
    # -- accelerator supervisor (nomad_tpu/device) --------------------
    "NOMAD_TPU_SUPERVISOR": EnvKnob(
        "auto", "nomad_tpu/device/supervisor.py",
        "1 forces device supervision on, 0 off (default: on when "
        "JAX resolved an accelerator at server start, whatever "
        "JAX_PLATFORMS holds, or a fault is armed)",
    ),
    "NOMAD_TPU_PROBE_INTERVAL_S": EnvKnob(
        "30", "nomad_tpu/device/supervisor.py",
        "canary probe cadence",
    ),
    "NOMAD_TPU_PROBE_TIMEOUT_S": EnvKnob(
        "10", "nomad_tpu/device/supervisor.py",
        "canary probe deadline",
    ),
    "NOMAD_TPU_LOST_PROBES": EnvKnob(
        "2", "nomad_tpu/device/supervisor.py",
        "consecutive canary failures past DEGRADED before LOST",
    ),
    "NOMAD_TPU_RECOVER_CANARIES": EnvKnob(
        "3", "nomad_tpu/device/supervisor.py",
        "consecutive canary passes before flipping back HEALTHY",
    ),
    "NOMAD_TPU_INIT_GRACE_S": EnvKnob(
        "600", "nomad_tpu/device/supervisor.py",
        "deadline floor until the device answers once (cold PJRT "
        "init must not read as a wedge)",
    ),
    "NOMAD_TPU_WATCHDOG_MIN_S": EnvKnob(
        "5", "nomad_tpu/device/supervisor.py",
        "launch-watchdog budget floor",
    ),
    "NOMAD_TPU_WATCHDOG_MAX_S": EnvKnob(
        "120", "nomad_tpu/device/supervisor.py",
        "launch-watchdog budget ceiling",
    ),
    "NOMAD_TPU_FAULT": EnvKnob(
        "", "nomad_tpu/device/faults.py",
        "deterministic CPU fault plan "
        "(wedge_launch|slow_fetch|init_block|flaky[:N])",
    ),
    # -- client -------------------------------------------------------
    "NOMAD_TPU_EXEC_ISOLATION": EnvKnob(
        "1", "nomad_tpu/client/drivers/exec.py",
        "0 forces the in-process restricted-env spawn instead of "
        "the isolated executor process",
    ),
    "NOMAD_TPU_EXECUTOR_STATE": EnvKnob(
        "auto", "nomad_tpu/client/executor.py",
        "executor state directory (default: per-user temp dir)",
    ),
    # -- tests --------------------------------------------------------
    "NOMAD_TPU_SOAK": EnvKnob(
        "0", "tests/test_soak.py",
        "1 opts in to the long-running soak tests",
    ),
}
