"""Accelerator supervisor: in-process device health, launch watchdogs
and hot CPU failover.

The device subsystem owns accelerator liveness for the whole server:

* ``supervisor``  — the DeviceSupervisor state machine
  (HEALTHY -> DEGRADED -> LOST -> RECOVERING) with canary health
  probes, EWMA-budgeted launch watchdogs and listener-driven failover;
* ``watchdog``    — sacrificial-thread bounded calls and per-stage
  deadline budgets (a wedged PJRT client is *abandoned*, never joined);
* ``faults``      — deterministic fault injection
  (``NOMAD_TPU_FAULT=wedge_launch|slow_fetch|init_block|flaky``) so
  every transition is testable on CPU;
* ``preflight``   — ``python -m nomad_tpu.device.preflight``, the
  bounded canary probe as a standalone check that names the platform
  that answered.

Which backend the process is on is answered by ``nomad_tpu.backend``
(``resolve_backend``), from what JAX initialised.
"""
from .faults import FaultPlan, InjectedFault
from .supervisor import (
    CPU_ONLY,
    DEGRADED,
    HEALTHY,
    LOST,
    RECOVERING,
    STATE_CODES,
    DeviceSupervisor,
)
from .watchdog import BudgetTracker, DeviceTimeout, bounded_call

__all__ = [
    "BudgetTracker",
    "CPU_ONLY",
    "DEGRADED",
    "DeviceSupervisor",
    "DeviceTimeout",
    "FaultPlan",
    "HEALTHY",
    "InjectedFault",
    "LOST",
    "RECOVERING",
    "STATE_CODES",
    "bounded_call",
]
