"""Bounded accelerator preflight: ``python -m nomad_tpu.device.preflight``.

The supervisor's canary probe as a standalone check: retry a
bounded-time backend-init + canary kernel until JAX's default backend
answers or the deadline passes.

Prints ONE machine-readable state line on stdout::

    DEVICE_PREFLIGHT {"state": "HEALTHY", "platform": "tpu", ...}

and exits 0 when the backend answered, 2 otherwise — the contract
unattended retry loops script against
(``while ! python -m nomad_tpu.device.preflight; do sleep ...; done``).
The canary passes on whatever backend JAX resolved, the CPU included:
the ``platform``/``device_kind``/``device_count`` fields say which one
answered, and a caller that needs the chip checks them
(``chip_smoke.py`` is the check that REQUIRES a TPU).  The preflight
claims the chip like any JAX process — never run it beside a live
scheduler.

``--budget-s`` sets the total retry budget (default 600); the
supervisor's ``NOMAD_TPU_PROBE_TIMEOUT_S`` is the per-attempt deadline.
"""
from __future__ import annotations

import json
import sys
import time
from typing import Callable, Dict

from .supervisor import HEALTHY, DeviceSupervisor

# preflight verdicts beyond the supervisor's state machine
SKIPPED = "SKIPPED"  # explicit opt-out (budget <= 0)
FATAL = "FATAL"  # permanent (e.g. jax not importable)
UNREACHABLE = "UNREACHABLE"  # deadline passed without a canary pass
# verdicts callers may proceed on
HEALTHY_STATES = (HEALTHY, SKIPPED)

_RETRY_SLEEP_S = 10.0
PREFLIGHT_BUDGET_S = 600.0


def _stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_preflight(
    total_s: float = PREFLIGHT_BUDGET_S,
    log: Callable[[str], None] = _stderr,
) -> Dict:
    """Probe the accelerator until it answers or ``total_s`` passes.
    Returns the machine-readable result dict (the state line payload);
    never raises."""
    if total_s <= 0:
        return {"state": SKIPPED, "attempts": 0}
    # a throwaway supervisor: its canary + bounded-call machinery IS
    # the preflight; expected=True whatever backend resolves (a CPU
    # canary passes instantly; the result names the platform).
    # init_grace_s=0: preflight attempts must be bounded by the probe
    # timeout alone — the OUTER total_s loop owns the slow-init wait
    # (the single-flight canary keeps retries from stacking threads on
    # the memoized init; once it completes, the next attempt passes)
    sup = DeviceSupervisor(metrics=None, expected=True, init_grace_s=0.0)
    deadline = time.monotonic() + total_s
    attempts = 0
    retried = False
    try:
        while True:
            attempts += 1
            t0 = time.monotonic()
            ok = sup.probe_once()
            if not ok and str(sup.last_error or "").startswith(
                ("ImportError", "ModuleNotFoundError")
            ):
                # permanent: no amount of waiting installs jax
                return {
                    "state": FATAL,
                    "attempts": attempts,
                    "error": sup.last_error,
                }
            if ok:
                if retried:
                    log("preflight: device ok after retrying")
                from ..backend import resolve_backend

                # the canary initialised the backend: name what answered
                backend = resolve_backend()
                return {
                    "state": HEALTHY,
                    "attempts": attempts,
                    "latency_ms": round(
                        (time.monotonic() - t0) * 1000.0, 3
                    ),
                    "platform": backend.platform,
                    "device_kind": backend.device_kind,
                    "device_count": backend.device_count,
                }
            retried = True
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            log(
                f"preflight: canary failed "
                f"({sup.last_error}); retrying "
                f"({remaining:.0f}s left)"
            )
            time.sleep(min(_RETRY_SLEEP_S, max(0.0, remaining)))
    finally:
        sup.stop()
    return {
        "state": UNREACHABLE,
        "attempts": attempts,
        "budget_s": total_s,
        "error": sup.last_error
        or "backend init blocked (no error raised)",
    }


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="nomad_tpu.device.preflight",
        description="bounded accelerator canary probe",
    )
    parser.add_argument(
        "--budget-s", type=float, default=PREFLIGHT_BUDGET_S,
        help="total retry budget in seconds",
    )
    args = parser.parse_args(argv)
    result = run_preflight(total_s=args.budget_s)
    # the ONE machine-readable line scripts key on
    print("DEVICE_PREFLIGHT " + json.dumps(result), flush=True)
    return 0 if result["state"] in HEALTHY_STATES else 2


if __name__ == "__main__":
    sys.exit(main())
