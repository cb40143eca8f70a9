"""DeviceSupervisor: the accelerator liveness state machine.

One supervisor per server owns three jobs the batch pipeline must never
do inline:

1. **Health probes.**  A watchdog thread launches a tiny canary kernel
   (bounded backend init + ``a + 1`` on an 8-vector, executed on a
   sacrificial thread) on a configurable cadence, so a wedged PJRT
   client is *detected* as LOST instead of hanging whichever thread
   touches the device next.

2. **Launch watchdogs.**  ``guard(stage, fn)`` wraps the batch worker's
   assemble/launch/fetch stages with deadline monitors; a stage that
   exceeds its EWMA-derived budget by a large factor trips the
   supervisor (and raises ``DeviceTimeout`` into the worker's existing
   per-stage error handling, which routes the affected evals to the
   exact sequential path — zero dropped evals).

3. **The HEALTHY -> DEGRADED -> LOST -> RECOVERING state machine.**
   Entering LOST fails the pipeline over to the CPU JAX backend: the
   backend epoch bumps and every subscribed listener (the batch
   worker) flushes its backend-keyed caches, re-jits on CPU and
   disables the sharded mesh path.  The CPU kernels are bit-identical
   to the device kernels (``chip_smoke.py`` checks the chip's f32
   placements against the sequential oracle), so failover preserves
   decision parity.  In LOST the canary keeps probing the *device*; a
   success moves to RECOVERING, and after ``recover_canaries`` consecutive
   passes the pipeline flips back; the registered re-warm hooks (the
   ``NOMAD_TPU_WARM_ON_START`` machinery) then recompile the launch
   shapes for the restored backend, the cold-compile shield covering
   the gap.

State is exported as the ``device.state`` gauge, ``/v1/device``, and —
for failover incidents — a flight-recorder trace
(``device:failover:<n>``) whose ``device.failover`` event names the
tripped watchdog.

Env knobs (config-file equivalents in ``config.DeviceConfig``):

  NOMAD_TPU_SUPERVISOR         1 forces supervision on (0 off) whatever
                               backend JAX resolved (default: on when
                               it resolved an accelerator) — the
                               fault-injection and soak tests run
                               forced-on over the CPU backend
  NOMAD_TPU_PROBE_INTERVAL_S   canary cadence (default 30)
  NOMAD_TPU_PROBE_TIMEOUT_S    canary deadline (default 10)
  NOMAD_TPU_INIT_GRACE_S       deadline floor until the FIRST canary
                               or guarded stage succeeds (default 600)
                               — real PJRT backend init takes tens of
                               seconds, and a cold start must not read
                               as a wedge
  NOMAD_TPU_WATCHDOG_MIN_S     floor (default 5) of the launch budget,
                               which is 20 x the stage's EWMA
  NOMAD_TPU_WATCHDOG_MAX_S     budget ceiling (default 120)
  NOMAD_TPU_LOST_PROBES        consecutive canary failures past
                               DEGRADED before LOST (default 2)
  NOMAD_TPU_RECOVER_CANARIES   consecutive passes before flipping back
                               (default 3)
"""
from __future__ import annotations

import itertools
import logging
import os
import threading
import time
from collections import deque
from contextlib import nullcontext
from typing import Callable, Dict, List, Optional

LOG = logging.getLogger("nomad_tpu.device")

from ..backend import Backend
from ..telemetry import percentile as _percentile
from ..trace import TRACE
from .faults import FAULT_ENV, FaultPlan
from .watchdog import BudgetTracker, DeviceTimeout, bounded_call

# -- states -----------------------------------------------------------

CPU_ONLY = "CPU_ONLY"  # JAX resolved no accelerator; supervision idle
HEALTHY = "HEALTHY"
DEGRADED = "DEGRADED"
LOST = "LOST"
RECOVERING = "RECOVERING"

# the device.state gauge encoding (documented in docs/ARCHITECTURE.md)
STATE_CODES = {
    CPU_ONLY: 0,
    HEALTHY: 1,
    DEGRADED: 2,
    LOST: 3,
    RECOVERING: 4,
}

# pipeline-facing: in these states launches target the device backend
_DEVICE_STATES = frozenset({CPU_ONLY, HEALTHY, DEGRADED})

# -- metric registry ---------------------------------------------------
# every device.* name the supervisor emits, zero-registered at start so
# prometheus_text() exports the whole family before the first incident
# (tools/check_stage_accounting.py lints emissions against these)
METRIC_COUNTERS = frozenset(
    {
        "device.failover",
        "device.recovered",
        "device.canary_ok",
        "device.canary_fail",
        "device.watchdog_trips",
        "device.probe_timeouts",
    }
)
METRIC_GAUGES = frozenset(
    {
        "device.state",
        "device.backend_epoch",
    }
)
METRIC_SAMPLES = frozenset(
    {
        "device.probe_latency_ms",
        # failover detect-to-resume: LOST transition to the restored
        # HEALTHY flip, the latency the SLO engine's
        # failover_detect_to_resume objective grades
        "device.failover_resume_ms",
    }
)

# deadline for one post-recovery re-warm hook: generous (XLA compiles
# for every warmed shape), but bounded — a device that re-wedges
# mid-warm must not hang the probe thread that supervises it
REWARM_BUDGET_S = 600.0

# ring of recent probe latencies backing the /v1/device + bench
# percentile summaries (independent of any Metrics sink)
_PROBE_RING = 256
# transitions retained for /v1/device history
_HISTORY = 64

_INCIDENT_SEQ = itertools.count(1)


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        LOG.warning("invalid %s=%r; using %s", name, raw, default)
        return default


class DeviceSupervisor:
    """Owns accelerator liveness for one server process."""

    def __init__(
        self,
        metrics=None,
        config=None,
        canary: Optional[Callable[[], object]] = None,
        expected: Optional[bool] = None,
        backend: Optional[Backend] = None,
        probe_interval_s: Optional[float] = None,
        probe_timeout_s: Optional[float] = None,
        watchdog_min_s: Optional[float] = None,
        watchdog_max_s: Optional[float] = None,
        lost_probes: Optional[int] = None,
        recover_canaries: Optional[int] = None,
        init_grace_s: Optional[float] = None,
    ) -> None:
        def opt(value, cfg_attr, env, default):
            if value is not None:
                return value
            if config is not None and getattr(
                config, cfg_attr, None
            ) is not None:
                return getattr(config, cfg_attr)
            return _env_float(env, default)

        self.metrics = metrics
        self.faults = FaultPlan.from_env()
        self.probe_interval_s = float(
            opt(probe_interval_s, "probe_interval_s",
                "NOMAD_TPU_PROBE_INTERVAL_S", 30.0)
        )
        self.probe_timeout_s = float(
            opt(probe_timeout_s, "probe_timeout_s",
                "NOMAD_TPU_PROBE_TIMEOUT_S", 10.0)
        )
        self.lost_probes = max(1, int(
            opt(lost_probes, "lost_probes", "NOMAD_TPU_LOST_PROBES", 2)
        ))
        self.recover_canaries = max(1, int(
            opt(recover_canaries, "recover_canaries",
                "NOMAD_TPU_RECOVER_CANARIES", 3)
        ))
        # deadline floor until the device has answered ONCE: first
        # contact pays full PJRT backend init (tens of seconds on real
        # hardware — this repo's own bench history budgeted 600s for
        # it), which must not read as a wedge
        self.init_grace_s = float(
            opt(init_grace_s, "init_grace_s",
                "NOMAD_TPU_INIT_GRACE_S", 600.0)
        )
        self._device_ready = False
        self.budgets = BudgetTracker(
            min_s=float(
                opt(watchdog_min_s, "watchdog_min_s",
                    "NOMAD_TPU_WATCHDOG_MIN_S", 5.0)
            ),
            max_s=float(
                opt(watchdog_max_s, "watchdog_max_s",
                    "NOMAD_TPU_WATCHDOG_MAX_S", 120.0)
            ),
        )
        self._canary = canary or self._default_canary
        # the backend JAX resolved (nomad_tpu.backend.resolve_backend,
        # passed in by the server at start); None = this process runs
        # no kernels (a sequential-oracle server never touches JAX)
        self.backend = backend
        self.expected = (
            expected
            if expected is not None
            else self._accelerator_expected(backend)
        )
        self._state = HEALTHY if self.expected else CPU_ONLY
        self.backend_epoch = 0
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._listeners: List[Callable] = []
        self._warm_hooks: List[Callable] = []
        self._history: deque = deque(maxlen=_HISTORY)
        self._probe_ring: deque = deque(maxlen=_PROBE_RING)
        self._canary_fail_streak = 0
        self._recover_streak = 0
        # single-flight canary: backend init is process-wide and
        # memoized behind a lock, so parallel probe attempts against a
        # wedged device would only stack sacrificial threads on the
        # same blocked call (the old bench preflight kept ONE prober
        # for exactly this reason).  While a canary is still in
        # flight, later probes report the wedge instantly instead of
        # spawning another thread.
        self._canary_lock = threading.Lock()
        self._canary_inflight = False
        self._canary_started = 0.0
        # generation counter orphans a parked attempt when the
        # relaunch window passes, so its eventual finally-clear can't
        # clobber a newer attempt's in-flight flag
        self._canary_gen = 0
        self.failover_count = 0
        self.recovered_count = 0
        self.watchdog_trips = 0
        self.canary_ok = 0
        self.canary_fail = 0
        self.probe_timeouts = 0
        self.last_error: Optional[str] = None
        self._incident: Optional[str] = None
        self.last_incident: Optional[str] = None
        # detect-to-resume stopwatch: stamped at failover, read (and
        # cleared) when the restored flip samples
        # device.failover_resume_ms
        self._failover_at: Optional[float] = None
        # unhealthy-time accounting (bench time_degraded_s): cumulative
        # seconds spent outside HEALTHY/CPU_ONLY plus the live segment
        self._unhealthy_accum = 0.0
        self._unhealthy_since: Optional[float] = None
        self._since_wall = time.time()
        self._cpu_device = None
        self._register_metrics()
        # happens-before sanitizer (NOMAD_TPU_TSAN=1)
        from ..tsan import maybe_instrument

        maybe_instrument(self, "DeviceSupervisor")

    # -- construction helpers ------------------------------------------

    @staticmethod
    def _accelerator_expected(backend: Optional[Backend]) -> bool:
        forced = os.environ.get("NOMAD_TPU_SUPERVISOR")
        if forced == "1":
            return True
        if forced == "0":
            return False
        if os.environ.get(FAULT_ENV, "").strip():
            # an armed fault plan simulates an accelerator: the
            # supervisor must be live for the faults to mean anything
            return True
        # what JAX resolved decides, never how JAX_PLATFORMS is spelt:
        # on a TPU host JAX picks the chip with the variable unset
        return backend is not None and backend.accelerated

    def _register_metrics(self) -> None:
        metrics = self.metrics
        if metrics is None:
            return
        metrics.preregister(
            counters=METRIC_COUNTERS,
            gauges=METRIC_GAUGES,
            samples=METRIC_SAMPLES,
        )
        metrics.set_gauge("device.state", STATE_CODES[self._state])
        metrics.set_gauge("device.backend_epoch", 0.0)

    def _incr(self, name: str) -> None:
        if self.metrics is not None:
            self.metrics.incr(name)

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        """Start the probe thread (no-op when no accelerator is
        expected — CPU-only test servers must stay thread-free)."""
        if not self.expected:
            return
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop.clear()
            self.faults.stop_event.clear()
            self._thread = threading.Thread(
                target=self._probe_loop,
                name="device-supervisor",
                daemon=True,
            )
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        # release every sacrificial thread parked on an injected wedge
        self.faults.stop_event.set()

    def _probe_loop(self) -> None:
        while not self._stop.wait(self.probe_interval_s):
            try:
                self.probe_once()
            except Exception:  # noqa: BLE001 — supervision must survive
                LOG.exception("device probe crashed")

    # -- state queries -------------------------------------------------

    def state(self) -> str:
        return self._state

    def failed_over(self) -> bool:
        """True while the pipeline must target the CPU backend."""
        return self._state not in _DEVICE_STATES

    def device_available(self) -> bool:
        """True when launches may target the accelerator."""
        return self.expected and self._state in (HEALTHY, DEGRADED)

    def jax_device(self):
        """Explicit placement target for device_put: the CPU backend
        while failed over, None (jax's default device) otherwise."""
        if not self.failed_over():
            return None
        if self._cpu_device is None:
            try:
                import jax

                self._cpu_device = jax.devices("cpu")[0]
            except Exception:  # noqa: BLE001 — placement is best-effort
                return None
        return self._cpu_device

    def subscribe(self, fn: Callable) -> None:
        """Register a backend-transition listener
        ``fn(old_state, new_state, reason)`` (called synchronously on
        the transitioning thread, after the epoch bump)."""
        self._listeners.append(fn)

    def add_warm_hook(self, fn: Callable) -> None:
        """Register a re-warm hook run (best-effort) right after a
        recovered supervisor flips the pipeline back to the device —
        the NOMAD_TPU_WARM_ON_START machinery, reused so the restored
        backend's launch shapes recompile under the new epoch (until
        then the cold-compile shield routes evals to the exact
        sequential path).  Idempotent: leadership re-establishment
        re-registers the same hooks, and duplicates would multiply the
        post-recovery compile work."""
        if fn not in self._warm_hooks:
            self._warm_hooks.append(fn)

    # -- launch watchdogs ----------------------------------------------

    def _effective_budget(self, stage: str) -> float:
        """Stage deadline, floored to the init grace until the device
        has answered once — the first guarded call pays full backend
        init, which must not read as a wedge."""
        budget = self.budgets.budget(stage)
        if not self._device_ready:
            return max(budget, self.init_grace_s)
        return budget

    def guard(
        self, stage: str, fn: Callable, eval_id: Optional[str] = None
    ):
        """Run one pipeline stage under a deadline monitor.  While no
        accelerator is expected (or the pipeline is already failed over
        to CPU — the backend hot failover exists because CPU cannot
        wedge) the call passes straight through with zero overhead."""
        if not self.expected or self.failed_over():
            return fn()
        budget = self._effective_budget(stage)

        def wrapped():
            self.faults.stage_hook(stage, budget)
            return fn()

        t0 = time.monotonic()
        try:
            result = bounded_call(
                wrapped, budget, name=f"device-{stage}", stage=stage
            )
        except DeviceTimeout:
            self._watchdog_tripped(stage, budget, eval_id)
            raise
        self._device_ready = True
        self.budgets.note(stage, time.monotonic() - t0)
        return result

    def _watchdog_tripped(
        self, stage: str, budget_s: float, eval_id: Optional[str]
    ) -> None:
        self.watchdog_trips += 1
        self._incr("device.watchdog_trips")
        self.last_error = (
            f"watchdog: {stage} exceeded {budget_s:.2f}s budget"
        )
        if eval_id:
            # name the tripped watchdog on the eval that paid for it
            TRACE.event(
                eval_id, "device.watchdog_trip",
                stage=stage, budget_ms=budget_s * 1000.0,
            )
        LOG.warning(
            "device watchdog tripped: stage %s exceeded %.2fs budget",
            stage, budget_s,
        )
        from ..decisions import DECISIONS

        ewma = self.budgets.ewma(stage)
        DECISIONS.record(
            "watchdog_budget",
            "trip",
            inputs={
                "stage": stage,
                "budget_s": round(budget_s, 3),
                "ewma_s": round(ewma, 4) if ewma is not None else None,
                "factor": self.budgets.factor,
                "backend_epoch": self.backend_epoch,
            },
            alternatives=["keep_waiting"],
            outcome="lost",
            trace_id=eval_id or self._incident or "",
            metrics=self.metrics,
        )
        self._transition(LOST, f"watchdog:{stage}", stage=stage)

    def trip(self, stage: str = "manual") -> None:
        """Operator/test surface: force a LOST transition (and the
        failover it implies) as if a watchdog had tripped."""
        if not self.expected:
            return
        self._transition(LOST, f"watchdog:{stage}", stage=stage)

    # -- health probes -------------------------------------------------

    def _default_canary(self):
        """Bounded-init canary: put an 8-vector on the probed backend
        and run a jitted ``a + 1`` — exactly the kernel the old
        ``bench.py`` preflight used, small enough to be free and
        end-to-end enough (init + compile + execute + fetch) to catch
        every wedge mode seen so far."""
        import jax
        import jax.numpy as jnp

        # jax's default device: the accelerator wherever one resolved
        # (a failed-over pipeline places on the CPU explicitly, so the
        # canary keeps probing the device it must recover)
        x = jax.device_put(jnp.ones(8), jax.devices()[0])
        # nomadlint: disable=jit-purity -- deliberate per-probe retrace: the canary must exercise the FULL trace+compile+execute+fetch path each probe (a cached wrapper would skip the compile wedge mode)
        return float(jax.jit(lambda a: a + 1)(x).sum())

    def _canary_call(self):
        self.faults.canary_hook()
        return self._canary()

    def _canary_relaunch_s(self) -> float:
        """How long an in-flight (presumed wedged) canary attempt
        blocks new attempts.  Short enough that a device whose old
        parked RPC never returns is still re-probed (the documented
        LOST -> RECOVERING path must stay reachable), long enough that
        a persistent wedge leaks at most ~one abandoned thread per
        window instead of one per probe."""
        return max(60.0, 4.0 * self.probe_timeout_s)

    def _canary_bounded(self):
        """One bounded canary attempt, single-flight: while a previous
        attempt's sacrificial thread is still parked inside a wedged
        call, report the wedge immediately instead of stacking another
        thread behind the same process-wide memoized backend init —
        until the relaunch window passes, after which the parked
        attempt is orphaned and a fresh probe runs (device recovery
        must stay observable even when the old call never returns)."""
        now = time.monotonic()
        with self._canary_lock:
            if self._canary_inflight:
                if (
                    now - self._canary_started
                    < self._canary_relaunch_s()
                ):
                    raise DeviceTimeout(
                        "canary_inflight", self.probe_timeout_s
                    )
                # orphan the parked attempt: bump the generation so
                # its eventual finally-clear becomes a no-op
                self._canary_gen += 1
            self._canary_inflight = True
            self._canary_started = now
            gen = self._canary_gen

        def call():
            try:
                return self._canary_call()
            finally:
                with self._canary_lock:
                    if self._canary_gen == gen:
                        self._canary_inflight = False

        timeout = self.probe_timeout_s
        if not self._device_ready:
            timeout = max(timeout, self.init_grace_s)
        return bounded_call(
            call, timeout, name="device-canary", stage="canary"
        )

    def probe_once(self) -> bool:
        """Run one canary probe and feed the state machine.  Returns
        the probe verdict (True = device answered in time)."""
        if not self.expected:
            return True
        t0 = time.monotonic()
        ok = False
        timed_out = False
        measured = True
        err: Optional[str] = None
        try:
            self._canary_bounded()
            ok = True
        except DeviceTimeout as exc:
            timed_out = True
            err = str(exc)
            # an instant still-in-flight verdict is wedge evidence,
            # not a latency measurement
            measured = exc.stage != "canary_inflight"
        except Exception as exc:  # noqa: BLE001 — any failure counts
            err = f"{type(exc).__name__}: {exc}"
        dt = time.monotonic() - t0
        if measured:
            with self._lock:
                # status() sorts this ring from other threads; appends
                # must not race its iteration
                self._probe_ring.append(dt * 1000.0)
            if self.metrics is not None:
                self.metrics.add_sample(
                    "device.probe_latency_ms", dt * 1000.0
                )
        incident = self._incident
        if incident is not None:
            TRACE.add_span(
                incident, "device.probe", t0, dt,
                ok=ok, timeout=timed_out,
            )
        if ok:
            self._note_canary_ok()
        else:
            self._note_canary_fail(err, timed_out)
        return ok

    def _note_canary_ok(self) -> None:
        self.canary_ok += 1
        self._incr("device.canary_ok")
        self._canary_fail_streak = 0
        self._device_ready = True
        state = self._state
        if state == DEGRADED:
            self._transition(HEALTHY, "canary_ok")
        elif state == LOST:
            self._recover_streak = 1
            self._transition(RECOVERING, "canary_ok")
        elif state == RECOVERING:
            self._recover_streak += 1
            if self._recover_streak >= self.recover_canaries:
                self._transition(
                    HEALTHY,
                    f"recovered after {self._recover_streak} canaries",
                )
                # re-warm AFTER the flip: the hooks must compile for
                # the restored backend under the post-restore epoch
                # (before the flip they would target the CPU fallback
                # and the restore's cache flush would discard every
                # warmed shape).  Until they finish, the cold-compile
                # shield keeps evals on the exact sequential path.
                self._run_warm_hooks()

    def _note_canary_fail(
        self, err: Optional[str], timed_out: bool
    ) -> None:
        self.canary_fail += 1
        self._incr("device.canary_fail")
        self.last_error = err
        self._canary_fail_streak += 1
        state = self._state
        if timed_out:
            self.probe_timeouts += 1
            self._incr("device.probe_timeouts")
            # a canary that BLOCKS is a wedge, not a degradation — the
            # next pipeline launch would hang the same way
            if state not in (LOST,):
                self._transition(LOST, "probe_timeout")
            return
        if state == HEALTHY:
            self._transition(DEGRADED, f"canary_fail: {err}")
        elif state == DEGRADED:
            if self._canary_fail_streak >= 1 + self.lost_probes:
                self._transition(
                    LOST,
                    f"{self._canary_fail_streak} consecutive canary "
                    "failures",
                )
        elif state == RECOVERING:
            self._transition(LOST, f"canary_fail_in_recovery: {err}")

    def _run_warm_hooks(self) -> None:
        """Re-warm the launch shapes for the just-restored backend
        (best-effort: a warm failure only means the first
        post-recovery launches pay their compiles through the
        cold-compile shield).  Runs after the restore flip, so the
        spans land on the (already closed) incident trace via its
        retained id."""
        tid = self.last_incident
        for hook in self._warm_hooks:
            try:
                with TRACE.span(
                    tid or "", "device.rewarm"
                ) if tid else nullcontext():
                    # bounded: a device that re-wedges mid-warm must
                    # not hang the probe thread; the next canaries
                    # will re-detect it
                    bounded_call(
                        hook, REWARM_BUDGET_S,
                        name="device-rewarm", stage="rewarm",
                    )
            except Exception:  # noqa: BLE001
                LOG.exception("device re-warm hook failed")

    # -- transitions ---------------------------------------------------

    def _transition(
        self, new: str, reason: str, stage: Optional[str] = None
    ) -> None:
        with self._lock:
            old = self._state
            if old == new or old == CPU_ONLY:
                return
            self._state = new
            now = time.monotonic()
            self._since_wall = time.time()
            # unhealthy-time accounting
            if old == HEALTHY and new != HEALTHY:
                self._unhealthy_since = now
            elif new == HEALTHY and self._unhealthy_since is not None:
                self._unhealthy_accum += now - self._unhealthy_since
                self._unhealthy_since = None
            failover = new == LOST and old in (HEALTHY, DEGRADED)
            restored = new == HEALTHY and old == RECOVERING
            if failover or restored:
                self.backend_epoch += 1
            failover_at = None
            if failover:
                self.failover_count += 1
                # detect-to-resume stopwatch start: sampled (and
                # cleared) by the matching restored transition
                self._failover_at = now
            if restored:
                self.recovered_count += 1
                failover_at = self._failover_at
                self._failover_at = None
            self._history.append(
                {
                    "at": self._since_wall,
                    "from": old,
                    "to": new,
                    "reason": reason,
                }
            )
        LOG.warning(
            "device supervisor: %s -> %s (%s)", old, new, reason
        )
        if self.metrics is not None:
            self.metrics.set_gauge("device.state", STATE_CODES[new])
            self.metrics.set_gauge(
                "device.backend_epoch", float(self.backend_epoch)
            )
        if failover:
            self._incr("device.failover")
            self._open_incident(old, reason, stage)
        incident = self._incident
        if incident is not None:
            TRACE.event(
                incident, "device.state_change",
                state_from=old, state_to=new, reason=reason,
            )
        if failover or restored:
            # backend flip: listeners flush their backend-keyed caches
            # before any further launch can read stale device state
            span_ctx = (
                TRACE.span(incident, "device.flush", to=new)
                if incident is not None
                else nullcontext()
            )
            with span_ctx:
                for listener in list(self._listeners):
                    try:
                        listener(old, new, reason)
                    except Exception:  # noqa: BLE001
                        LOG.exception(
                            "device transition listener failed"
                        )
        if restored:
            self._incr("device.recovered")
            if failover_at is not None and self.metrics is not None:
                self.metrics.add_sample(
                    "device.failover_resume_ms",
                    (time.monotonic() - failover_at) * 1000.0,
                    exemplar=self._incident or "",
                )
            self._close_incident(reason)

    def _open_incident(
        self, old: str, reason: str, stage: Optional[str]
    ) -> None:
        tid = f"device:failover:{next(_INCIDENT_SEQ)}"
        self._incident = tid
        self.last_incident = tid
        TRACE.begin(tid, root_span="device.incident", kind="device")
        TRACE.event(
            tid, "device.failover",
            watchdog=stage or "", reason=reason, state_from=old,
        )

    def _close_incident(self, reason: str) -> None:
        tid = self._incident
        if tid is None:
            return
        TRACE.event(
            tid, "device.recover",
            reason=reason, canaries=self._recover_streak,
        )
        TRACE.finish(tid, "recovered")
        self._incident = None

    # -- status --------------------------------------------------------

    def time_degraded_s(self) -> float:
        accum = self._unhealthy_accum
        since = self._unhealthy_since
        if since is not None:
            accum += time.monotonic() - since
        return accum

    def status(self) -> Dict:
        """The /v1/device payload (also the bench's
        ``device_supervisor`` block source)."""
        with self._lock:
            ordered = sorted(self._probe_ring)
            history = list(self._history)
            return self._status_locked(ordered, history)

    def _status_locked(self, ordered, history) -> Dict:
        # the whole payload is read under self._lock (RLock): /v1/device
        # polls race the probe thread's transitions, and a torn
        # multi-field view (state from before a failover, epoch from
        # after) would mislead exactly the operator debugging it
        backend = self.backend
        return {
            "enabled": self.expected,
            "state": self._state,
            "state_code": STATE_CODES[self._state],
            # what JAX resolved at server start (None: no kernel path)
            "platform": backend.platform if backend else None,
            "device_kind": backend.device_kind if backend else None,
            "device_count": backend.device_count if backend else 0,
            # where launches go right now: the resolved platform, the
            # cpu while failed over, "none" without a kernel path
            "backend": (
                "cpu"
                if self.failed_over()
                else backend.platform if backend else "none"
            ),
            "backend_epoch": self.backend_epoch,
            # False until the device answered once; deadlines are
            # floored to init_grace_s while it is
            "device_ready": self._device_ready,
            "since": self._since_wall,
            "failover_count": self.failover_count,
            "recovered_count": self.recovered_count,
            "watchdog_trips": self.watchdog_trips,
            "canary_ok": self.canary_ok,
            "canary_fail": self.canary_fail,
            "probe_timeouts": self.probe_timeouts,
            "time_degraded_s": round(self.time_degraded_s(), 3),
            "probe_latency_ms": {
                "count": len(ordered),
                "p50": round(_percentile(ordered, 0.50), 3),
                "p99": round(_percentile(ordered, 0.99), 3),
            },
            "budgets": self.budgets.snapshot(),
            "probe_interval_s": self.probe_interval_s,
            "probe_timeout_s": self.probe_timeout_s,
            "faults": self.faults.describe(),
            "last_error": self.last_error,
            "last_incident": self.last_incident,
            "history": history,
        }


