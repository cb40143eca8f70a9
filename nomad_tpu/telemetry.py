"""Telemetry: in-memory metrics with counters, gauges and timing samples
(reference go-metrics usage; sinks like statsd/prometheus are
export-format adapters over this store — `dump()` is the /v1/metrics
payload, `prometheus_text()` the scrape format).
"""
from __future__ import annotations

import os
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Tuple

# cluster-scope observability telemetry, zero-registered at Server
# construction (the `cluster-obs-metrics` nomadlint rule enforces
# registry membership for every obs.* / cluster.* emission)
CLUSTER_OBS_COUNTERS = (
    # leader side of cross-server trace stitching (cluster.py)
    "cluster.segments_absorbed",  # follower segments stitched in
    "cluster.segment_spans",  # spans absorbed from segments
    # leader fan-in queries (/v1/cluster/*)
    "cluster.fanin_queries",
    "cluster.fanin_unreachable",  # per-peer timeouts/failures
    # metric time-series history (MetricsHistory below)
    "obs.history_snapshots",
)
CLUSTER_OBS_GAUGES = (
    "obs.history_windows",  # windows currently retained in the ring
)


def obs_history_enabled() -> bool:
    return os.environ.get("NOMAD_TPU_OBS_HISTORY", "1") != "0"


def obs_history_windows() -> int:
    try:
        return max(
            2, int(os.environ.get("NOMAD_TPU_OBS_HISTORY_N", "60"))
        )
    except ValueError:
        return 60


# seconds between history snapshot windows (60 windows by default: a
# 10-minute rolling view)
OBS_HISTORY_INTERVAL_S = 10.0


def percentile(ordered: List[float], q: float) -> float:
    """Nearest-rank percentile over a pre-sorted list — the single
    shared implementation (summary snapshots here, the device
    supervisor's probe-latency status) so /v1/metrics and /v1/device
    can never report different p99s for the same ring."""
    if not ordered:
        return 0.0
    idx = min(len(ordered) - 1, int(round(q * (len(ordered) - 1))))
    return ordered[idx]


class _Summary:
    __slots__ = (
        "count", "total", "min", "max", "_ring", "_ring_ex",
        "_ring_pos",
    )

    # sliding window for percentile estimates: large enough for a
    # stable p99 over recent traffic, small enough to stay O(1) memory
    RING = 2048
    # exemplar trace ids reported per snapshot (the p99 ring entries)
    EXEMPLARS = 4

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        # -inf, not 0.0: an all-negative sample stream must report its
        # true (negative) max, mirroring min's +inf idiom
        self.max = float("-inf")
        self._ring: List[float] = []
        # exemplar per ring slot: the trace (eval) id that produced
        # the sample, or None — links a slow percentile to the eval
        # that caused it (/v1/traces/<id>)
        self._ring_ex: List[Optional[str]] = []
        self._ring_pos = 0

    def add(self, value: float, exemplar: Optional[str] = None) -> None:
        self.count += 1
        self.total += value
        self.min = min(self.min, value)
        self.max = max(self.max, value)
        if len(self._ring) < self.RING:
            self._ring.append(value)
            self._ring_ex.append(exemplar)
        else:
            self._ring[self._ring_pos] = value
            self._ring_ex[self._ring_pos] = exemplar
            self._ring_pos = (self._ring_pos + 1) % self.RING

    def _percentile(self, ordered: List[float], q: float) -> float:
        return percentile(ordered, q)

    def _exemplars(self, p99: float) -> List[Dict]:
        """Trace refs of the ring entries at or above p99, slowest
        first — the samples an operator will want to explain.  A ref
        is whatever the caller passed (callers pass eval ids), and
        /v1/traces/<ref> resolves it — to the newest generation when
        the eval was redelivered."""
        tagged = sorted(
            (
                (v, ex)
                for v, ex in zip(self._ring, self._ring_ex)
                if ex is not None and v >= p99
            ),
            reverse=True,
        )
        return [
            {"value": v, "trace_id": ex}
            for v, ex in tagged[: self.EXEMPLARS]
        ]

    def snapshot(self) -> Dict:
        ordered = sorted(self._ring)
        p99 = self._percentile(ordered, 0.99)
        return {
            "count": self.count,
            "sum": self.total,
            "mean": self.total / self.count if self.count else 0.0,
            "min": self.min if self.count else 0.0,
            "max": self.max if self.count else 0.0,
            # percentiles over the sliding window (last RING samples)
            "p50": self._percentile(ordered, 0.50),
            "p90": self._percentile(ordered, 0.90),
            "p99": p99,
            # trace exemplars for the slow tail (eval flight recorder)
            "exemplars": self._exemplars(p99),
        }


class Metrics:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = defaultdict(float)
        self._gauges: Dict[str, float] = {}
        self._samples: Dict[str, _Summary] = defaultdict(_Summary)
        # counters another module keeps as plain integers (no lock, no
        # call a count) and this registry reads when it is read
        self._live_counters: List[Callable[[], Dict[str, float]]] = []
        # happens-before sanitizer (NOMAD_TPU_TSAN=1)
        from .tsan import maybe_instrument

        maybe_instrument(self, "Metrics")

    def incr(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] += value

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def add_sample(
        self, name: str, value: float,
        exemplar: Optional[str] = None,
    ) -> None:
        with self._lock:
            self._samples[name].add(value, exemplar)

    def add_samples(
        self, samples, exemplar: Optional[str] = None
    ) -> None:
        """Several (name, value) samples under one lock take (the
        flight recorder's fold adds a trace's whole layer split)."""
        store = self._samples
        with self._lock:
            for name, value in samples:
                store[name].add(value, exemplar)

    def attach_live_counters(
        self, read: Callable[[], Dict[str, float]]
    ) -> None:
        """`read()` gives counters by name as they stand; every read of
        this registry shows them in place of a stored value."""
        with self._lock:
            self._live_counters.append(read)

    def _counters_now(self) -> Dict[str, float]:
        """The stored counters under the live ones (lock held)."""
        counters = dict(self._counters)
        for read in self._live_counters:
            counters.update(read())
        return counters

    def get_counter(self, name: str) -> float:
        """O(1) single-counter read (tests/operators polling one hot
        counter — e.g. the optimistic-replay `replay.*` family —
        shouldn't pay for a full dump() copy)."""
        with self._lock:
            for read in self._live_counters:
                value = read().get(name)
                if value is not None:
                    return value
            return self._counters.get(name, 0.0)

    def get_gauge(self, name: str) -> Optional[float]:
        """O(1) single-gauge read; None when the gauge was never set."""
        with self._lock:
            return self._gauges.get(name)

    def get_sample(self, name: str) -> Optional[Dict]:
        """Snapshot of ONE summary (None when never sampled) without
        paying for a full dump() copy — the overload controller polls
        the flight-recorder latency p99 at mode-evaluation cadence."""
        with self._lock:
            summary = self._samples.get(name)
            return summary.snapshot() if summary is not None else None

    def preregister(
        self,
        counters=(),
        gauges=(),
        samples=(),
    ) -> None:
        """Zero-register metric names so they appear on /v1/metrics and
        prometheus scrapes from process start (a `device.failover`
        counter that only materializes DURING an incident would make
        absence-of-series indistinguishable from absence-of-failures
        on every dashboard)."""
        with self._lock:
            for name in counters:
                self._counters[name] += 0.0
            for name in gauges:
                self._gauges.setdefault(name, 0.0)
            for name in samples:
                self._samples[name]  # defaultdict materializes it

    @contextmanager
    def measure(self, name: str):
        """(reference go-metrics MeasureSince)"""
        start = time.monotonic()
        try:
            yield
        finally:
            self.add_sample(name, (time.monotonic() - start) * 1000.0)

    def dump(self) -> Dict:
        with self._lock:
            return {
                "counters": self._counters_now(),
                "gauges": dict(self._gauges),
                "samples": {
                    k: s.snapshot() for k, s in self._samples.items()
                },
            }

    def dump_lean(self) -> Dict:
        """dump() without the per-summary exemplar scan — the history
        snapshotter's cadence payload (exemplar trace refs are a
        point-in-time debugging surface, not a time series)."""
        with self._lock:
            return {
                "counters": self._counters_now(),
                "gauges": dict(self._gauges),
                "samples": {
                    k: {
                        "count": s.count,
                        "p50": percentile(sorted(s._ring), 0.50),
                        "p99": percentile(sorted(s._ring), 0.99),
                    }
                    for k, s in self._samples.items()
                },
            }

    def prometheus_text(self) -> str:
        lines: List[str] = []
        # esc() is lossy (both "." and "-" map to "_"), so two
        # distinct store names can collide into one scrape name —
        # which Prometheus rejects as a duplicate series.  First
        # occurrence (sorted order, counters < gauges < summaries)
        # wins; later collisions are skipped with a comment so the
        # scrape stays valid and the loss is visible.
        emitted: set = set()

        def esc(name: str) -> str:
            return name.replace(".", "_").replace("-", "_")

        def claim(name: str) -> Optional[str]:
            base = esc(name)
            if base in emitted:
                lines.append(
                    f"# collision: {name} already emitted as {base}"
                )
                return None
            emitted.add(base)
            return base

        with self._lock:
            for name, value in sorted(self._counters_now().items()):
                base = claim(name)
                if base is None:
                    continue
                lines.append(f"# TYPE {base} counter")
                lines.append(f"{base} {value}")
            for name, value in sorted(self._gauges.items()):
                base = claim(name)
                if base is None:
                    continue
                lines.append(f"# TYPE {base} gauge")
                lines.append(f"{base} {value}")
            for name, summary in sorted(self._samples.items()):
                base = claim(name)
                if base is None:
                    continue
                snap = summary.snapshot()
                lines.append(f"# TYPE {base} summary")
                lines.append(f"{base}_count {snap['count']}")
                lines.append(f"{base}_sum {snap['sum']}")
                for q, key in (
                    ("0.5", "p50"),
                    ("0.9", "p90"),
                    ("0.99", "p99"),
                ):
                    lines.append(
                        f'{base}{{quantile="{q}"}} {snap[key]}'
                    )
        return "\n".join(lines) + "\n"


class MetricsHistory:
    """Fixed-size ring of periodic metric snapshots — the first way to
    see "p99 over the last N minutes" without an external scraper, and
    the training-data surface the future self-tuning controller reads.

    Every ``interval_s`` seconds (10 by default) a snapshot thread
    (`obs-history`) captures all registered counters (cumulative),
    gauges (point-in-time) and sample summaries (count + p50/p99 over
    the summary's sliding window, read at the window boundary) into a
    ``NOMAD_TPU_OBS_HISTORY_N``-deep ring.  Memory is bounded at
    windows x registered-metric-count small floats — sizing math in
    docs/ARCHITECTURE.md "Cluster observability".

    Served as /v1/metrics/history, captured in the operator debug
    bundle, and fanned in cluster-wide via /v1/cluster/* queries.
    """

    def __init__(
        self,
        metrics: Metrics,
        windows: Optional[int] = None,
        interval_s: float = OBS_HISTORY_INTERVAL_S,
    ) -> None:
        self.metrics = metrics
        self.enabled = obs_history_enabled()
        self.windows = (
            windows if windows is not None else obs_history_windows()
        )
        self.interval_s = interval_s
        self._ring: deque = deque(maxlen=self.windows)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------

    def start(self) -> None:
        if not self.enabled or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="obs-history", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=5.0)
        self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.snapshot_once()

    # -- capture -------------------------------------------------------

    def snapshot_once(self) -> Dict:
        """Capture one window (also the debug-bundle/test entry point,
        so a capture never has to wait out the interval)."""
        dump = self.metrics.dump_lean()
        window = {
            "t": time.time(),
            "counters": dump["counters"],
            "gauges": dump["gauges"],
            "samples": dump["samples"],
        }
        with self._lock:
            self._ring.append(window)
            retained = len(self._ring)
        self.metrics.incr("obs.history_snapshots")
        self.metrics.set_gauge("obs.history_windows", float(retained))
        return window

    # -- reads ---------------------------------------------------------

    def to_dict(self) -> Dict:
        """/v1/metrics/history payload: every retained window, oldest
        first, plus the sizing that produced them."""
        with self._lock:
            windows = list(self._ring)
        return {
            "enabled": self.enabled,
            "interval_s": self.interval_s,
            "max_windows": self.windows,
            "windows": windows,
        }

    def series(self, name: str) -> List[Dict]:
        """One metric's time series across the retained windows —
        [{t, value}] for counters/gauges, [{t, count, p50, p99}] for
        samples."""
        with self._lock:
            windows = list(self._ring)
        out: List[Dict] = []
        for w in windows:
            if name in w["samples"]:
                entry = dict(w["samples"][name])
                entry["t"] = w["t"]
                out.append(entry)
            elif name in w["counters"]:
                out.append({"t": w["t"], "value": w["counters"][name]})
            elif name in w["gauges"]:
                out.append({"t": w["t"], "value": w["gauges"][name]})
        return out
