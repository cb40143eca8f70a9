#!/usr/bin/env python3
"""One run of one benchmark cell on the served HTTP path.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): the world from ``--seed``, the server
and its HTTP listener, ``warm_shapes`` for the single-group chunk
ladder, one ``gc.collect()``, then warm-up traffic only: closed loops of
pinned count-1 copies of the job at a few in-flight counts (the traffic
file's ``probe_ramp``), then the cell's own loop, until its warm-up
completions are in and no launch shape has compiled and no evaluation
has gone down the host path for ``QUIET_S`` seconds.  The window opens
on that running loop WITHOUT a drain and closes ``--seconds`` later;
jobs in flight at either edge belong to the side on which they
complete.  Then the loop is stopped and drained, the device's peak
memory is read, the answers are read back, the server is stopped, and
the plain reference replays every evaluation in commit order
(``correct.py``).  A run in whose window a launch shape compiled is no
measurement: it exits with code 3 and prints no result.

``--trace 1`` profiles the LAST seconds of the window (the profiler's
stop takes tens of seconds and so falls after the window); counters and
host-clock layers are read over the whole window, device-trace layers
over the traced part.

The last line of standard output is the contract's JSON object.  With
``--allow-cpu`` (a rehearsal on the CPU, for the tests and for dry runs)
the line carries differently named keys (``rehearsal_*``) that the
driver cannot read as a chip result.  Without it, no TPU is a failure.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import correct, metrics, system, tracered  # noqa: E402
from benchmark.loadgen import Client, LoadGen  # noqa: E402
from benchmark.manifest import Manifest, ManifestError  # noqa: E402
from benchmark.stream import JobStream, ProbeStream  # noqa: E402
from benchmark.world import make_world  # noqa: E402

TRACE_PART_S = 3.0  # the profiler covers the last this much of the window
DRAIN_GRACE_S = 60.0
QUIET_S = 3.0  # no launch-shape compile, no host-path eval, before the window
PROBE_QUIET_S = 1.0  # the same between the steps of the probe ramp
LAUNCH_JIT = "jit(chained_plan_picks_cols"  # a launch shape's compile event
MAX_PINS = 256
GAP_SPANS = (
    "replay.speculate", "replay.commit", "replay.commit_wait",
    "batch_worker.launch", "batch_worker.simulate", "batch_worker.assemble",
    "batch_worker.fetch", "batch_worker.admit", "plan.evaluate", "plan.apply",
)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process was started (exec), from /proc."""
    try:
        with open("/proc/self/stat", encoding="ascii") as fh:
            fields = fh.read().rsplit(")", 1)[1].split()
        start_ticks = float(fields[19])
        with open("/proc/uptime", encoding="ascii") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


def host_steal_s() -> float:
    """Seconds the hypervisor ran something else on this machine's
    cores (``steal`` of /proc/stat, all cores summed)."""
    try:
        with open("/proc/stat", encoding="ascii") as fh:
            fields = fh.readline().split()
        return float(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


class GcClock:
    """Seconds Python's collector held the process, from gc.callbacks.
    It observes; no threshold, freeze or disable anywhere."""

    def __init__(self) -> None:
        self.pause_s = 0.0
        self.collections = 0
        self.longest_s = 0.0
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        elif self._t:
            dt = time.monotonic() - self._t
            self.pause_s += dt
            self.collections += 1
            self.longest_s = max(self.longest_s, dt)

    def snapshot(self) -> tuple:
        return self.pause_s, self.collections


class CompileClock:
    """Instants of JAX's backend-compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.at: list = []
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == self.EVENT:
            self.at.append((time.monotonic(), secs, str(kw.get("fun_name", "?"))))

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _s, _n in self.at if t0 <= t < t1)

    def launch_shapes(self, t0: float = 0.0, t1: float = float("inf")) -> list:
        """(instant, seconds, name) of the launch shapes compiled (or
        fetched from the compile cache) in [t0, t1)."""
        return [
            e for e in list(self.at)
            if t0 <= e[0] < t1 and e[2].startswith(LAUNCH_JIT)
        ]


def metrics_snapshot(client: Client) -> dict:
    """``/v1/metrics`` as the window's edges read it."""
    doc = client.get_json("/v1/metrics")
    return {
        "counters": doc.get("counters", {}),
        "samples": {
            k: {"count": v.get("count", 0), "sum_ms": v.get("sum", 0.0)}
            for k, v in doc.get("samples", {}).items()
        },
        "gauges": doc.get("gauges", {}),
    }


def delta(after: dict, before: dict) -> dict:
    counters = {
        k: v - before["counters"].get(k, 0.0)
        for k, v in after["counters"].items()
    }
    samples = {}
    for k, v in after["samples"].items():
        b = before["samples"].get(k, {"count": 0, "sum_ms": 0.0})
        samples[k] = {
            "count": v["count"] - b["count"],
            "sum_ms": v["sum_ms"] - b["sum_ms"],
        }
    return {"counters": counters, "samples": samples}


class Quiet:
    """Whether the launch shapes have settled: no launch shape compiled
    and no evaluation took the host path for some seconds.  Read
    from JAX's compile events and ``/v1/metrics`` alone."""

    def __init__(self, client: Client, compiles: CompileClock) -> None:
        self.client = client
        self.compiles = compiles
        self.host_path = -1.0
        self.moved_at = time.monotonic()

    def poll(self) -> float:
        """Seconds since a launch shape last compiled or an evaluation
        last took the host path."""
        snap = metrics_snapshot(self.client)
        now = time.monotonic()
        host_path = system.host_path_evals(snap["counters"], snap["samples"])
        if host_path != self.host_path:
            self.host_path, self.moved_at = host_path, now
        shapes = self.compiles.launch_shapes()
        last = max([self.moved_at] + [t for t, _s, _n in shapes[-1:]])
        return now - last


def warm_up(lg, want: int, quiet: Quiet, quiet_s: float) -> None:
    """Let a running loop go on until ``want`` completions are in and
    the launch shapes have been settled for ``quiet_s`` seconds."""
    t_end = time.monotonic() + 600.0
    while True:
        settled = quiet.poll()
        if lg.completed >= want and settled >= quiet_s:
            return
        if time.monotonic() > t_end:
            raise RuntimeError("warm-up traffic did not settle")
        time.sleep(0.1)


def dump(args, cell: dict, what: str, doc) -> None:
    """A study's file under ``--out``: <cell>.<seed>.<what>.json."""
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{cell['name']}.{args.seed}.{what}.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--allow-cpu", action="store_true",
        help="rehearse on the CPU; prints rehearsal_* keys only",
    )
    ap.add_argument(
        "--rehearsal-scale", type=float, default=1.0,
        help="with --allow-cpu: shrink the fleet by this factor",
    )
    ap.add_argument(
        "--out", default="",
        help="directory for this run's request log and details",
    )
    return ap.parse_args(argv)


def run(args, manifest: Manifest) -> tuple:
    """(exit code, result line object or None)."""
    cell = manifest.workload(args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    if args.rehearsal_scale != 1.0:
        if not args.allow_cpu:
            raise ManifestError("--rehearsal-scale needs --allow-cpu")
        fleet = config["fleet"]
        fleet["nodes"] = max(50, int(fleet["nodes"] * args.rehearsal_scale))
        fleet["resident_allocs"] = int(
            fleet["resident_allocs"] * args.rehearsal_scale
        )
    # the precision the configuration states, before JAX is imported
    x64 = bool(config.get("jax_enable_x64"))
    os.environ["JAX_ENABLE_X64"] = "1" if x64 else "0"
    try:
        backend = system.resolve_device(int(cell["chips"]), args.allow_cpu)
    except system.NoChip as exc:
        log(f"no chip: {exc}")
        return 2, None
    import jax

    log(
        f"platform={backend.platform} device_kind={backend.device_kind} "
        f"devices={backend.device_count} host_cpus={os.cpu_count()} "
        f"x64={jax.config.jax_enable_x64} "
        f"compile_cache={jax.config.jax_compilation_cache_dir}"
    )
    if bool(jax.config.jax_enable_x64) != x64 and not args.allow_cpu:
        raise ManifestError(
            f"jax_enable_x64 is {jax.config.jax_enable_x64}, the "
            f"configuration states {x64}"
        )
    compiles = CompileClock()
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    closed = traffic["loop"] == "closed"
    seconds = float(args.seconds)

    # ---- set-up -------------------------------------------------------
    t = time.monotonic()
    world = make_world(config, args.seed)
    server = system.build_server(args.seed)
    system.load_world(server.store, world)
    log(f"world: {world.n_nodes} nodes / {world.n_allocs} allocs in "
        f"{time.monotonic() - t:.1f}s")
    server.start()
    http = system.start_http(server)
    client = Client(http.port)
    stream = JobStream(config, traffic, args.seed)
    sent: list = []  # (payload, eval id) of every acknowledged registration
    profiling = False
    trace_dir = os.path.join(ROOT, ".bench_trace", f"{os.getpid()}")
    try:
        t = time.monotonic()
        server.workers[0].warm_shapes(t_buckets=(1,))
        log(f"warm_shapes: {time.monotonic() - t:.1f}s")
        gc.collect()  # the same collector state at every run's start

        def loadgen(stream_, traffic_, prefix):
            return LoadGen(
                port=http.port, stream=stream_, traffic=traffic_, prefix=prefix,
                wait_index=lambda i, timeout: server.store.wait_for_index(
                    i, timeout=timeout
                ),
                latest_index=server.store.latest_index,
                eval_status=lambda e: system.eval_status(server.store, e),
            )

        # the probe ramp: closed loops of pinned count-1 copies of the job
        t = time.monotonic()
        quiet = Quiet(client, compiles)
        dcs = set(config["job"]["datacenters"])
        probes = ProbeStream(stream, [
            system.node_name(i) for i in range(world.n_nodes)
            if world.datacenters[int(world.node_dc[i])] in dcs
        ][:MAX_PINS])
        for k, (in_flight, evals) in enumerate(traffic.get("probe_ramp", ())):
            plg = loadgen(
                probes, {"loop": "closed", "in_flight": int(in_flight),
                         "senders": int(in_flight)}, f"probe{k}",
            )
            plg.start()
            warm_up(plg, int(evals), quiet, PROBE_QUIET_S)
            if not plg.finish(DRAIN_GRACE_S):
                raise RuntimeError("a probe did not complete")
            sent.extend(
                (probes.payload(r.index, f"probe{k}"), r.eval_id)
                for r in plg.requests if r.eval_id
            )
        log(f"probe ramp: {time.monotonic() - t:.1f}s probes={len(sent)} "
            f"launch_shapes={len(compiles.launch_shapes())} "
            f"host_path_evals={quiet.host_path:.0f}")

        lg = loadgen(stream, traffic, "job")
        if closed:
            lg.start()
            warm_up(lg, int(traffic.get("warmup_evals", 0)), quiet, QUIET_S)
        else:
            lead = float(traffic.get("warmup_s", 0.0))
            lg.start(send_for_s=lead + seconds)
            t0 = lg.t_start + lead
            time.sleep(max(0.0, t0 - 0.05 - time.monotonic()))
        edge0 = metrics_snapshot(client)
        steal0 = host_steal_s()
        gc0 = gc_clock.snapshot()
        cpu0 = lg.cpu_s()
        if closed:
            t0 = time.monotonic()
        else:
            time.sleep(max(0.0, t0 - time.monotonic()))
        in_flight0 = lg.in_flight()
        setup_s = process_age_s() - (time.monotonic() - t0)
        t1 = t0 + seconds
        log(f"window open: setup_s={setup_s:.3f} in_flight={in_flight0} "
            f"warmup_done={lg.completed} "
            f"launch_shapes={len(compiles.launch_shapes())} "
            f"host_path_evals={quiet.host_path:.0f}")

        # ---- the window ----------------------------------------------
        part = None
        spans: list = []
        if args.trace:
            # the last seconds of the window: the profiler's slow stop
            # then falls after it
            p0 = t1 - min(seconds, TRACE_PART_S)
            time.sleep(max(0.0, p0 - time.monotonic()))
            part_edge = metrics_snapshot(client)
            os.makedirs(trace_dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            profiling = True
            mark0 = time.monotonic_ns()
            with jax.profiler.TraceAnnotation(tracered.MARK_OPEN):
                pass
        time.sleep(max(0.0, t1 - time.monotonic()))
        if profiling:
            with jax.profiler.TraceAnnotation(tracered.MARK_CLOSE):
                pass
            mark1 = time.monotonic_ns()
        in_flight1 = lg.in_flight()
        steal1 = host_steal_s()
        cpu1 = lg.cpu_s()
        gc1 = gc_clock.snapshot()
        edge1 = metrics_snapshot(client)
        if profiling:
            # the recorder keeps its last 1,024 evaluations: read it
            # before the drain and the profiler's stop let the ring roll
            spans = system.recent_spans(GAP_SPANS, mark0 / 1e9, mark1 / 1e9)
        drained = lg.finish(DRAIN_GRACE_S)
        log(f"window closed: in_flight={in_flight1} drained={drained}")
        if profiling:
            t = time.monotonic()
            jax.profiler.stop_trace()
            profiling = False
            part = (mark0, mark1, delta(edge1, part_edge))
            log(f"traced part: {(mark1 - mark0) / 1e9:.2f}s, "
                f"{len(spans)} flight-recorder spans, profiler stop "
                f"{time.monotonic() - t:.1f}s")

        # ---- after the window ------------------------------------------
        peak = 0
        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        server.drain_to_idle(timeout=DRAIN_GRACE_S)
        requests = sorted(lg.requests, key=lambda r: r.index)
        for r in requests:
            if r.eval_id:
                sent.append((stream.payload(r.index), r.eval_id))
        served = []
        unfinished = 0
        for payload, eval_id in sent:
            if system.eval_status(server.store, eval_id) != "complete":
                unfinished += 1
            index, placed = system.job_answers(server.store, payload["id"])
            served.append((index, payload, placed))
        # a sample read back over HTTP, the last job in it
        rng = np.random.default_rng([args.seed, 0x5A3E])
        pick = sorted(
            set(rng.integers(0, len(served), min(32, len(served))).tolist())
            | {len(served) - 1}
        )
        readback = 0
        for k in pick:
            _i, payload, placed = served[k]
            got = {
                a["name"]: a["node_id"]
                for a in client.get_json(f"/v1/job/{payload['id']}/allocations")
                if a.get("desired_status") == "run"
            }
            readback += got != placed
        arena_rows = system.arena_rows(server)
    finally:
        if profiling:
            jax.profiler.stop_trace()
        client.close()
        http.stop()
        server.stop()
        gc.callbacks.remove(gc_clock)

    in_window_shapes = compiles.launch_shapes(t0, t1)
    log("compiles in window (at s, took s, what): " + json.dumps(
        [(round(t - t0, 2), round(sec, 3), n)
         for t, sec, n in compiles.at if t0 <= t < t1][:12]
    ))
    if in_window_shapes:
        log("no measurement: a launch shape compiled inside the window "
            "(at s, took s): " + json.dumps(
                [(round(t - t0, 2), round(sec, 3)) for t, sec, _n in in_window_shapes]
            ) + "; the warm-up did not meet it, or the program's warm_shapes "
            "does not cover it")
        return 3, None

    # ---- the reference, once the program's state is let go -----------
    del server, http
    gc.collect()
    t = time.monotonic()
    numbers = correct.compare(world, args.seed, served)
    numbers["unfinished_acked"] = unfinished
    numbers["readback_mismatches"] = int(readback)
    reference_s = time.monotonic() - t

    # ---- metrics -----------------------------------------------------
    in_window = metrics.window_completions(requests, t0, t1)
    if closed:
        attempted_reqs = [r for r in requests if t0 <= r.sent < t1]
    else:
        attempted_reqs = metrics.due_in_window(requests, t0, t1)
    failed = sum(1 for r in attempted_reqs if not r.ok)
    values = {"setup_s": setup_s}
    if closed:
        values["placements_per_s"] = metrics.placements_per_s(requests, t0, t1)
    else:
        for pct in (50, 95, 99):
            values[f"eval_p{pct}_ms"] = metrics.latency_percentile_ms(
                attempted_reqs, float(pct)
            )
    late = metrics.lateness_ms(attempted_reqs) or [0.0]
    post_ms = [(r.acked - r.sent) * 1e3 for r in attempted_reqs] or [0.0]
    gen_cpu_share = (cpu1 - cpu0) / seconds
    floor_jobs = len(in_window)
    ok = correct.verdict(numbers, floor_jobs)
    window = delta(edge1, edge0)
    log(
        f"window: evals={len(in_window)} attempted={len(attempted_reqs)} "
        f"failed={failed} in_flight_open={in_flight0} in_flight_close={in_flight1} "
        f"gen_cpu_share={gen_cpu_share:.4f} "
        f"gen_late_ms_p50={metrics.percentile(late, 50):.3f} "
        f"gen_late_ms_p95={metrics.percentile(late, 95):.3f} "
        f"post_ms_p50={metrics.percentile(post_ms, 50):.3f} "
        f"post_ms_p95={metrics.percentile(post_ms, 95):.3f} "
        f"gc_pause_s={gc1[0] - gc0[0]:.3f} gc_collections={gc1[1] - gc0[1]} "
        f"gc_longest_s={gc_clock.longest_s:.3f} "
        f"compiles_in_window={compiles.between(t0, t1)} "
        f"launches={window['samples'].get('batch_worker.launch', {}).get('count')} "
        f"prescored={window['counters'].get('batch_worker.prescored')} "
        f"host_path_evals={system.host_path_evals(window['counters'], window['samples']):.0f} "
        f"reference_s={reference_s:.2f}"
    )
    log("values: " + json.dumps(values))
    stalls = metrics.longest_gaps(in_window, t0, t1)
    log("longest gaps between completions (at s, lasted s): "
        + json.dumps([(round(a, 2), round(g, 3)) for a, g in stalls[:5]])
        + f" host_steal_s={steal1 - steal0:.3f}")

    device = {
        "platform": backend.platform,
        "kind": backend.device_kind,
        "count": backend.device_count,
        "memory_peak_bytes": peak,
    }
    out_metrics = {}
    breakdown = None
    if args.trace:
        obs = {
            "workload": cell["name"], "window_s": seconds,
            "evals": len(in_window), "attempted": len(attempted_reqs),
            "refused": sum(1 for r in attempted_reqs if r.http_status != 200),
            "counters": window["counters"], "samples": window["samples"],
            "gen": {"late_ms": metrics.lateness_ms(attempted_reqs)},
            "latency_ms": [] if closed else metrics.latencies_ms(attempted_reqs),
            "gc": {"pause_s": gc1[0] - gc0[0]},
            "longest_gap_s": stalls[0][1] if stalls else None,
            "compiles": compiles.between(t0, t1),
            "trace": None, "device_kind": backend.device_kind,
            "arena_rows": arena_rows, "column_bytes": 8 if x64 else 4,
            "picks_per_eval": (
                sum(r.placements for r in in_window) / max(1, len(in_window))
            ),
        }
        try:
            trace = tracered.load_xplane(tracered.find_xplane(trace_dir))
            win = tracered.window_of(trace)
            if args.out:
                dump(args, cell, "planes", tracered.outline(trace))
                if win is not None:
                    dump(args, cell, "excerpt",
                         tracered.excerpt(trace, win[0] + 10**9, 250 * 10**6))
            host_spans = []
            if win is not None:
                # flight-recorder spans onto the profiler's clock: the
                # open marker was emitted at mark0 on time.monotonic
                shift = win[0] - part[0]
                host_spans = [
                    (n, int(s * 1e9) + shift, int(e * 1e9) + shift)
                    for n, s, e in spans
                ]
            reduced = tracered.reduce_trace(trace, host_spans, win)
            # evaluations launched in the traced part, for the kernel's
            # time an evaluation
            reduced["launch_evals"] = part[2]["counters"].get(
                "batch_worker.prescored", 0.0
            )
            obs["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": tracered.top(reduced["ops"]),
                "idle_gaps": tracered.top(reduced["idle_gaps"]),
            }
            log(f"trace: planes={reduced['planes']} busy_s={reduced['busy_s']:.4f} "
                f"window_s={reduced['window_s']:.4f} "
                f"modules={tracered.top(reduced['modules'], 5)}")
        except (OSError, ValueError) as exc:
            log(f"trace not readable: {exc!r}")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        for m in manifest.metrics_of("per_layer", cell["name"]):
            value = manifest.layer_reader(m["name"])(obs)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in manifest.metrics_of("end_to_end", cell["name"]):
            out_metrics[m["name"]] = {
                "value": values[m["name"]], "unit": m["unit"],
            }

    for line in correct.lines(numbers, floor_jobs):
        log(line)
    if args.out:
        dump(args, cell, "log", {
            "values": values, "numbers": numbers,
            "window": window, "gen_cpu_share": gen_cpu_share,
            "done": [r.done - t0 for r in in_window],
            "requests": [
                [r.due - t0, (r.done - t0) if r.ok else -1.0] for r in requests
            ],
        })
    result = {
        "correct": bool(ok),
        "attempted": len(attempted_reqs),
        "failed": int(failed),
        "metrics": out_metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = correct.table(numbers, floor_jobs)
    return 0, result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        manifest = Manifest()
        code, result = run(args, manifest)
    except ManifestError as exc:
        log(f"refused: {exc}")
        return 2
    if result is None:
        return code
    if args.allow_cpu:
        # a rehearsal: nothing here can be read as a chip result
        result = {"rehearsal": True} | {
            "rehearsal_" + k: v for k, v in result.items()
        }
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    rc = 1
    try:
        rc = main()
    except Exception:  # noqa: BLE001 — the run failed; say how, exit non-zero
        import traceback

        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads may sit inside XLA calls; leave with the real status
    os._exit(rc)
