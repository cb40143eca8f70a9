"""The 11 historical stage-accounting checks as individual rules,
plus ``span-layers`` (the flight recorder's span -> layer table).

These migrated 1:1 from the ``tools/check_stage_accounting.py``
monolith (which now shims onto them); the check numbers in each
docstring refer to that file's original numbering, and the messages
keep the original wording so operator muscle memory (and the tier-1
test's substring asserts) survive the migration.
"""
from __future__ import annotations

import ast
import os
from typing import List, Set

from .. import astutil
from ..core import Context, Finding, Rule, register

# allocs_fit / BinPackIterator exhaustion-dimension vocabulary a
# literal exhausted_node() in the vectorized path may use
EXHAUST_DIMENSIONS = {"cpu", "memory", "disk"}


def _device_module_paths(ctx: Context) -> List[str]:
    device_dir = ctx.path("device_dir")
    subst = {}
    sup = ctx.overrides.get("device_supervisor")
    if sup:
        subst[ctx.default_path("device_supervisor")] = sup
    return sorted(
        subst.get(
            os.path.join(device_dir, name),
            os.path.join(device_dir, name),
        )
        for name in os.listdir(device_dir)
        if name.endswith(".py")
    )


@register
class StageObservedRule(Rule):
    """Check 1: every key in the ``self.timings = {...}`` literal in
    batch_worker.py appears in at least one ``self._observe(...)``
    call — a stage added without observation would stay 0 forever."""

    name = "stage-observed"
    description = (
        "every BatchWorker.timings key is observed via _observe"
    )

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("batch_worker")
        tree = ctx.tree(path)
        declared = astutil.timings_keys(tree)
        if not declared:
            return [
                Finding(
                    self.name, path, 0,
                    "could not find the self.timings literal in "
                    "batch_worker.py",
                )
            ]
        unobserved = declared - astutil.observed_keys(tree)
        if unobserved:
            return [
                Finding(
                    self.name, path, 0,
                    "timings keys never passed to _observe (stage "
                    "time would stay 0 forever): "
                    f"{sorted(unobserved)}",
                )
            ]
        return []

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "batch_worker",
            old='self._observe("simulate"',
            new='_unused("simulate"',
        )


@register
class StageOrphansRule(Rule):
    """Check 2: every ``self._observe("<key>", ...)`` call uses a
    declared timings key (no orphan stages accumulating into
    nothing)."""

    name = "stage-orphans"
    description = "every _observe key is declared in timings"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("batch_worker")
        tree = ctx.tree(path)
        declared = astutil.timings_keys(tree)
        if not declared:
            # stage-observed already reports the missing literal
            return []
        orphans = astutil.observed_keys(tree) - declared
        if orphans:
            return [
                Finding(
                    self.name, path, 0,
                    "_observe calls with keys missing from the "
                    "timings literal (would KeyError at runtime): "
                    f"{sorted(orphans)}",
                )
            ]
        return []

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "batch_worker",
            old='self._observe("simulate"',
            new='self._observe("bogus_simulate"',
        )


@register
class BenchStageExportRule(Rule):
    """Check 3: bench.py snapshots ``worker.timings`` wholesale
    (``dict(worker.timings)``) and exports ``e2e_stage_times_s``, so
    new stages flow into BENCH_*.json without a bench edit."""

    name = "bench-stage-export"
    description = "bench.py exports the stage timings wholesale"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("bench")
        tree = ctx.tree(path)
        source = ctx.source(path)
        out: List[Finding] = []
        wholesale = any(
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "dict"
            and node.args
            and isinstance(node.args[0], ast.Attribute)
            and node.args[0].attr == "timings"
            for node in ast.walk(tree)
        )
        if not wholesale:
            out.append(
                Finding(
                    self.name, path, 0,
                    "bench.py no longer snapshots the stage times "
                    "wholesale (expected a dict(worker.timings) "
                    "call) — new stages would silently drop from "
                    "the bench",
                )
            )
        if '"e2e_stage_times_s"' not in source:
            out.append(
                Finding(
                    self.name, path, 0,
                    "bench.py no longer exports the "
                    "e2e_stage_times_s JSON key",
                )
            )
        return out

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "bench",
            old='"e2e_stage_times_s"',
            new='"renamed_stage_times_s"',
        )


@register
class SpanRegistryRule(Rule):
    """Checks 4+5 (span half), generalized: every span/event name
    literal used with ``TRACE.span/add_span/event`` anywhere in
    ``nomad_tpu/`` must be declared in the ``SPAN_NAMES`` registry in
    trace.py — a renamed stage must update the documented registry
    (and with it every dashboard/report keyed on the name), never
    drift silently.  Check 10's span half rides along: the
    continuous-micro-batching admission names must stay registered
    even if their call sites change shape."""

    name = "span-registry"
    description = "every span/event literal is in trace.SPAN_NAMES"

    REQUIRED = (
        "batch_worker.admit",
        "batch_worker.admit_deferred",
        # follower scheduling fan-out: the lease RPC on every
        # remotely dequeued eval and the serialized-commit round
        # trip into the leader's plan queue — without them a
        # follower-planned eval's trace loses its cross-server hops
        "fanout.remote_dequeue",
        "fanout.plan_submit",
        # cluster-scope observability: the follower's segment-ship
        # marker (the stitched waterfall's cross-server seam) and
        # the leader's fan-in query span — without them a stitched
        # trace can't show WHEN spans left the follower, and a slow
        # /v1/cluster/* query has no flight-recorder trail
        "fanout.remote_span_ship",
        "cluster.fanin",
        # the overload control plane's incident roots: the per-
        # excursion shed incident and the batched mass node-death
        # wave — without them an overload or a rack death leaves no
        # flight-recorder trail
        "ingress.shed",
        "server.node_down_wave",
        # the sharded hot path's pipeline stages: mesh time must stay
        # separable from single-chip chunk time on every dashboard
        "batch_worker.mesh_launch",
        "batch_worker.mesh_fetch",
        # the global storm solver's lifecycle: the coalesced drain,
        # the single device solve, the per-eval decomposition, and
        # every serial-chain fallback — the auditability half of the
        # relaxed serial-equivalence contract
        "batch_worker.storm_gulp",
        "batch_worker.storm_solve",
        "batch_worker.storm_decompose",
        "storm.fallback",
        # policy-weighted scoring: the per-member weight-tensor
        # assembly inside storm staging — without it a weighted
        # storm's staging cost is invisible on every trace dashboard
        "batch_worker.policy_assemble",
        # multi-region federation: the cross-region forward and the
        # Multiregion fan-out roots — without them a WAN hop leaves
        # no flight-recorder trail and a fanned job's per-region
        # registrations can't be attributed
        "federation.forward",
        "federation.fanout",
    )

    def check(self, ctx: Context) -> List[Finding]:
        trace_path = ctx.path("trace")
        registry = astutil.span_registry(ctx.tree(trace_path))
        if not registry:
            return [
                Finding(
                    self.name, trace_path, 0,
                    "could not find the SPAN_NAMES registry in "
                    "nomad_tpu/trace.py",
                )
            ]
        out: List[Finding] = []
        trace_default = ctx.default_path("trace")
        for path in ctx.scan_files():
            # trace.py is the registry itself (its internal add_span
            # plumbing passes variables, not stage literals)
            if path in (trace_path, trace_default):
                continue
            used = astutil.span_names_used(ctx.tree(path))
            unregistered = used - registry
            if unregistered:
                out.append(
                    Finding(
                        self.name, path, 0,
                        "span names used but missing from "
                        "trace.SPAN_NAMES (rename must update the "
                        "documented registry): "
                        f"{sorted(unregistered)}",
                    )
                )
        for required in self.REQUIRED:
            if required not in registry:
                out.append(
                    Finding(
                        self.name, trace_path, 0,
                        f"{required!r} missing from "
                        "trace.SPAN_NAMES — a required pipeline "
                        "stage would vanish from every trace-keyed "
                        "dashboard",
                    )
                )
        return out

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "trace",
            old='"batch_worker.simulate"',
            new='"batch_worker.renamed_simulate"',
        )


def _literal_assignment(tree: ast.AST, name: str):
    """The value node of a module-level ``name = ...`` (annotated or
    not)."""
    for node in ast.walk(tree):
        targets = ()
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign):
            targets = (node.target,)
        if any(
            isinstance(t, ast.Name) and t.id == name for t in targets
        ):
            return node.value
    return None


@register
class SpanLayersRule(Rule):
    """The flight recorder's fold sums every span's self time into
    its layer (``trace.self.<layer>`` on /v1/metrics, the benchmark's
    per-layer self-time metrics).  ``LAYER_OF`` in trace.py must
    therefore cover ``SPAN_NAMES`` exactly — every name has a layer
    or is marked event-only (``EVENT``), no entry names a span that
    is not registered — and every layer of ``LAYERS`` must have at
    least one span name: a renamed stage cannot silently leave its
    layer's metric, and a layer cannot silently lose its last
    source."""

    name = "span-layers"
    description = "trace.LAYER_OF covers SPAN_NAMES; no empty layer"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("trace")
        tree = ctx.tree(path)
        registry = astutil.span_registry(tree)
        table = _literal_assignment(tree, "LAYER_OF")
        layers = astutil.assigned_strings(tree, "LAYERS")
        if not isinstance(table, ast.Dict) or not layers:
            return [
                Finding(
                    self.name, path, 0,
                    "could not find the LAYER_OF dict literal and "
                    "the LAYERS tuple in nomad_tpu/trace.py",
                )
            ]
        out: List[Finding] = []
        layer_of = {}
        for key, value in zip(table.keys, table.values):
            if not (
                isinstance(key, ast.Constant)
                and isinstance(key.value, str)
            ):
                continue
            if isinstance(value, ast.Constant) and isinstance(
                value.value, str
            ):
                layer_of[key.value] = value.value
            elif (
                isinstance(value, ast.Name) and value.id == "EVENT"
            ) or (
                isinstance(value, ast.Constant)
                and value.value is None
            ):
                layer_of[key.value] = None
            else:
                out.append(
                    Finding(
                        self.name, path, key.lineno,
                        f"LAYER_OF[{key.value!r}] is neither a "
                        "layer literal nor EVENT",
                    )
                )
                layer_of[key.value] = None
        missing = registry - set(layer_of)
        if missing:
            out.append(
                Finding(
                    self.name, path, 0,
                    "span names with no LAYER_OF entry (give each a "
                    "layer or mark it EVENT — the fold would look "
                    "through it and its layer's metric lose it): "
                    f"{sorted(missing)}",
                )
            )
        stray = set(layer_of) - registry
        if stray:
            out.append(
                Finding(
                    self.name, path, 0,
                    "LAYER_OF entries that are not in SPAN_NAMES "
                    f"(a renamed stage left them): {sorted(stray)}",
                )
            )
        used = {v for v in layer_of.values() if v is not None}
        unknown = used - layers
        if unknown:
            out.append(
                Finding(
                    self.name, path, 0,
                    "LAYER_OF names layers missing from LAYERS "
                    f"(no trace.self.* series): {sorted(unknown)}",
                )
            )
        empty = layers - used
        if empty:
            out.append(
                Finding(
                    self.name, path, 0,
                    "layers of LAYERS with no span name in LAYER_OF "
                    "(their trace.self.* series would read 0 "
                    f"forever): {sorted(empty)}",
                )
            )
        return out

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "trace",
            old='    "replay.speculate": "replay_pool",\n',
            new="",
        )


@register
class DeviceMetricsRule(Rule):
    """Check 5 (metric half): every ``device.*`` counter/gauge/sample
    emitted by the accelerator supervisor modules appears in the
    ``METRIC_COUNTERS``/``METRIC_GAUGES``/``METRIC_SAMPLES`` registry
    literals in device/supervisor.py — those are zero-registered at
    supervisor construction, which is what guarantees
    ``prometheus_text()`` exports the whole family before the first
    incident."""

    name = "device-metrics"
    description = "device.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        sup_path = ctx.path("device_supervisor")
        registry = astutil.device_metric_registry(
            ctx.tree(sup_path)
        )
        if not registry:
            return [
                Finding(
                    self.name, sup_path, 0,
                    "could not find the METRIC_COUNTERS/GAUGES/"
                    "SAMPLES registry in device/supervisor.py",
                )
            ]
        emitted: Set[str] = set()
        for path in _device_module_paths(ctx):
            emitted |= astutil.metric_names_emitted(
                ctx.tree(path), "device."
            )
        unexported = emitted - registry
        if unexported:
            return [
                Finding(
                    self.name, sup_path, 0,
                    "device.* metrics emitted but not in the "
                    "supervisor's zero-registered registry (they "
                    "would be absent from prometheus_text() until "
                    f"the first incident): {sorted(unexported)}",
                )
            ]
        return []

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "device_supervisor",
            append=(
                "def _nomadlint_bad_fixture(metrics):\n"
                '    metrics.incr("device.bogus_metric")\n'
            ),
        )


@register
class DebugBundleDeviceRule(Rule):
    """Check 6: the operator debug bundle (cli.py
    ``cmd_operator_debug``) captures ``/v1/device``, so a bundle from
    a degraded server always carries the supervisor's state
    history."""

    name = "debug-bundle-device"
    description = "operator debug bundle captures /v1/device"

    # quoted form: "/v1/devices" (the fingerprint family) must not
    # satisfy the supervisor-status capture check
    NEEDLE = '"/v1/device"'
    ENDPOINT = "/v1/device"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("cli")
        bundle_src = ctx.source(path).split(
            "cmd_operator_debug", 1
        )[-1].split("def ", 1)[0]
        if self.NEEDLE not in bundle_src:
            return [
                Finding(
                    self.name, path, 0,
                    "the operator debug bundle "
                    "(cli.cmd_operator_debug) no longer captures "
                    f"{self.ENDPOINT}",
                )
            ]
        return []

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        # drop the last path char: the mutated source must not keep
        # the needle as a substring ("/v1/placements_renamed" would)
        return cls._mutated(
            ctx, tmpdir, "cli",
            old=cls.ENDPOINT,
            new=cls.ENDPOINT[:-1],
        )


@register
class DebugBundlePlacementsRule(DebugBundleDeviceRule):
    """Check 9: the operator debug bundle captures
    ``/v1/placements`` so the per-eval explanations travel with the
    traces they cross-reference."""

    name = "debug-bundle-placements"
    description = "operator debug bundle captures /v1/placements"

    NEEDLE = "/v1/placements"
    ENDPOINT = "/v1/placements"


@register
class PlacementMetricsRule(Rule):
    """Check 7: placement.* emissions in explain.py stay inside the
    zero-registered families.  Literal names must be registered
    verbatim; f-string names may only be `placement.filtered.{...}` /
    `placement.exhausted.{...}` with the slug produced by
    reason_slug()/dimension_slug() (the fixed vocabularies); and the
    server zero-registers the family at construction."""

    name = "placement-metrics"
    description = "placement.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("explain")
        tree = ctx.tree(path)
        problems: List[Finding] = []
        counters = astutil.assigned_strings(
            tree, "PLACEMENT_COUNTERS"
        )
        gauges = astutil.assigned_strings(tree, "PLACEMENT_GAUGES")
        filter_slugs = astutil.assigned_strings(
            tree, "PLACEMENT_FILTER_SLUGS"
        )
        exhaust_slugs = astutil.assigned_strings(
            tree, "PLACEMENT_EXHAUST_SLUGS"
        )
        if not (
            counters and gauges and filter_slugs and exhaust_slugs
        ):
            return [
                Finding(
                    self.name, path, 0,
                    "could not find the PLACEMENT_* registries in "
                    "nomad_tpu/explain.py",
                )
            ]
        registered = (
            counters
            | gauges
            | {f"placement.filtered.{s}" for s in filter_slugs}
            | {f"placement.exhausted.{s}" for s in exhaust_slugs}
        )
        slug_fns = {"reason_slug", "dimension_slug"}
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in astutil.METRIC_CALLS
                and node.args
            ):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(
                arg.value, str
            ):
                if arg.value.startswith("placement.") and (
                    arg.value not in registered
                ):
                    problems.append(
                        Finding(
                            self.name, path, node.lineno,
                            f"placement metric {arg.value!r} "
                            "emitted but not in the "
                            "zero-registered PLACEMENT_* "
                            "registries",
                        )
                    )
                continue
            if isinstance(arg, ast.JoinedStr):
                prefix = ""
                if arg.values and isinstance(
                    arg.values[0], ast.Constant
                ):
                    prefix = str(arg.values[0].value)
                if not prefix.startswith("placement."):
                    continue
                if prefix not in (
                    "placement.filtered.",
                    "placement.exhausted.",
                ):
                    problems.append(
                        Finding(
                            self.name, path, node.lineno,
                            "dynamic placement metric prefix "
                            f"{prefix!r} has no zero-registered "
                            "family",
                        )
                    )
                    continue
                for part in arg.values[1:]:
                    if not isinstance(part, ast.FormattedValue):
                        continue
                    call = part.value
                    ok = (
                        isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Name)
                        and call.func.id in slug_fns
                    )
                    if not ok:
                        problems.append(
                            Finding(
                                self.name, path, node.lineno,
                                "placement metric family "
                                f"{prefix!r} interpolates a value "
                                "not produced by reason_slug()/"
                                "dimension_slug() — the name space "
                                "would be unbounded",
                            )
                        )
        server_path = ctx.path("server")
        server_src = ctx.source(server_path)
        if (
            "preregister" not in server_src
            or "explain" not in server_src
        ):
            problems.append(
                Finding(
                    self.name, server_path, 0,
                    "server.py no longer zero-registers the "
                    "placement.* families at construction "
                    "(explain.preregister)",
                )
            )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "explain",
            append=(
                "def _nomadlint_bad_fixture(metrics):\n"
                '    metrics.incr("placement.bogus_metric")\n'
            ),
        )


@register
class ReasonVocabularyRule(Rule):
    """Check 8: reason-string literals used by the vectorized path
    must come from the serial chain's shared vocabulary — a string
    literal passed to ``filter_node(...)`` in sched/tpu_stack.py must
    be one of the ``FILTER_*`` constants' values (sched/feasible.py),
    and a literal ``exhausted_node(...)`` dimension must be in the
    ``allocs_fit`` superset vocabulary."""

    name = "reason-vocab"
    description = "vectorized-path reason literals use shared vocab"

    def check(self, ctx: Context) -> List[Finding]:
        feasible_path = ctx.path("feasible")
        allowed: Set[str] = set()
        for node in ast.walk(ctx.tree(feasible_path)):
            if not isinstance(node, ast.Assign):
                continue
            for target in node.targets:
                if (
                    isinstance(target, ast.Name)
                    and target.id.startswith("FILTER_")
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, str)
                ):
                    allowed.add(node.value.value)
        if not allowed:
            return [
                Finding(
                    self.name, feasible_path, 0,
                    "could not find the FILTER_* reason constants "
                    "in sched/feasible.py",
                )
            ]
        stack_path = ctx.path("tpu_stack")
        problems: List[Finding] = []
        for node in ast.walk(ctx.tree(stack_path)):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and len(node.args) >= 2
                and isinstance(node.args[1], ast.Constant)
                and isinstance(node.args[1].value, str)
            ):
                continue
            literal = node.args[1].value
            if (
                node.func.attr == "filter_node"
                and literal not in allowed
            ):
                problems.append(
                    Finding(
                        self.name, stack_path, node.lineno,
                        "ad-hoc filter reason literal in "
                        f"sched/tpu_stack.py: {literal!r} is not a "
                        "shared FILTER_* constant value (import "
                        "the constant instead)",
                    )
                )
            if (
                node.func.attr == "exhausted_node"
                and literal not in EXHAUST_DIMENSIONS
            ):
                problems.append(
                    Finding(
                        self.name, stack_path, node.lineno,
                        "ad-hoc exhaustion dimension literal in "
                        f"sched/tpu_stack.py: {literal!r} is "
                        "outside the allocs_fit superset "
                        "vocabulary",
                    )
                )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "tpu_stack",
            append=(
                "def _nomadlint_bad_fixture(it, node):\n"
                '    it.filter_node(node, "bogus ad-hoc reason")\n'
            ),
        )


@register
class AdmissionMetricsRule(Rule):
    """Check 10 (counter half): every ``admission.*`` metric the
    batch worker emits — literal first args of metric calls plus the
    ``self._count_admission("<kind>")`` sites, which emit
    ``admission.<kind>`` — is in the zero-registered
    ``ADMISSION_COUNTERS`` registry, and server.py actually
    zero-registers it."""

    name = "admission-metrics"
    description = "admission.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("batch_worker")
        tree = ctx.tree(path)
        registry = astutil.assigned_strings(
            tree, "ADMISSION_COUNTERS"
        )
        if not registry:
            return [
                Finding(
                    self.name, path, 0,
                    "could not find the ADMISSION_COUNTERS "
                    "registry in batch_worker.py",
                )
            ]
        emitted: Set[str] = set()
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
            ):
                continue
            if (
                node.func.attr in astutil.METRIC_CALLS
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value.startswith("admission.")
            ):
                emitted.add(node.args[0].value)
            if (
                node.func.attr == "_count_admission"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                emitted.add(f"admission.{node.args[0].value}")
        problems: List[Finding] = []
        unregistered = emitted - registry
        if unregistered:
            problems.append(
                Finding(
                    self.name, path, 0,
                    "admission.* metrics emitted but not in the "
                    "ADMISSION_COUNTERS registry (they would be "
                    "absent from prometheus scrapes until the "
                    "first mid-chain admission): "
                    f"{sorted(unregistered)}",
                )
            )
        server_path = ctx.path("server")
        if "ADMISSION_COUNTERS" not in ctx.source(server_path):
            problems.append(
                Finding(
                    self.name, server_path, 0,
                    "server.py no longer zero-registers the "
                    "admission.* family at construction "
                    "(ADMISSION_COUNTERS preregister)",
                )
            )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "batch_worker",
            append=(
                "def _nomadlint_bad_fixture(metrics):\n"
                '    metrics.incr("admission.bogus_metric")\n'
            ),
        )


@register
class LatencySweepRule(Rule):
    """Check 11: bench.py exports the ``latency_sweep`` JSON block
    (offered-load vs p50/p99 with p99 trace exemplars) — the
    per-round tracking of the <250 ms tail-latency target."""

    name = "latency-sweep"
    description = "bench.py exports the latency_sweep block"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("bench")
        if '"latency_sweep"' not in ctx.source(path):
            return [
                Finding(
                    self.name, path, 0,
                    "bench.py no longer exports the latency_sweep "
                    "JSON block (offered-load vs p50/p99 with p99 "
                    "trace exemplars)",
                )
            ]
        return []

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "bench",
            old='"latency_sweep"',
            new='"renamed_latency_sweep"',
        )


@register
class MeshMetricsRule(Rule):
    """Sharded hot path: every ``mesh.*`` counter/gauge the batch
    worker emits is in the zero-registered ``MESH_COUNTERS`` /
    ``MESH_GAUGES`` registries, and server.py zero-registers both at
    construction — absence of a ``mesh.*`` series must mean "mesh
    never engaged", never "not exported"."""

    name = "mesh-metrics"
    description = "mesh.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("batch_worker")
        tree = ctx.tree(path)
        registry = astutil.assigned_strings(
            tree, "MESH_COUNTERS"
        ) | astutil.assigned_strings(tree, "MESH_GAUGES")
        if not registry:
            return [
                Finding(
                    self.name, path, 0,
                    "could not find the MESH_COUNTERS/MESH_GAUGES "
                    "registries in batch_worker.py",
                )
            ]
        emitted = astutil.metric_names_emitted(tree, "mesh.")
        problems: List[Finding] = []
        unregistered = emitted - registry
        if unregistered:
            problems.append(
                Finding(
                    self.name, path, 0,
                    "mesh.* metrics emitted but not in the "
                    "MESH_COUNTERS/MESH_GAUGES registries (they "
                    "would be absent from prometheus scrapes until "
                    "the first sharded flush): "
                    f"{sorted(unregistered)}",
                )
            )
        server_path = ctx.path("server")
        server_src = ctx.source(server_path)
        for reg_name in ("MESH_COUNTERS", "MESH_GAUGES"):
            if reg_name not in server_src:
                problems.append(
                    Finding(
                        self.name, server_path, 0,
                        "server.py no longer zero-registers the "
                        f"mesh.* family at construction ({reg_name} "
                        "preregister)",
                    )
                )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "batch_worker",
            append=(
                "def _nomadlint_bad_fixture(metrics):\n"
                '    metrics.set_gauge("mesh.bogus_metric", 1.0)\n'
            ),
        )


@register
class StormMetricsRule(Rule):
    """Global storm solver: every ``storm.*`` metric the batch worker
    emits — literal first args of metric calls plus the
    ``self._count_storm("<kind>")`` sites, which emit
    ``storm.<kind>`` — is in the zero-registered ``STORM_COUNTERS`` /
    ``STORM_GAUGES`` registries, and server.py zero-registers both at
    construction: absence of a ``storm.*`` series must mean "no storm
    ever coalesced", never "not exported"."""

    name = "storm-metrics"
    description = "storm.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("batch_worker")
        tree = ctx.tree(path)
        registry = astutil.assigned_strings(
            tree, "STORM_COUNTERS"
        ) | astutil.assigned_strings(tree, "STORM_GAUGES")
        if not registry:
            return [
                Finding(
                    self.name, path, 0,
                    "could not find the STORM_COUNTERS/STORM_GAUGES "
                    "registries in batch_worker.py",
                )
            ]
        emitted: Set[str] = set()
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
            ):
                continue
            if (
                node.func.attr in astutil.METRIC_CALLS
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value.startswith("storm.")
            ):
                emitted.add(node.args[0].value)
            if (
                node.func.attr == "_count_storm"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                emitted.add(f"storm.{node.args[0].value}")
        problems: List[Finding] = []
        unregistered = emitted - registry
        if unregistered:
            problems.append(
                Finding(
                    self.name, path, 0,
                    "storm.* metrics emitted but not in the "
                    "STORM_COUNTERS/STORM_GAUGES registries (they "
                    "would be absent from prometheus scrapes until "
                    "the first coalesced solve): "
                    f"{sorted(unregistered)}",
                )
            )
        server_path = ctx.path("server")
        server_src = ctx.source(server_path)
        for reg_name in ("STORM_COUNTERS", "STORM_GAUGES"):
            if reg_name not in server_src:
                problems.append(
                    Finding(
                        self.name, server_path, 0,
                        "server.py no longer zero-registers the "
                        f"storm.* family at construction ({reg_name} "
                        "preregister)",
                    )
                )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "batch_worker",
            append=(
                "def _nomadlint_bad_fixture(self):\n"
                '    self._count_storm("bogus_kind")\n'
            ),
        )


@register
class PolicyMetricsRule(Rule):
    """Policy-weighted scoring: every ``policy.*`` metric emitted
    anywhere in the layer — literal first args of metric calls in
    sched/policy.py (tensor-cache accounting), sched/storm.py
    (weighted staging), batch_worker.py and tpu_stack.py, plus the
    ``self._count_policy("<kind>")`` sites, which emit
    ``policy.<kind>`` — is in the zero-registered ``POLICY_COUNTERS``
    / ``POLICY_GAUGES`` registries (sched/policy.py), and server.py
    zero-registers both at construction: absence of a ``policy.*``
    series must mean "no policy-weighted select ever ran", never
    "not exported"."""

    name = "policy-metrics"
    description = "policy.* emissions are zero-registered"

    SCAN_KEYS = (
        "sched_policy", "sched_storm", "batch_worker", "tpu_stack"
    )

    def check(self, ctx: Context) -> List[Finding]:
        policy_path = ctx.path("sched_policy")
        registry = astutil.assigned_strings(
            ctx.tree(policy_path), "POLICY_COUNTERS"
        ) | astutil.assigned_strings(
            ctx.tree(policy_path), "POLICY_GAUGES"
        )
        if not registry:
            return [
                Finding(
                    self.name, policy_path, 0,
                    "could not find the POLICY_COUNTERS/"
                    "POLICY_GAUGES registries in sched/policy.py",
                )
            ]
        problems: List[Finding] = []
        for key in self.SCAN_KEYS:
            path = ctx.path(key)
            tree = ctx.tree(path)
            emitted: Set[str] = set()
            for node in ast.walk(tree):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                ):
                    continue
                if (
                    node.func.attr in astutil.METRIC_CALLS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith("policy.")
                ):
                    emitted.add(node.args[0].value)
                if (
                    node.func.attr == "_count_policy"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    emitted.add(f"policy.{node.args[0].value}")
            unregistered = emitted - registry
            if unregistered:
                problems.append(
                    Finding(
                        self.name, path, 0,
                        "policy.* metrics emitted but not in the "
                        "POLICY_COUNTERS/POLICY_GAUGES registries "
                        "(they would be absent from prometheus "
                        "scrapes until the first weighted select): "
                        f"{sorted(unregistered)}",
                    )
                )
        server_path = ctx.path("server")
        server_src = ctx.source(server_path)
        for reg_name in ("POLICY_COUNTERS", "POLICY_GAUGES"):
            if reg_name not in server_src:
                problems.append(
                    Finding(
                        self.name, server_path, 0,
                        "server.py no longer zero-registers the "
                        f"policy.* family at construction ({reg_name}"
                        " preregister)",
                    )
                )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "batch_worker",
            append=(
                "def _nomadlint_bad_fixture(self):\n"
                '    self._count_policy("bogus_kind")\n'
            ),
        )


@register
class LeadershipMetricsRule(Rule):
    """Leadership failover: every ``leadership.*`` / ``raft.*`` metric
    emitted by server.py, batch_worker.py, plan_apply.py or
    cluster.py — literal first args of metric calls plus the
    ``self._count_leadership("<kind>")`` sites, which emit
    ``leadership.<kind>`` — is in the zero-registered
    ``LEADERSHIP_COUNTERS`` / ``LEADERSHIP_GAUGES`` registries
    (server.py) and server.py preregisters them at construction:
    absence of a ``leadership.*`` series must mean "leadership never
    changed", never "not exported"."""

    name = "leadership-metrics"
    description = "leadership.*/raft.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        server_path = ctx.path("server")
        registry = astutil.assigned_strings(
            ctx.tree(server_path), "LEADERSHIP_COUNTERS"
        ) | astutil.assigned_strings(
            ctx.tree(server_path), "LEADERSHIP_GAUGES"
        )
        if not registry:
            return [
                Finding(
                    self.name, server_path, 0,
                    "could not find the LEADERSHIP_COUNTERS/"
                    "LEADERSHIP_GAUGES registries in server.py",
                )
            ]
        problems: List[Finding] = []
        for key in ("server", "batch_worker", "plan_apply", "cluster"):
            path = ctx.path(key)
            tree = ctx.tree(path)
            emitted: Set[str] = set()
            for node in ast.walk(tree):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                ):
                    continue
                if (
                    node.func.attr in astutil.METRIC_CALLS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith(
                        ("leadership.", "raft.")
                    )
                ):
                    emitted.add(node.args[0].value)
                if (
                    node.func.attr == "_count_leadership"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    emitted.add(f"leadership.{node.args[0].value}")
            unregistered = emitted - registry
            if unregistered:
                problems.append(
                    Finding(
                        self.name, path, 0,
                        "leadership./raft. metrics emitted but not "
                        "in the LEADERSHIP_COUNTERS/LEADERSHIP_GAUGES "
                        "registries (they would be absent from "
                        "prometheus scrapes until the first "
                        f"failover): {sorted(unregistered)}",
                    )
                )
        server_src = ctx.source(server_path)
        # the registry assignment is one occurrence; a preregister
        # call site must reference the name at least once more
        if server_src.count("LEADERSHIP_COUNTERS") < 2:
            problems.append(
                Finding(
                    self.name, server_path, 0,
                    "server.py no longer zero-registers the "
                    "leadership.* family at construction "
                    "(LEADERSHIP_COUNTERS preregister)",
                )
            )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "batch_worker",
            append=(
                "def _nomadlint_bad_fixture(self):\n"
                '    self._count_leadership("bogus_kind")\n'
            ),
        )


@register
class OverloadMetricsRule(Rule):
    """Overload control plane: every ``overload.*`` metric emitted by
    overload.py, server.py or api/http.py — literal first args of
    metric calls — is in the zero-registered ``OVERLOAD_COUNTERS`` /
    ``OVERLOAD_GAUGES`` registries (overload.py) and server.py
    preregisters both at construction: absence of an ``overload.*``
    series must mean "never overloaded", never "not exported"."""

    name = "overload-metrics"
    description = "overload.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        overload_path = ctx.path("overload")
        registry = astutil.assigned_strings(
            ctx.tree(overload_path), "OVERLOAD_COUNTERS"
        ) | astutil.assigned_strings(
            ctx.tree(overload_path), "OVERLOAD_GAUGES"
        )
        if not registry:
            return [
                Finding(
                    self.name, overload_path, 0,
                    "could not find the OVERLOAD_COUNTERS/"
                    "OVERLOAD_GAUGES registries in overload.py",
                )
            ]
        problems: List[Finding] = []
        for key in ("overload", "server", "api_http"):
            path = ctx.path(key)
            tree = ctx.tree(path)
            emitted: Set[str] = set()
            for node in ast.walk(tree):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                ):
                    continue
                if (
                    node.func.attr in astutil.METRIC_CALLS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith("overload.")
                ):
                    emitted.add(node.args[0].value)
            unregistered = emitted - registry
            if unregistered:
                problems.append(
                    Finding(
                        self.name, path, 0,
                        "overload.* metrics emitted but not in the "
                        "OVERLOAD_COUNTERS/OVERLOAD_GAUGES "
                        "registries (they would be absent from "
                        "prometheus scrapes until the first "
                        f"overload): {sorted(unregistered)}",
                    )
                )
        server_src = ctx.source(ctx.path("server"))
        if "OVERLOAD_COUNTERS" not in server_src:
            problems.append(
                Finding(
                    self.name, ctx.path("server"), 0,
                    "server.py no longer zero-registers the "
                    "overload.* family at construction "
                    "(OVERLOAD_COUNTERS preregister)",
                )
            )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "overload",
            append=(
                "def _nomadlint_bad_fixture(metrics):\n"
                '    metrics.incr("overload.bogus_metric")\n'
            ),
        )


@register
class FanoutMetricsRule(Rule):
    """Follower scheduling fan-out: every ``fanout.*`` metric emitted
    by fanout.py, cluster.py or server.py — literal first args of
    metric calls, the ``self._count_fanout("<kind>")`` worker sites
    and the ``self._count("<kind>")`` RemoteBrokerClient sites (both
    emit ``fanout.<kind>``) — is in the zero-registered
    ``FANOUT_COUNTERS`` / ``FANOUT_GAUGES`` registries (fanout.py)
    and server.py preregisters both at construction: absence of a
    ``fanout.*`` series must mean "fan-out never engaged", never
    "not exported"."""

    name = "fanout-metrics"
    description = "fanout.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        fanout_path = ctx.path("fanout")
        registry = astutil.assigned_strings(
            ctx.tree(fanout_path), "FANOUT_COUNTERS"
        ) | astutil.assigned_strings(
            ctx.tree(fanout_path), "FANOUT_GAUGES"
        )
        if not registry:
            return [
                Finding(
                    self.name, fanout_path, 0,
                    "could not find the FANOUT_COUNTERS/"
                    "FANOUT_GAUGES registries in fanout.py",
                )
            ]
        problems: List[Finding] = []
        for key in ("fanout", "cluster", "server"):
            path = ctx.path(key)
            tree = ctx.tree(path)
            emitted: Set[str] = set()
            for node in ast.walk(tree):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                ):
                    continue
                if (
                    node.func.attr in astutil.METRIC_CALLS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith("fanout.")
                ):
                    emitted.add(node.args[0].value)
                if (
                    key == "fanout"
                    and node.func.attr
                    in ("_count_fanout", "_count")
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    emitted.add(f"fanout.{node.args[0].value}")
            unregistered = emitted - registry
            if unregistered:
                problems.append(
                    Finding(
                        self.name, path, 0,
                        "fanout.* metrics emitted but not in the "
                        "FANOUT_COUNTERS/FANOUT_GAUGES registries "
                        "(they would be absent from prometheus "
                        "scrapes until the first remote lease): "
                        f"{sorted(unregistered)}",
                    )
                )
        server_src = ctx.source(ctx.path("server"))
        if "FANOUT_COUNTERS" not in server_src:
            problems.append(
                Finding(
                    self.name, ctx.path("server"), 0,
                    "server.py no longer zero-registers the "
                    "fanout.* family at construction "
                    "(FANOUT_COUNTERS preregister)",
                )
            )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "fanout",
            append=(
                "def _nomadlint_bad_fixture(self):\n"
                '    self._count_fanout("bogus_kind")\n'
            ),
        )


@register
class ClusterObsMetricsRule(Rule):
    """Cluster-scope observability plane: every ``cluster.*`` /
    ``obs.*`` metric emitted by telemetry.py, cluster.py, fanout.py,
    server.py or api/http.py — literal first args of metric calls —
    is in the zero-registered ``CLUSTER_OBS_COUNTERS`` /
    ``CLUSTER_OBS_GAUGES`` registries (telemetry.py) and server.py
    preregisters both at construction: absence of a
    ``cluster.fanin_queries`` or ``obs.history_snapshots`` series
    must mean "nothing happened", never "not exported"."""

    name = "cluster-obs-metrics"
    description = "cluster.*/obs.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        telemetry_path = ctx.path("telemetry")
        registry = astutil.assigned_strings(
            ctx.tree(telemetry_path), "CLUSTER_OBS_COUNTERS"
        ) | astutil.assigned_strings(
            ctx.tree(telemetry_path), "CLUSTER_OBS_GAUGES"
        )
        if not registry:
            return [
                Finding(
                    self.name, telemetry_path, 0,
                    "could not find the CLUSTER_OBS_COUNTERS/"
                    "CLUSTER_OBS_GAUGES registries in telemetry.py",
                )
            ]
        problems: List[Finding] = []
        for key in (
            "telemetry", "cluster", "fanout", "server", "api_http",
        ):
            path = ctx.path(key)
            emitted: Set[str] = set()
            for node in ast.walk(ctx.tree(path)):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in astutil.METRIC_CALLS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith(
                        ("cluster.", "obs.")
                    )
                ):
                    emitted.add(node.args[0].value)
            unregistered = emitted - registry
            if unregistered:
                problems.append(
                    Finding(
                        self.name, path, 0,
                        "cluster.*/obs.* metrics emitted but not in "
                        "the CLUSTER_OBS_COUNTERS/CLUSTER_OBS_GAUGES "
                        "registries (they would be absent from "
                        "prometheus scrapes until the first fan-in "
                        "query or history snapshot): "
                        f"{sorted(unregistered)}",
                    )
                )
        server_src = ctx.source(ctx.path("server"))
        if "CLUSTER_OBS_COUNTERS" not in server_src:
            problems.append(
                Finding(
                    self.name, ctx.path("server"), 0,
                    "server.py no longer zero-registers the "
                    "cluster.*/obs.* families at construction "
                    "(CLUSTER_OBS_COUNTERS preregister)",
                )
            )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "cluster",
            append=(
                "def _nomadlint_bad_fixture(metrics):\n"
                '    metrics.incr("cluster.bogus_metric")\n'
            ),
        )


@register
class ClusterFanoutExportRule(Rule):
    """Follower fan-out: bench.py exports the ``cluster_fanout`` JSON
    block (placements/s through 1/3/5-server clusters with the 3v1
    speedup and zero-lost/parity verdicts) — the per-round proof that
    scheduling throughput actually scales with servers."""

    name = "cluster-fanout-export"
    description = "bench.py exports the cluster_fanout block"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("bench")
        if '"cluster_fanout"' not in ctx.source(path):
            return [
                Finding(
                    self.name, path, 0,
                    "bench.py no longer exports the cluster_fanout "
                    "JSON block (1/3/5-server scheduling-throughput "
                    "scaling with zero-lost/parity verdicts)",
                )
            ]
        return []

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "bench",
            old='"cluster_fanout"',
            new='"renamed_cluster_fanout"',
        )


@register
class SwarmExportRule(Rule):
    """Swarm harness: bench.py exports the ``swarm`` JSON block (the
    SLO-gated overload + mass-death run: heartbeat success, sheds,
    storm-solve count, p99 exemplars) — the per-round proof that the
    control plane degrades instead of collapsing."""

    name = "swarm-export"
    description = "bench.py exports the swarm block"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("bench")
        if '"swarm"' not in ctx.source(path):
            return [
                Finding(
                    self.name, path, 0,
                    "bench.py no longer exports the swarm JSON "
                    "block (SLO-gated overload + mass node-death "
                    "harness results)",
                )
            ]
        return []

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "bench",
            old='"swarm"',
            new='"renamed_swarm"',
        )


@register
class MultichipExportRule(Rule):
    """Sharded hot path: bench.py exports the ``multichip`` JSON block
    (placements/s, host->device bytes/flush, per-device FLOPs vs
    device count) — the per-round proof that the node-sharded pipeline
    actually scales, feeding the MULTICHIP_r*.json tail."""

    name = "multichip-export"
    description = "bench.py exports the multichip block"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("bench")
        if '"multichip"' not in ctx.source(path):
            return [
                Finding(
                    self.name, path, 0,
                    "bench.py no longer exports the multichip JSON "
                    "block (placements/s, bytes/flush, per-device "
                    "FLOPs vs device count on the node-axis mesh)",
                )
            ]
        return []

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "bench",
            old='"multichip"',
            new='"renamed_multichip"',
        )


@register
class BigworldExportRule(Rule):
    """Composed topology: bench.py exports the ``bigworld`` JSON block
    (placements/s, per-host bytes/flush, snapshot catch-up seconds for
    the million-node world driven by fan-out followers heading pod
    meshes) — the per-round proof that the composed follower × pod
    stack holds at world scale."""

    name = "bigworld-export"
    description = "bench.py exports the bigworld block"

    def check(self, ctx: Context) -> List[Finding]:
        path = ctx.path("bench")
        if '"bigworld"' not in ctx.source(path):
            return [
                Finding(
                    self.name, path, 0,
                    "bench.py no longer exports the bigworld JSON "
                    "block (placements/s, per-host bytes/flush, "
                    "snapshot catch-up on the fan-out × pod composed "
                    "topology)",
                )
            ]
        return []

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "bench",
            old='"bigworld"',
            new='"renamed_bigworld"',
        )


@register
class FederationMetricsRule(Rule):
    """Multi-region federation plane: every ``federation.*`` metric
    emitted by federation.py, cluster.py, server.py or api/http.py —
    literal first args of metric calls — is in the zero-registered
    ``FEDERATION_COUNTERS`` / ``FEDERATION_GAUGES`` registries
    (federation.py) and server.py preregisters both at construction:
    absence of a ``federation.wan_reads`` or
    ``federation.forwarded`` series must mean "single region,
    nothing ever crossed the WAN", never "not exported"."""

    name = "federation-metrics"
    description = "federation.* emissions are zero-registered"

    def check(self, ctx: Context) -> List[Finding]:
        federation_path = ctx.path("federation")
        registry = astutil.assigned_strings(
            ctx.tree(federation_path), "FEDERATION_COUNTERS"
        ) | astutil.assigned_strings(
            ctx.tree(federation_path), "FEDERATION_GAUGES"
        )
        if not registry:
            return [
                Finding(
                    self.name, federation_path, 0,
                    "could not find the FEDERATION_COUNTERS/"
                    "FEDERATION_GAUGES registries in federation.py",
                )
            ]
        problems: List[Finding] = []
        for key in ("federation", "cluster", "server", "api_http"):
            path = ctx.path(key)
            tree = ctx.tree(path)
            emitted: Set[str] = set()
            for node in ast.walk(tree):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                ):
                    continue
                if (
                    node.func.attr in astutil.METRIC_CALLS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith("federation.")
                ):
                    emitted.add(node.args[0].value)
            unregistered = emitted - registry
            if unregistered:
                problems.append(
                    Finding(
                        self.name, path, 0,
                        "federation.* metrics emitted but not in "
                        "the FEDERATION_COUNTERS/FEDERATION_GAUGES "
                        "registries (they would be absent from "
                        "prometheus scrapes until the first WAN "
                        f"crossing): {sorted(unregistered)}",
                    )
                )
        server_src = ctx.source(ctx.path("server"))
        if "FEDERATION_COUNTERS" not in server_src:
            problems.append(
                Finding(
                    self.name, ctx.path("server"), 0,
                    "server.py no longer zero-registers the "
                    "federation.* family at construction "
                    "(FEDERATION_COUNTERS preregister)",
                )
            )
        return problems

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._mutated(
            ctx, tmpdir, "federation",
            append=(
                "def _nomadlint_bad_fixture(metrics):\n"
                '    metrics.incr("federation.bogus_metric")\n'
            ),
        )


MIGRATED_RULES = (
    "stage-observed",
    "stage-orphans",
    "bench-stage-export",
    "span-registry",
    "span-layers",
    "device-metrics",
    "debug-bundle-device",
    "placement-metrics",
    "reason-vocab",
    "debug-bundle-placements",
    "admission-metrics",
    "latency-sweep",
)
