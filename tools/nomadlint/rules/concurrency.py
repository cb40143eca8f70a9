"""Whole-program concurrency rules over the flowgraph core.

* **shared-state-guard** — static race detector: any attribute of a
  shared singleton written from one thread entry and touched from
  another must share a lock across both sites, or carry a justified
  ``SHARED_STATE_ALLOWLIST`` entry.  Findings name both access sites
  and both thread entries.  The ``NOMAD_TPU_TSAN=1`` runtime
  sanitizer (nomad_tpu/tsan.py) checks the same allowlist from the
  other direction: every runtime-observed conflicting pair must be
  lock-ordered or allowlisted here, so the list can't grow stale
  entries in either direction.
* **blocking-while-locked** — no lock-holding call may transitively
  reach a blocking op (``block_until_ready``, ``device_put``/
  ``device_get``, sockets, ``time.sleep``, event waits): a wedged
  device call under a lock parks every thread that needs it — the
  wedge class that ate the r03–r05 bench rounds.  ``Condition.wait``
  under its own lock is exempt (it releases the lock).

Both rules read the cross-file flowgraph, so a ``--files``-narrowed
run computes it from the FULL module set (``cross_file = True``) —
a narrowed run can't false-pass by hiding one side of a race pair.
"""
from __future__ import annotations

import os
import re
from typing import Dict, List, Set, Tuple

from ..core import Context, Finding, Rule, register
from ..flowgraph import (
    blocking_op,
    entries_conflict,
    flowgraph,
)

# (family, attr regex) -> one-line justification.  Every entry must
# match at least one live race pair on a full run — stale entries are
# themselves findings, so the allowlist can't rot.  The TSAN soak
# (tests/test_tsan.py) asserts runtime-observed conflicts stay inside
# this list.
SHARED_STATE_ALLOWLIST: Tuple[Tuple[str, str, str], ...] = (
    (
        "StateStore",
        r"jobs|evals|allocs|deployments|namespaces|job_versions"
        r"|scaling_events|scaling_policies|scheduler_config"
        r"|_scaling_by_target|_index|_table_index",
        "deliberately lock-free read side: CPython dict/int reads "
        "are GIL-atomic and every mutation runs under _lock; "
        "schedulers fence cross-table consistency via "
        "snapshot_min_index, so a racy read sees a complete older "
        "index, never a torn row",
    ),
    (
        "DeviceSupervisor",
        r"_device_ready",
        "monotonic bool latch (False->True once the device first "
        "answers); GIL-atomic store and both writers converge on "
        "True",
    ),
    (
        "DeviceSupervisor",
        r"_state|backend_epoch",
        "state/epoch reads outside the lock are advisory fast-path "
        "checks; every transition revalidates and writes under "
        "_lock, and consumers key caches by the epoch so a stale "
        "read costs one extra resync, never stale device buffers",
    ),
    (
        "DeviceSupervisor",
        r"last_error|last_incident|_incident|_recover_streak"
        r"|canary_ok|canary_fail|probe_timeouts|watchdog_trips",
        "incident/counter bookkeeping: single GIL-atomic scalar "
        "stores whose worst-case race is one miscounted or stale "
        "/v1/device status field, never scheduling state",
    ),
    (
        "Server",
        r"_running|_leader_established",
        "lifecycle latches: bool stores are GIL-atomic and every "
        "consumer loop (sweeper, HTTP heartbeat path) re-checks "
        "per tick, so a racing stop()/establish is observed one "
        "tick later — shutdown needs no lock ordering (the TSAN "
        "soak first caught this pair at runtime)",
    ),
    (
        "DeviceSupervisor",
        r"_warm_hooks",
        "warm-hook registration list: GIL-atomic append from "
        "leadership setup; the probe thread iterates the whole "
        "list per recovery pass, and a hook registered mid-pass "
        "is picked up on the next one",
    ),
    (
        "Worker",
        r"_replay_pool",
        "lazy pool singleton: one writer (the worker thread); "
        "stop() reads a complete-or-None reference (GIL-atomic "
        "object store) and shuts it down after joining the thread",
    ),
    (
        "Worker",
        r"_thread",
        "generation latch: start() rebinds the reference "
        "(GIL-atomic object store) and run() threads compare it "
        "against current_thread() per loop tick — a straggler from "
        "a previous leadership generation observes the new binding "
        "one tick later and exits; both orderings are safe",
    ),
    (
        "Server",
        r"_clients",
        "node->connection registry: dict get/set are GIL-atomic; a "
        "concurrent re-register keeps one of the two live "
        "connections and the client's next register heals it",
    ),
    (
        "Server",
        r"_heartbeat_deadlines|_down_wave",
        "per-node deadline map + the pending mass-death gather set: "
        "HTTP threads set/pop single keys, the sweeper iterates "
        "list() snapshots and pops expired ones; dict ops are "
        "GIL-atomic, a deadline racing its own expiry is re-armed "
        "by the node's next heartbeat, and the wave commit "
        "re-verifies each member against the live store (already-"
        "down and re-heartbeated nodes drop out)",
    ),
    (
        "Tracer",
        r"_by_id",
        "hot-path span append reads the ring dict lock-free (the "
        "O(1)-append/<50us contract); dict get is GIL-atomic and "
        "eviction under _lock swaps whole trace objects, so a "
        "racing lookup sees a complete (old) trace",
    ),
    (
        "Tracer",
        r"_segments",
        "the hot-path resolve skips _lock while the segment map is "
        "empty (every server that is not a fan-out follower): an "
        "emptiness test on a dict is GIL-atomic, every mutation "
        "runs under _lock, and a lease that opens a segment races "
        "only spans of an eval this server does not hold yet",
    ),
    (
        "Tracer",
        r"_ingress",
        "a threading.local: each HTTP handler thread reads and "
        "writes its own slot (the attribute itself is bound once, "
        "at construction)",
    ),
    (
        "Worker",
        r"_pod",
        "set-once pod-service latch: _attach_pod checks-then-binds "
        "a complete PodService (GIL-atomic object store) from the "
        "mesh bring-up path and is idempotent across leadership "
        "rebuilds; dispose() closes it only after stop() joined "
        "the worker thread, so no launch can race the teardown",
    ),
    (
        "Worker",
        r"_backend_epoch|_cand_cache|_mask_cache|_port_col_cache"
        r"|_dev_codes_cache|_dev_aff_cache|_donate_carries"
        r"|_launch_ewma|_launch_ewma_seed|_mesh_ewma_seed|_mesh"
        r"|_mesh_hosts"
        r"|_sharded_runners|_mirror_dirty|_mirror_dirty_sharded"
        r"|_usage_cache|_usage_cache_sharded",
        "the documented wedge-bypass epoch protocol: "
        "_on_device_transition must flush these WITHOUT locks (a "
        "wedged sacrificial thread may hold _usage_cache_lock "
        "forever), so it rebinds fresh objects — never mutates in "
        "place — and every consumer keys entries by _backend_epoch "
        "and discards stale publishes",
    ),
)


def _allowlisted(fam: str, attr: str) -> int:
    """Index of the matching allowlist entry, or -1."""
    for i, (afam, pattern, _why) in enumerate(
        SHARED_STATE_ALLOWLIST
    ):
        if afam == fam and re.fullmatch(pattern, attr):
            return i
    return -1


def _fixture_ctx(ctx: Context, sub: str, name: str) -> Context:
    fixtures = os.path.join(
        os.path.dirname(os.path.dirname(__file__)),
        "fixtures",
        sub,
    )
    return ctx.with_overrides(
        scan_files=[os.path.join(fixtures, name)]
    )


@register
class SharedStateGuardRule(Rule):
    name = "shared-state-guard"
    description = (
        "cross-thread shared attributes are consistently locked "
        "or allowlisted"
    )
    cross_file = True

    def check(self, ctx: Context) -> List[Finding]:
        g = flowgraph(ctx)
        findings: List[Finding] = []
        used: Set[int] = set()
        for (fam, attr), sites in sorted(g.shared_access.items()):
            pair = None
            for a in sites:
                if a.kind != "w":
                    continue
                for b in sites:
                    if not entries_conflict(a.entry, b.entry):
                        continue
                    if a.guards & b.guards:
                        continue
                    pair = (a, b)
                    break
                if pair:
                    break
            if pair is None:
                continue
            idx = _allowlisted(fam, attr)
            if idx >= 0:
                used.add(idx)
                continue
            a, b = pair
            kind_b = "written" if b.kind == "w" else "read"
            findings.append(
                Finding(
                    self.name,
                    a.path,
                    a.line,
                    f"{fam}.{attr} is written at "
                    f"{os.path.basename(a.path)}:{a.line} "
                    f"(thread entry {a.entry.render()}) and "
                    f"{kind_b} at "
                    f"{os.path.basename(b.path)}:{b.line} "
                    f"(thread entry {b.entry.render()}) with no "
                    "common lock "
                    f"(guards: {sorted(a.guards) or 'none'} vs "
                    f"{sorted(b.guards) or 'none'}) — guard both "
                    "sites with one lock or add a justified "
                    "SHARED_STATE_ALLOWLIST entry "
                    "(tools/nomadlint/rules/concurrency.py)",
                )
            )
        if "scan_files" not in ctx.overrides:
            for i, (fam, pattern, _why) in enumerate(
                SHARED_STATE_ALLOWLIST
            ):
                if i not in used:
                    findings.append(
                        Finding(
                            self.name,
                            os.path.abspath(__file__),
                            0,
                            "stale SHARED_STATE_ALLOWLIST entry "
                            f"({fam!r}, {pattern!r}): no live race "
                            "pair matches it — remove it so the "
                            "allowlist can't rot",
                        )
                    )
        return findings

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return _fixture_ctx(ctx, "shared_state", "bad.py")

    @classmethod
    def clean_fixture(cls, ctx, tmpdir):
        return _fixture_ctx(ctx, "shared_state", "clean.py")


@register
class BlockingWhileLockedRule(Rule):
    name = "blocking-while-locked"
    description = (
        "no lock-holding call transitively reaches a blocking op"
    )
    cross_file = True

    def check(self, ctx: Context) -> List[Finding]:
        g = flowgraph(ctx)
        findings: List[Finding] = []
        seen: Set[Tuple[str, int, str]] = set()
        for qual in sorted(g.methods):
            info = g.methods[qual]
            for call in info.calls:
                if not call.held:
                    continue
                locks = ", ".join(sorted(call.held))
                op = blocking_op(call, g.lock_attr_names)
                if op is not None:
                    key = (info.path, call.line, op)
                    if key not in seen:
                        seen.add(key)
                        findings.append(
                            Finding(
                                self.name,
                                info.path,
                                call.line,
                                f"{qual} calls blocking {op} "
                                f"while holding {locks} — a "
                                "wedged call parks every thread "
                                "queued on the lock (the r03–r05 "
                                "bench wedge class); move the "
                                "blocking op outside the critical "
                                "section",
                            )
                        )
                callee = g.resolve(info.cls, call, info)
                if callee is None:
                    continue
                for op, path in sorted(
                    g.blocking.get(callee.qualname, {}).items()
                ):
                    key = (info.path, call.line, op)
                    if key in seen:
                        continue
                    seen.add(key)
                    findings.append(
                        Finding(
                            self.name,
                            info.path,
                            call.line,
                            f"{qual} holds {locks} while calling "
                            f"{callee.qualname}, which reaches "
                            f"blocking {op} ({path}) — a wedged "
                            "call parks every thread queued on "
                            "the lock; move the blocking op "
                            "outside the critical section or "
                            "suppress with the documented wedge "
                            "recovery story",
                        )
                    )
        return findings

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return _fixture_ctx(ctx, "blocking", "bad.py")

    @classmethod
    def clean_fixture(cls, ctx, tmpdir):
        return _fixture_ctx(ctx, "blocking", "clean.py")
