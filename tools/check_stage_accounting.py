#!/usr/bin/env python3
"""Compatibility shim over ``tools/nomadlint``.

The 11 stage-accounting checks that used to live here as a 608-line
monolith are now individual rules in the pluggable AST analysis suite
(``tools/nomadlint/rules/stage_accounting.py`` — run them with
``python -m tools.nomadlint``, which also carries the newer donation-
safety / jit-purity / lock-discipline / config-drift passes).

This module keeps the original surface — the path globals, the AST
helpers and ``check() -> (ok, [problem strings])`` — so
``tests/test_stage_accounting.py`` and operator muscle memory keep
working unmodified.  The path globals are read at call time: tests
monkeypatch them to point single files at mutated copies, and
``check()`` forwards them as nomadlint Context overrides.
"""
from __future__ import annotations

import os
import sys
from typing import List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.nomadlint import astutil as _astutil  # noqa: E402
from tools.nomadlint.core import Context, run  # noqa: E402
from tools.nomadlint.rules import MIGRATED_RULES  # noqa: E402

BATCH_WORKER = os.path.join(
    REPO, "nomad_tpu", "server", "batch_worker.py"
)
PLAN_APPLY = os.path.join(
    REPO, "nomad_tpu", "server", "plan_apply.py"
)
TRACE_MOD = os.path.join(REPO, "nomad_tpu", "trace.py")
BENCH = os.path.join(REPO, "bench.py")
DEVICE_DIR = os.path.join(REPO, "nomad_tpu", "device")
DEVICE_SUPERVISOR = os.path.join(DEVICE_DIR, "supervisor.py")
CLI = os.path.join(REPO, "nomad_tpu", "cli.py")
EXPLAIN_MOD = os.path.join(REPO, "nomad_tpu", "explain.py")
TPU_STACK = os.path.join(REPO, "nomad_tpu", "sched", "tpu_stack.py")
FEASIBLE = os.path.join(REPO, "nomad_tpu", "sched", "feasible.py")
SERVER_MOD = os.path.join(REPO, "nomad_tpu", "server", "server.py")

# historical helper API, re-exported from the nomadlint toolbox
_parse = _astutil.parse
timings_keys = _astutil.timings_keys
observed_keys = _astutil.observed_keys
span_names_used = _astutil.span_names_used
span_registry = _astutil.span_registry


def _context() -> Context:
    """Context bound to this module's (possibly monkeypatched) path
    globals."""
    return Context(
        REPO,
        overrides={
            "batch_worker": BATCH_WORKER,
            "plan_apply": PLAN_APPLY,
            "trace": TRACE_MOD,
            "bench": BENCH,
            "device_dir": DEVICE_DIR,
            "device_supervisor": DEVICE_SUPERVISOR,
            "cli": CLI,
            "explain": EXPLAIN_MOD,
            "tpu_stack": TPU_STACK,
            "feasible": FEASIBLE,
            "server": SERVER_MOD,
        },
    )


def check() -> Tuple[bool, List[str]]:
    """Run the migrated stage-accounting rules (the 11 historical
    checks and ``span-layers``); returns
    ``(ok, [problem strings])`` like the historical monolith."""
    result = run(_context(), MIGRATED_RULES)
    problems = [f.message for f in result.findings]
    return not problems, problems


def main() -> int:
    ok, problems = check()
    if ok:
        print("stage accounting: OK")
        return 0
    for p in problems:
        print(f"stage accounting: {p}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
