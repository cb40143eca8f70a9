"""Config/registry drift: every ``NOMAD_TPU_*`` environment knob must
be registered in ``nomad_tpu/envknobs.py`` and documented in the
``docs/ARCHITECTURE.md`` knob table — in both directions, so a new
knob can't ship undocumented and a removed one can't haunt the docs.

This generalizes the metric/span registry checks (4–10 of the
stage-accounting family) to the configuration surface: the registry
is the single place an operator looks up a knob, and the lint is what
keeps it complete.  Usage is collected by AST scan for full-match
``NOMAD_TPU_[A-Z0-9_]+`` string constants (docstrings excluded) over
``nomad_tpu/``, ``bench.py`` and ``tests/`` — reads through
``os.environ``/``os.getenv``, constants like ``FAULT_ENV``, and env
dicts handed to subprocesses all surface the name as exactly such a
literal.

Five directions checked:

1. every knob used in code is registered in ``ENV_KNOBS``;
2. every registered knob appears in the docs table;
3. every ``NOMAD_TPU_*`` name in the docs table is registered
   (no stale doc rows);
4. every registered knob is actually read somewhere (no dead
   registry rows);
5. every registered knob is set by something other than its read
   site — a ``setenv`` / ``setdefault`` call, an ``x["NAME"] = ...``
   store, an env-dict key, or ``NAME=`` in ``tools/ci_check.sh`` —
   or is listed in ``DEPLOYMENT_KNOBS`` (addresses, ports, paths,
   pod identity).  An option nothing sets is a constant.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Set

from ..astutil import (
    assigned_strings,
    dict_key_strings,
    string_constants,
)
from ..core import Context, Finding, Rule, register

ENV_RE = re.compile(r"^NOMAD_TPU_[A-Z0-9_]+$")
DOC_ENV_RE = re.compile(r"NOMAD_TPU_[A-Z0-9_]+")
SHELL_SET_RE = re.compile(r"\b(NOMAD_TPU_[A-Z0-9_]+)=")
# entry points outside the usage scan whose setters count (direction 5)
SETTER_ONLY_FILES = ("chip_smoke.py", "__graft_entry__.py")
SETTER_SHELL = "tools/ci_check.sh"


def _knob(node: ast.AST) -> Set[str]:
    if (
        isinstance(node, ast.Constant)
        and isinstance(node.value, str)
        and ENV_RE.match(node.value)
    ):
        return {node.value}
    return set()


def setters(tree: ast.AST) -> Set[str]:
    """Knob names a module sets: first argument of a ``setenv`` /
    ``setdefault`` call, subscript of an assignment target, or key of
    a dict literal."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("setenv", "setdefault")
            and node.args
        ):
            out |= _knob(node.args[0])
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    out |= _knob(t.slice)
        elif isinstance(node, ast.Dict):
            for k in node.keys:
                if k is not None:
                    out |= _knob(k)
    return out


@register
class ConfigDriftRule(Rule):
    name = "config-drift"
    description = (
        "NOMAD_TPU_* knobs registered in envknobs.py + documented"
    )
    # needs BOTH sides of every pair (usage scan + registry + docs
    # table): a --files-narrowed run sees only a slice of the reads,
    # so direction 4 (dead registry rows) would false-fire and
    # direction 1 would false-pass — the runner always hands this
    # rule the full file set
    cross_file = True

    def _files(self, ctx: Context) -> List[str]:
        """The scan scope, the registry itself left out."""
        override = ctx.overrides.get("scan_files")
        if override is not None:
            files = list(override)
        else:
            files = ctx.scan_files()
            files.append(ctx.path("bench"))
            tests_dir = os.path.join(ctx.repo, "tests")
            if os.path.isdir(tests_dir):
                files.extend(
                    os.path.join(tests_dir, fn)
                    for fn in sorted(os.listdir(tests_dir))
                    if fn.endswith(".py")
                )
        registry = (ctx.path("envknobs"), ctx.default_path("envknobs"))
        return [p for p in files if p not in registry]

    def _usage(self, ctx: Context) -> Dict[str, List]:
        """knob -> [(path, line), ...] across the scan scope."""
        out: Dict[str, List] = {}
        for path in self._files(ctx):
            for value, line in string_constants(ctx.tree(path)):
                if ENV_RE.match(value):
                    out.setdefault(value, []).append(
                        (path, line)
                    )
        return out

    def _set(self, ctx: Context) -> Set[str]:
        """Knobs with a setter in the scan scope (plus, on the live
        tree, the root entry points and ``tools/ci_check.sh``)."""
        files = self._files(ctx)
        out: Set[str] = set()
        if "scan_files" not in ctx.overrides:
            files += [
                os.path.join(ctx.repo, fn)
                for fn in SETTER_ONLY_FILES
            ]
            out |= set(SHELL_SET_RE.findall(
                ctx.source(os.path.join(ctx.repo, SETTER_SHELL))
            ))
        for path in files:
            out |= setters(ctx.tree(path))
        return out

    def check(self, ctx: Context) -> List[Finding]:
        envknobs_path = ctx.path("envknobs")
        doc_path = ctx.path("arch_doc")
        findings: List[Finding] = []
        try:
            registry = dict_key_strings(
                ctx.tree(envknobs_path), "ENV_KNOBS"
            )
        except OSError:
            return [
                Finding(
                    self.name, envknobs_path, 0,
                    "central env-knob registry "
                    "nomad_tpu/envknobs.py is missing",
                )
            ]
        registered = {n for n in registry if ENV_RE.match(n)}
        if not registered:
            return [
                Finding(
                    self.name, envknobs_path, 0,
                    "could not find the ENV_KNOBS registry "
                    "literal in nomad_tpu/envknobs.py",
                )
            ]
        documented: Set[str] = set()
        try:
            doc_src = ctx.source(doc_path)
        except OSError:
            doc_src = ""
            findings.append(
                Finding(
                    self.name, doc_path, 0,
                    "docs knob table missing (docs/ARCHITECTURE.md"
                    " not found)",
                )
            )
        for line in doc_src.splitlines():
            if line.lstrip().startswith("|"):
                documented |= set(DOC_ENV_RE.findall(line))

        usage = self._usage(ctx)
        for knob in sorted(set(usage) - registered):
            path, line = usage[knob][0]
            findings.append(
                Finding(
                    self.name, path, line,
                    f"env knob {knob} is read here but missing "
                    "from the ENV_KNOBS registry "
                    "(nomad_tpu/envknobs.py) — new knobs can't "
                    "ship unregistered",
                )
            )
        for knob in sorted(registered - documented):
            findings.append(
                Finding(
                    self.name, envknobs_path, 0,
                    f"env knob {knob} is registered but missing "
                    "from the docs/ARCHITECTURE.md knob table",
                )
            )
        for knob in sorted(documented - registered):
            findings.append(
                Finding(
                    self.name, doc_path, 0,
                    f"docs table documents {knob} but it is not "
                    "in the ENV_KNOBS registry — stale doc row "
                    "or missing registration",
                )
            )
        # direction 4 needs the FULL usage scan to be meaningful: a
        # --files/fixture narrowing sees only a slice of the reads,
        # so every other registered knob would read as dead
        if "scan_files" not in ctx.overrides:
            for knob in sorted(registered - set(usage)):
                findings.append(
                    Finding(
                        self.name, envknobs_path, 0,
                        f"env knob {knob} is registered but never "
                        "read anywhere — dead registry row",
                    )
                )
        deployment = assigned_strings(
            ctx.tree(envknobs_path), "DEPLOYMENT_KNOBS"
        )
        for knob in sorted(registered - deployment - self._set(ctx)):
            findings.append(
                Finding(
                    self.name, envknobs_path, 0,
                    f"env knob {knob} is never set by a test, smoke, "
                    "bench.py or tools/ci_check.sh and is not in "
                    "DEPLOYMENT_KNOBS — an option nothing sets is a "
                    "constant: fold it",
                )
            )
        return findings

    @classmethod
    def _fixture_ctx(cls, ctx: Context, which: str) -> Context:
        fixtures = os.path.join(
            os.path.dirname(os.path.dirname(__file__)),
            "fixtures", "config_drift",
        )
        return ctx.with_overrides(
            scan_files=[os.path.join(fixtures, f"{which}.py")],
            envknobs=os.path.join(fixtures, "envknobs.py"),
            arch_doc=os.path.join(fixtures, "docs.md"),
        )

    @classmethod
    def bad_fixture(cls, ctx, tmpdir):
        return cls._fixture_ctx(ctx, "bad")

    @classmethod
    def clean_fixture(cls, ctx, tmpdir):
        return cls._fixture_ctx(ctx, "clean")
