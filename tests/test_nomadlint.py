"""Tier-1 wiring of tools/nomadlint — the pluggable AST analysis
suite.  Every registered rule must trip on its bad fixture and stay
quiet on its clean fixture, and a repo-wide run must report zero
unsuppressed findings (suppressions must carry justifications)."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.nomadlint import Context, all_rules, run  # noqa: E402
from tools.nomadlint.rules import MIGRATED_RULES  # noqa: E402


def _ctx():
    return Context(REPO)


def test_rule_inventory():
    """11 migrated stage-accounting rules + the 4 new passes."""
    names = [cls.name for cls in all_rules()]
    assert len(names) == len(set(names))
    for migrated in MIGRATED_RULES:
        assert migrated in names
    for new in (
        "donation-safety",
        "jit-purity",
        "lock-discipline",
        "config-drift",
    ):
        assert new in names
    assert len(names) >= 15


def test_repo_wide_run_is_clean():
    """The acceptance gate: zero unsuppressed findings on the live
    tree with all rules active."""
    result = run(_ctx())
    assert result.ok, [
        f.render(REPO) for f in result.findings
    ]
    # the two documented, justified suppressions (mirror-sync
    # donation + per-probe canary retrace) are present and applied
    assert len(result.suppressed) >= 2
    rules = {f.rule for f in result.suppressed}
    assert "donation-safety" in rules
    assert "jit-purity" in rules


def test_every_rule_trips_its_bad_fixture(tmp_path):
    ctx = _ctx()
    for cls in all_rules():
        bad_ctx = cls.bad_fixture(ctx, str(tmp_path))
        findings = cls().check(bad_ctx)
        assert findings, f"rule {cls.name} missed its bad fixture"
        assert all(f.rule == cls.name for f in findings)


def test_every_rule_passes_its_clean_fixture(tmp_path):
    ctx = _ctx()
    for cls in all_rules():
        clean_ctx = cls.clean_fixture(ctx, str(tmp_path))
        if clean_ctx is ctx:
            continue  # live repo: covered by the repo-wide run
        findings = cls().check(clean_ctx)
        assert not findings, (
            f"rule {cls.name} tripped on its clean fixture: "
            f"{findings[0].message}"
        )


def test_suppression_hides_finding_and_requires_reason(tmp_path):
    """A justified suppression hides the finding; a bare one (no
    `-- reason`) surfaces as a bare-suppression finding instead."""
    fixtures = os.path.join(
        REPO, "tools", "nomadlint", "fixtures", "donation"
    )
    with open(os.path.join(fixtures, "bad.py")) as fh:
        bad_src = fh.read()
    # findings anchor on the donating CALL line
    justified = bad_src.replace(
        "    out = patch(col, idx, vals)",
        "    # nomadlint: disable=donation-safety -- fixture: "
        "verified safe\n    out = patch(col, idx, vals)",
    )
    assert justified != bad_src
    p1 = tmp_path / "suppressed.py"
    p1.write_text(justified)
    result = run(
        _ctx().with_overrides(scan_files=[str(p1)]),
        ["donation-safety"],
    )
    lines = {f.line for f in result.suppressed}
    assert result.suppressed and lines
    assert all(
        f.rule != "donation-safety" or f.line not in lines
        for f in result.findings
    )

    bare = bad_src.replace(
        "    out = patch(col, idx, vals)",
        "    # nomadlint: disable=donation-safety\n"
        "    out = patch(col, idx, vals)",
    )
    p2 = tmp_path / "bare.py"
    p2.write_text(bare)
    result = run(
        _ctx().with_overrides(scan_files=[str(p2)]),
        ["donation-safety"],
    )
    assert any(
        f.rule == "bare-suppression" for f in result.findings
    ), [f.message for f in result.findings]


def test_wrong_rule_suppression_does_not_hide(tmp_path):
    fixtures = os.path.join(
        REPO, "tools", "nomadlint", "fixtures", "donation"
    )
    with open(os.path.join(fixtures, "bad.py")) as fh:
        bad_src = fh.read()
    wrong = bad_src.replace(
        "    out = patch(col, idx, vals)",
        "    # nomadlint: disable=jit-purity -- wrong rule\n"
        "    out = patch(col, idx, vals)",
    )
    p = tmp_path / "wrong.py"
    p.write_text(wrong)
    result = run(
        _ctx().with_overrides(scan_files=[str(p)]),
        ["donation-safety"],
    )
    assert any(
        f.rule == "donation-safety" for f in result.findings
    )


def test_a_knob_nothing_sets_is_a_finding(tmp_path):
    """config-drift direction 5: a folded knob registered again (row,
    docs line and all) with no setter anywhere in the tree is a
    finding; listing it as a deployment setting is the one way out."""
    ctx = _ctx()
    # in two parts: a whole name here would be a use of the knob
    knob = "NOMAD_TPU_" + "PIPELINE_DEPTH"
    row = f'    "{knob}": EnvKnob("2", "nomad_tpu/x.py", "folded"),\n'
    registry = ctx.source(ctx.path("envknobs")).replace(
        "ENV_KNOBS: Dict[str, EnvKnob] = {\n",
        "ENV_KNOBS: Dict[str, EnvKnob] = {\n" + row,
    )
    assert row in registry
    docs = tmp_path / "docs.md"
    docs.write_text(
        ctx.source(ctx.path("arch_doc")) + f"\n| `{knob}` | `2` |\n"
    )

    def unset(registry_src):
        path = tmp_path / "envknobs.py"
        path.write_text(registry_src)
        result = run(
            ctx.with_overrides(
                envknobs=str(path), arch_doc=str(docs)
            ),
            ["config-drift"],
        )
        return [
            f.message for f in result.findings
            if "never set" in f.message
        ]

    found = unset(registry)
    assert len(found) == 1 and knob in found[0], found
    assert unset(registry.replace(
        "DEPLOYMENT_KNOBS = frozenset({",
        f'DEPLOYMENT_KNOBS = frozenset({{"{knob}",',
    )) == []


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.nomadlint", *args],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_cli_repo_run_exits_zero_with_json():
    proc = _run_cli("--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    assert payload["findings"] == []
    assert len(payload["rules_run"]) >= 15


def test_cli_exits_nonzero_on_bad_fixture():
    bad = os.path.join(
        "tools", "nomadlint", "fixtures", "donation", "bad.py"
    )
    proc = _run_cli(
        "--rules", "donation-safety", "--files", bad
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "donation-safety" in proc.stderr


def test_cli_unknown_rule_is_usage_error():
    proc = _run_cli("--rules", "no-such-rule")
    assert proc.returncode == 2


def test_compat_shim_matches_nomadlint():
    """tools/check_stage_accounting.py delegates to the migrated
    rules: its check() agrees with a nomadlint run of the same
    subset."""
    tools_dir = os.path.join(REPO, "tools")
    sys.path.insert(0, tools_dir)
    try:
        import check_stage_accounting as shim
    finally:
        sys.path.remove(tools_dir)
    ok, problems = shim.check()
    assert ok, problems
    result = run(_ctx(), MIGRATED_RULES)
    assert result.ok
    assert len(result.rules_run) == len(MIGRATED_RULES)
