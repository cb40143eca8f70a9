"""Tier-1 wiring of tools/check_stage_accounting.py: every key in
``BatchWorker.timings`` must be observed via ``_observe`` and exported
through ``bench.py``'s ``e2e_stage_times_s``, so a new pipeline stage
can't silently vanish from the bench or /v1/metrics."""
import os
import sys

TOOLS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools",
)


def _load():
    sys.path.insert(0, TOOLS)
    try:
        import check_stage_accounting

        return check_stage_accounting
    finally:
        sys.path.remove(TOOLS)


def test_every_stage_is_observed_and_exported():
    lint = _load()
    ok, problems = lint.check()
    assert ok, problems


def test_lint_detects_unregistered_span_name(tmp_path, monkeypatch):
    """The span-registry check actually fires: a span name used in
    batch_worker.py that is missing from trace.SPAN_NAMES (here
    simulated by pointing the lint at a registry copy with one name
    renamed) must fail the lint."""
    lint = _load()
    with open(lint.TRACE_MOD) as fh:
        src = fh.read()
    assert '"batch_worker.simulate"' in src
    stripped = src.replace(
        '"batch_worker.simulate"', '"batch_worker.renamed_simulate"'
    )
    bad = tmp_path / "trace.py"
    bad.write_text(stripped)
    monkeypatch.setattr(lint, "TRACE_MOD", str(bad))
    ok, problems = lint.check()
    assert not ok
    assert any(
        "batch_worker.simulate" in p and "SPAN_NAMES" in p
        for p in problems
    ), problems


def test_span_registry_and_usage_are_parsed():
    """The lint's AST extraction sees real data on the live tree (an
    empty 'used' set would make the registry check vacuous)."""
    lint = _load()
    registry = lint.span_registry(lint._parse(lint.TRACE_MOD))
    used = lint.span_names_used(lint._parse(lint.BATCH_WORKER))
    used |= lint.span_names_used(lint._parse(lint.PLAN_APPLY))
    assert "batch_worker.simulate" in used
    assert "replay.conflict" in used
    assert "plan.apply" in used
    # the chunk-wide stages are emitted via _observe_chunk's f-string
    # name; the lint must still see them as batch_worker.<stage>
    assert "batch_worker.launch" in used
    assert "batch_worker.fetch" in used
    assert used <= registry


def test_lint_detects_a_dropped_stage(tmp_path, monkeypatch):
    """The lint actually fires: removing a stage's _observe call (here
    simulated by pointing the lint at a stripped copy) must fail."""
    lint = _load()
    with open(lint.BATCH_WORKER) as fh:
        src = fh.read()
    assert 'self._observe("simulate"' in src
    stripped = src.replace('self._observe("simulate"', '_unused("simulate"')
    bad = tmp_path / "batch_worker.py"
    bad.write_text(stripped)
    monkeypatch.setattr(lint, "BATCH_WORKER", str(bad))
    ok, problems = lint.check()
    assert not ok
    assert any("simulate" in p for p in problems)


def _span_layers_problems(lint, tmp_path, monkeypatch, old, new):
    with open(lint.TRACE_MOD) as fh:
        src = fh.read()
    assert old in src
    bad = tmp_path / "trace.py"
    bad.write_text(src.replace(old, new))
    monkeypatch.setattr(lint, "TRACE_MOD", str(bad))
    ok, problems = lint.check()
    assert not ok
    return problems


def test_lint_holds_layer_of_to_span_names(tmp_path, monkeypatch):
    """A registered span name with no LAYER_OF entry fails the lint:
    the fold would look through it and its layer's metric would lose
    it silently."""
    problems = _span_layers_problems(
        _load(), tmp_path, monkeypatch,
        '    "plan.stage_wait": "plan_handoff",\n', "",
    )
    assert any(
        "plan.stage_wait" in p and "LAYER_OF" in p for p in problems
    ), problems


def test_lint_detects_a_layer_with_no_span_name(tmp_path, monkeypatch):
    """The other direction: the replay pool's only source marked
    event-only leaves `trace.self.replay_pool` reading 0 forever."""
    problems = _span_layers_problems(
        _load(), tmp_path, monkeypatch,
        '"replay.speculate": "replay_pool"', '"replay.speculate": EVENT',
    )
    assert any(
        "replay_pool" in p and "no span name" in p for p in problems
    ), problems


def test_every_span_name_has_a_layer_or_is_event_only():
    """The table the lint reads is the table the fold uses."""
    from nomad_tpu.trace import LAYER_OF, LAYERS, SPAN_NAMES

    assert set(LAYER_OF) == set(SPAN_NAMES)
    used = {v for v in LAYER_OF.values() if v is not None}
    assert used == set(LAYERS)
