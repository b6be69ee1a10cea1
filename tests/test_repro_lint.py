"""Tests for tools/repro_lint: per-rule fixtures, pragmas, baseline
mechanism, CLI behaviour, and regression coverage for the live findings
this PR fixed or grandfathered.

Fixtures live in ``tests/lint_fixtures/`` (excluded from the linter's
own file walk — they are deliberately-bad code).  Path-scoped rules
(RL004 engine hot paths, RL006 ``src/``) are exercised by spoofing
``LintModule.rel_path`` while reading fixture content.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from tools.repro_lint import (
    ALL_RULES,
    Finding,
    LintModule,
    collect_py_files,
    get_rules,
    lint_paths,
    load_baseline,
    match_baseline,
    write_baseline,
)
from tools.repro_lint.core import run_rules
from tools.repro_lint.cli import main as cli_main

REPO_ROOT = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"


def lint_fixture(name, rel_path=None, select=None):
    """Run rules over one fixture, optionally spoofing its rel_path."""
    src = (FIXTURES / name).read_text(encoding="utf-8")
    module = LintModule(rel_path or f"tests/lint_fixtures/{name}", src)
    return run_rules(module, get_rules(select))


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# per-rule fixtures: positive flags, negative stays clean
# ---------------------------------------------------------------------------
def test_rl001_bad_fixture_flagged():
    found = lint_fixture("rl001_bad.py", select=["RL001"])
    assert len(found) == 3  # seed+1000*d, base_seed+7919*c, seed-j*31
    assert rules_of(found) == ["RL001"]


def test_rl001_good_fixture_clean():
    assert lint_fixture("rl001_good.py", select=["RL001"]) == []


def test_rl002_bad_fixture_flagged():
    found = lint_fixture("rl002_bad.py", select=["RL002"])
    # alias, attribute, inline producer, naming convention
    assert len(found) == 4
    assert rules_of(found) == ["RL002"]


def test_rl002_good_fixture_clean():
    assert lint_fixture("rl002_good.py", select=["RL002"]) == []


def test_rl003_bad_fixture_flagged():
    found = lint_fixture("rl003_bad.py", select=["RL003"])
    # default-record, explicit False, batch-indexed, inline, .task_events
    assert len(found) == 5
    assert rules_of(found) == ["RL003"]


def test_rl003_good_fixture_clean():
    assert lint_fixture("rl003_good.py", select=["RL003"]) == []


def test_rl004_bad_fixture_flagged_under_engine_path():
    found = lint_fixture(
        "rl004_bad.py",
        rel_path="src/repro/core/engine.py",
        select=["RL004"],
    )
    assert len(found) == 2  # for-loop REGISTRY call + while-loop observe
    assert rules_of(found) == ["RL004"]


def test_rl004_good_fixture_clean_under_engine_path():
    found = lint_fixture(
        "rl004_good.py",
        rel_path="src/repro/core/engine_jax.py",
        select=["RL004"],
    )
    assert found == []


def test_rl004_flags_host_spans_inside_hot_path_loops():
    found = lint_fixture(
        "rl004_span_bad.py",
        rel_path="src/repro/core/engine_jax.py",
        select=["RL004"],
    )
    assert len(found) == 2  # span() in a for-loop + TraceAnnotation in a while
    assert rules_of(found) == ["RL004"]
    assert all("host span" in f.message for f in found)


def test_rl004_span_around_the_loop_is_clean():
    found = lint_fixture(
        "rl004_span_good.py",
        rel_path="src/repro/core/engine.py",
        select=["RL004"],
    )
    assert found == []


def test_rl004_scoped_to_hot_paths_only():
    # same bad content under a non-engine path: rule does not apply
    found = lint_fixture(
        "rl004_bad.py",
        rel_path="src/repro/core/placement.py",
        select=["RL004"],
    )
    assert found == []


def test_rl005_bad_fixture_flagged():
    found = lint_fixture("rl005_bad.py", select=["RL005"])
    # float(), .item(), np. call, branch on traced param
    assert len(found) == 4
    assert rules_of(found) == ["RL005"]


def test_rl005_good_fixture_clean():
    # closure-config branching (`if collect:`) must NOT be flagged
    assert lint_fixture("rl005_good.py", select=["RL005"]) == []


def test_rl006_bad_fixture_flagged_under_src_path():
    found = lint_fixture(
        "rl006_bad.py",
        rel_path="src/repro/serve/handlers.py",
        select=["RL006"],
    )
    assert len(found) == 2
    assert rules_of(found) == ["RL006"]


def test_rl006_good_fixture_clean_under_src_path():
    found = lint_fixture(
        "rl006_good.py",
        rel_path="src/repro/serve/handlers.py",
        select=["RL006"],
    )
    assert found == []


def test_rl006_scoped_to_src_only():
    # tests/benchmarks exercise defaults on purpose — rule must not apply
    found = lint_fixture("rl006_bad.py", select=["RL006"])
    assert found == []


def test_rl007_bad_fixture_flagged():
    found = lint_fixture("rl007_bad.py", select=["RL007"])
    assert len(found) == 3  # bw assign, nic_caps assign, bandwidths= kwarg
    assert rules_of(found) == ["RL007"]


def test_rl007_good_fixture_clean():
    assert lint_fixture("rl007_good.py", select=["RL007"]) == []


# ---------------------------------------------------------------------------
# the live violation this PR fixed: placement.py chain seeds
# ---------------------------------------------------------------------------
def test_rl001_catches_pre_pr9_placement_seed_wiring():
    """The checker must flag all three affine sites of the pre-fix
    ``etp_multichain`` excerpt — the regression this PR's satellite
    removed from the live tree."""
    found = lint_fixture("rl001_placement_pre_pr9.py", select=["RL001"])
    assert len(found) == 3
    assert all("derive_seed" in f.message for f in found)


def test_live_tree_placement_is_clean_now():
    """The actual placement.py no longer trips RL001."""
    findings, errors = lint_paths(
        ["src/repro/core/placement.py"], REPO_ROOT, get_rules(["RL001"])
    )
    assert errors == []
    assert findings == []


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------
def test_line_pragma_waives_only_its_line():
    found = lint_fixture("pragma_line.py", select=["RL001"])
    assert len(found) == 1
    assert found[0].line > 5  # the un-waived second function


def test_file_pragma_waives_whole_file():
    assert lint_fixture("pragma_file.py", select=["RL001"]) == []


# ---------------------------------------------------------------------------
# baseline mechanism
# ---------------------------------------------------------------------------
def _finding(rule="RL001", path="src/x.py", line=3, snippet="seed + 2 * d"):
    return Finding(
        rule=rule, path=path, line=line, col=0,
        message="m", snippet=snippet,
    )


def test_baselined_finding_is_suppressed():
    f = _finding()
    match = match_baseline(
        [f], [{"rule": f.rule, "path": f.path, "snippet": f.snippet}]
    )
    assert match.new == []
    assert match.suppressed == [f]
    assert match.stale == []


def test_new_finding_fails_despite_baseline():
    old = _finding(snippet="seed + 2 * d")
    new = _finding(snippet="seed + 5 * d", line=9)
    match = match_baseline(
        [old, new],
        [{"rule": old.rule, "path": old.path, "snippet": old.snippet}],
    )
    assert match.new == [new]
    assert match.suppressed == [old]


def test_baseline_survives_line_drift():
    """Identity is (rule, path, snippet): moving the line must not
    un-baseline the finding."""
    drifted = _finding(line=120)
    match = match_baseline(
        [drifted],
        [{"rule": drifted.rule, "path": drifted.path,
          "snippet": drifted.snippet}],
    )
    assert match.new == []


def test_stale_baseline_entries_reported():
    match = match_baseline(
        [], [{"rule": "RL001", "path": "gone.py", "snippet": "x"}]
    )
    assert len(match.stale) == 1


def test_baseline_multiset_matching():
    """N identical snippets need N baseline entries."""
    f1 = _finding(line=3)
    f2 = _finding(line=9)
    entry = {"rule": f1.rule, "path": f1.path, "snippet": f1.snippet}
    match = match_baseline([f1, f2], [entry])
    assert len(match.new) == 1
    assert len(match.suppressed) == 1


def test_update_baseline_deterministic(tmp_path):
    findings = [
        _finding(path="src/b.py", line=9, snippet="s2"),
        _finding(path="src/a.py", line=3, snippet="s1"),
    ]
    p1, p2 = tmp_path / "b1.json", tmp_path / "b2.json"
    write_baseline(p1, findings)
    write_baseline(p2, list(reversed(findings)))
    assert p1.read_text() == p2.read_text()
    entries = load_baseline(p1)
    assert len(entries) == 2
    match = match_baseline(findings, entries)
    assert match.new == [] and match.stale == []


def test_load_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_clean_on_repo_head(capsys):
    """Acceptance gate: the PR head lints clean over the default paths."""
    rc = cli_main(["src", "tests", "benchmarks"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "OK" in out


def test_cli_fails_on_fixture_and_json_reports_it(tmp_path, capsys):
    bad = FIXTURES / "rl001_bad.py"
    rc = cli_main(
        [str(bad), "--format", "json", "--no-baseline", "--root",
         str(REPO_ROOT)]
    )
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert len(payload["new"]) == 3
    assert payload["errors"] == []
    assert {f["rule"] for f in payload["new"]} == {"RL001"}


def test_cli_update_baseline_roundtrip(tmp_path, capsys):
    bad = FIXTURES / "rl001_bad.py"
    bl = tmp_path / "baseline.json"
    rc = cli_main(
        [str(bad), "--baseline", str(bl), "--update-baseline"]
    )
    assert rc == 0
    capsys.readouterr()
    # now the same findings are fully baselined -> exit 0
    rc = cli_main([str(bad), "--baseline", str(bl)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "3 baselined" in out


def test_cli_select_unknown_rule_is_usage_error(capsys):
    rc = cli_main(["src", "--select", "RL999"])
    assert rc == 2


def test_cli_list_rules(capsys):
    rc = cli_main(["--list-rules"])
    out = capsys.readouterr().out
    assert rc == 0
    for rule in ALL_RULES:
        assert rule.rule_id in out


def test_cli_parse_error_fails(tmp_path, capsys):
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n", encoding="utf-8")
    rc = cli_main([str(broken), "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "PARSE ERROR" in out


def test_module_entrypoint_runs():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.repro_lint", "--list-rules"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0
    assert "RL001" in proc.stdout


# ---------------------------------------------------------------------------
# file walking
# ---------------------------------------------------------------------------
def test_collect_skips_lint_fixtures():
    files = collect_py_files(["tests"], REPO_ROOT)
    assert files, "tests/ should contain python files"
    assert not any("lint_fixtures" in f.parts for f in files)


def test_get_rules_select_and_reject():
    assert [r.rule_id for r in get_rules(["RL003"])] == ["RL003"]
    with pytest.raises(ValueError, match="RL999"):
        get_rules(["RL999"])
