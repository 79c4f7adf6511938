"""Tests for repro.analysis — the AST invariant checker.

Three layers:
  * the repo itself must scan clean (this is the tier-1 replacement for
    the deleted grep-guard tests in test_registry.py / test_obs.py);
  * every rule must flag its bad fixture exactly at the `# FLAG: RULE`
    markers and pass its good fixture — including the three encoded
    incidents (PR 6 jnp.max overhead, PR 7 _reauction read-only view,
    pagerank iters=None cache identity);
  * suppressions round-trip, unknown rule ids hard-fail, and the JSON
    report keeps its schema.
"""
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (SuppressionError, all_rules, parse, run_clean,
                            scan)
from repro.analysis.suppressions import apply as apply_suppressions

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "analysis"
_FLAG = re.compile(r"#\s*FLAG:\s*([A-Z]{2}\d{3})")


def expected_flags(path: Path) -> set[tuple[str, int]]:
    out = set()
    for lineno, line in enumerate(path.read_text().splitlines(), start=1):
        for rule in _FLAG.findall(line):
            out.add((rule, lineno))
    return out


BAD_FIXTURES = sorted(FIXTURES.glob("*_bad.py")) + \
    sorted(FIXTURES.glob("incident_*.py"))
GOOD_FIXTURES = sorted(FIXTURES.glob("*_good.py"))


# ---------------------------------------------------------------------------
# the repo scans clean (the single tier-1 invariant gate)
# ---------------------------------------------------------------------------

def test_repo_scans_clean():
    assert run_clean(str(REPO / "src" / "repro")), (
        "unsuppressed analyzer findings in src/repro — run "
        "`python -m repro.analysis src/repro` for the list; fix them or "
        "add a justified entry to analysis_suppressions.txt")


def test_catalogue_has_five_families():
    families = {r.family for r in all_rules().values()}
    assert {"trace-safety", "retrace-hazard", "lock-discipline",
            "aliasing", "layering"} <= families
    assert len(all_rules()) >= 10


# ---------------------------------------------------------------------------
# per-rule fixtures
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", BAD_FIXTURES, ids=lambda p: p.stem)
def test_bad_fixture_flagged(path):
    expected = expected_flags(path)
    assert expected, f"{path.name} has no # FLAG markers"
    got = {(f.rule, f.line) for f in scan([str(path)])}
    assert got == expected, (
        f"{path.name}: expected {sorted(expected)}, got {sorted(got)}")


@pytest.mark.parametrize("path", GOOD_FIXTURES, ids=lambda p: p.stem)
def test_good_fixture_clean(path):
    got = [(f.rule, f.line, f.message) for f in scan([str(path)])]
    assert not got, f"{path.name}: unexpected findings {got}"


def test_every_rule_has_a_bad_fixture_hit():
    hit = set()
    for path in BAD_FIXTURES:
        hit |= {rule for rule, _ in expected_flags(path)}
    assert set(all_rules()) <= hit, (
        f"rules without a bad fixture: {sorted(set(all_rules()) - hit)}")


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_suppression_round_trip():
    bad = str(FIXTURES / "lp002_bad.py")
    findings = scan([bad])
    assert findings
    supps = parse(
        "LP002 tests/fixtures/analysis/lp002_bad.py -- fixture exemption\n",
        all_rules())
    kept, silenced = apply_suppressions(findings, supps)
    assert not kept and len(silenced) == len(findings)
    assert all(s.used for s in supps)


def test_suppression_symbol_glob_narrows():
    bad = str(FIXTURES / "ld001_bad.py")
    findings = scan([bad])
    supps = parse("LD001 *ld001_bad.py Widget.refresh -- only refresh\n",
                  all_rules())
    kept, silenced = apply_suppressions(findings, supps)
    assert silenced and kept  # refresh silenced, bump still flagged
    assert all(f.symbol == "Widget.refresh" for f in silenced)
    assert all(f.symbol != "Widget.refresh" for f in kept)


def test_import_layering_names_the_importing_function():
    """An import inside a function is reported under that function, so a
    suppression can allow it there alone and keep the module's other
    imports flagged."""
    findings = [f for f in scan([str(FIXTURES / "lp003_bad.py")])
                if f.rule == "LP003"]
    assert sorted(f.symbol for f in findings) == [
        "<module>", "<module>", "<module>", "traced_partition"]
    supps = parse("LP003 *lp003_bad.py traced_partition -- one wrapper\n",
                  all_rules())
    kept, silenced = apply_suppressions(findings, supps)
    assert [f.symbol for f in silenced] == ["traced_partition"]
    assert len(kept) == 3


def test_unknown_rule_id_is_an_error():
    with pytest.raises(SuppressionError, match="unknown rule id"):
        parse("ZZ999 foo.py -- whatever\n", all_rules())


def test_missing_justification_is_an_error():
    with pytest.raises(SuppressionError):
        parse("LP002 foo.py\n", all_rules())
    with pytest.raises(SuppressionError, match="empty justification"):
        parse("LP002 foo.py --   \n", all_rules())


def test_unused_suppression_tracked():
    supps = parse("LP002 nowhere/*.py -- never matches\n", all_rules())
    kept, _ = apply_suppressions(scan([str(FIXTURES / "lp002_good.py")]),
                                 supps)
    assert not kept and not supps[0].used


def test_repo_suppressions_file_parses_and_is_fully_used():
    text = (REPO / "analysis_suppressions.txt").read_text()
    supps = parse(text, all_rules())
    assert supps, "repo suppressions file is empty?"
    findings = scan(_repo_sources())
    _, silenced = apply_suppressions(findings, supps)
    unused = [s for s in supps if not s.used]
    assert not unused, (
        f"stale suppressions (matched nothing): "
        f"{[(s.rule, s.path_glob, s.symbol_glob) for s in unused]}")


def _repo_sources():
    from repro.analysis.runner import iter_sources
    return iter_sources([str(REPO / "src" / "repro")])


# ---------------------------------------------------------------------------
# CLI + JSON report schema
# ---------------------------------------------------------------------------

def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=cwd,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})


def test_cli_clean_repo_exit_zero():
    proc = _cli("src/repro")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_findings_exit_one_and_json_schema(tmp_path):
    report = tmp_path / "analysis_report.json"
    proc = _cli(str(FIXTURES / "lp001_bad.py"), "--no-suppressions",
                "--format", "json", "-o", str(report))
    assert proc.returncode == 1
    payload = json.loads(report.read_text())
    assert payload["schema"] == "repro.analysis/v1"
    assert payload["ok"] is False
    assert payload["counts"]["unsuppressed"] == \
        len(payload["findings"]) > 0
    for f in payload["findings"]:
        assert set(f) == {"rule", "file", "line", "col", "symbol",
                          "message"}
        assert f["rule"] in payload["rules"]
    assert "unused_suppressions" in payload


def test_cli_unknown_suppression_rule_exit_two(tmp_path):
    supp = tmp_path / "analysis_suppressions.txt"
    supp.write_text("XX123 foo.py -- stale\n")
    proc = _cli(str(FIXTURES / "lp002_good.py"),
                "--suppressions", str(supp))
    assert proc.returncode == 2
    assert "unknown rule id" in proc.stderr


def test_cli_unknown_rules_filter_exit_two():
    proc = _cli(str(FIXTURES / "lp002_good.py"), "--rules", "NOPE01")
    assert proc.returncode == 2


def test_cli_list_rules():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rule_id in all_rules():
        assert rule_id in proc.stdout
