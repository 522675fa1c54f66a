"""The RPL invariant linter (repro.analysis, PR 6 Layer 1).

Contracts pinned here:
  * every rule in the registry has at least one FIRING corpus case (bad
    file, exact (rule, line) set derived from ``# expect: RPLnnn``
    markers) and at least one NON-FIRING case (good file, zero findings);
  * pragma accounting: a valid allow-pragma on the finding's line or the
    line above suppresses it and records its reason; a reason-less pragma
    suppresses NOTHING and is itself a finding (RPL000); a stale pragma
    (suppresses nothing) is a finding;
  * the REAL tree is clean: ``lint_paths(["src/repro"])`` reports zero
    active findings with at most MAX_PRAGMAS allow-pragmas — the linter
    is a tier-0 gate, not an aspiration;
  * the CLI (``python -m repro.analysis``) exits 0 on the clean tree in
    --strict mode and 1 on a corpus bad file, and writes the JSON report.
"""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (RULES, LintReport, lint_file, lint_paths,
                            lint_source)
from repro.analysis.__main__ import DEFAULT_MAX_PRAGMAS
from repro.analysis.linter import SCHEMA_VERSION

REPO = Path(__file__).resolve().parents[1]
CORPUS = REPO / "tests" / "analysis_corpus"
SRC = REPO / "src" / "repro"

BAD_FILES = sorted(CORPUS.glob("rpl*_bad.py"))
GOOD_FILES = sorted(CORPUS.glob("rpl*_good.py"))


def _expected_markers(path: Path):
    out = []
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        m = re.search(r"# expect: (RPL\d{3})", line)
        if m:
            out.append((m.group(1), i))
    return sorted(out)


@pytest.mark.parametrize("path", BAD_FILES, ids=lambda p: p.stem)
def test_corpus_bad_fires_exactly_at_markers(path):
    expected = _expected_markers(path)
    assert expected, f"{path} has no # expect: markers"
    report = lint_file(str(path))
    got = sorted((f.rule, f.line) for f in report.active)
    assert got == expected
    assert not report.suppressed


@pytest.mark.parametrize("path", GOOD_FILES, ids=lambda p: p.stem)
def test_corpus_good_is_silent(path):
    report = lint_file(str(path))
    assert report.ok, [f.format() for f in report.active]
    assert not report.findings


def test_every_rule_has_firing_and_nonfiring_cases():
    fired = {f.rule for p in BAD_FILES for f in lint_file(str(p)).active}
    assert fired == set(RULES), (
        f"rules without a firing corpus case: {set(RULES) - fired}")
    for rid in RULES:
        stem = rid.lower()
        assert (CORPUS / f"{stem}_bad.py").exists()
        assert (CORPUS / f"{stem}_good.py").exists()


def test_pragma_accounting():
    path = CORPUS / "pragmas_mixed.py"
    report = lint_file(str(path))
    # two valid suppressions: pragma on the line above, pragma on the line
    sup = sorted((f.rule, f.line) for f in report.suppressed)
    assert sup == [("RPL001", 7), ("RPL001", 13)]
    assert all(f.suppression for f in report.suppressed)
    # the reason-less pragma does NOT suppress: the RPL001 under it stays
    # active, and the pragma itself is an RPL000 finding; the stale
    # RPL003 pragma is RPL000 too; pragma-shaped text QUOTED in the
    # docstring / string literal at the bottom of the file is not a
    # pragma — it neither suppresses the adjacent RPL001 (line 37 stays
    # active) nor counts toward the budget
    act = sorted((f.rule, f.line) for f in report.active)
    assert act == [("RPL000", 19), ("RPL000", 25), ("RPL001", 20),
                   ("RPL001", 37)]
    # only the two honored pragmas count against the --strict budget
    assert report.pragma_count == 3  # 2 used + 1 stale (still has a reason)


def test_real_tree_is_clean_within_pragma_budget():
    report = lint_paths([str(SRC)])
    assert report.ok, "\n".join(f.format() for f in report.active)
    assert report.pragma_count <= DEFAULT_MAX_PRAGMAS, (
        f"{report.pragma_count} allow-pragmas > budget "
        f"{DEFAULT_MAX_PRAGMAS}: {[p.to_json() for p in report.pragmas]}")
    # every pragma in the real tree must be USED (no stale ones) — ok
    # already implies it (stale pragmas are RPL000 findings), but pin the
    # suppression count explicitly: 4 machine-audited deliberate sites
    assert len(report.suppressed) == report.pragma_count


def test_syntax_error_is_a_finding_not_a_crash():
    report = lint_source("def broken(:\n", path="x.py")
    assert [f.rule for f in report.findings] == ["RPL999"]
    assert not report.ok


def test_alias_shared_specs_dedupe_to_one_finding_per_site():
    src = (
        "from jax.experimental import pallas as pl\n"
        "specs = [pl.BlockSpec((8, 64), lambda i: (i,))]\n"
        "a = pl.pallas_call(k, grid=(4,), in_specs=specs)\n"
        "b = pl.pallas_call(k, grid=(4,), in_specs=specs)\n"
    )
    report = lint_source(src, path="x.py")
    assert [(f.rule, f.line) for f in report.active] == [("RPL006", 2)]


def test_rules_subset_and_unknown_rule():
    path = CORPUS / "rpl001_bad.py"
    only_2 = lint_file(str(path), rules=["RPL002"])
    assert not only_2.findings
    with pytest.raises(KeyError, match="RPL042"):
        lint_file(str(path), rules=["RPL042"])


def test_report_json_roundtrip(tmp_path):
    report = lint_file(str(CORPUS / "pragmas_mixed.py"))
    out = tmp_path / "report.json"
    report.dump_json(str(out))
    data = json.loads(out.read_text())
    assert data["schema_version"] == SCHEMA_VERSION == 2
    assert data["n_findings"] == len(report.active)
    assert data["n_suppressed"] == 2
    assert data["n_pragmas"] == 3
    assert {f["rule"] for f in data["findings"]} == {"RPL000", "RPL001"}


def _run_cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=env, cwd=str(REPO))


def test_cli_strict_clean_on_real_tree(tmp_path):
    out = tmp_path / "lint.json"
    r = _run_cli("src/repro", "--strict", "--json", str(out))
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(out.read_text())
    assert data["n_findings"] == 0
    assert data["n_pragmas"] <= DEFAULT_MAX_PRAGMAS


def test_cli_fails_on_bad_corpus_file():
    r = _run_cli(str(CORPUS / "rpl001_bad.py"))
    assert r.returncode == 1
    assert "RPL001" in r.stdout


def test_cli_pragma_budget_enforced():
    # budget 0 makes the real tree's 2 pragmas a failure in --strict mode
    r = _run_cli("src/repro", "--strict", "--max-pragmas", "0")
    assert r.returncode == 1
    assert "allow-pragma" in r.stdout + r.stderr


def test_cli_baseline_ratchet(tmp_path):
    """--write-baseline freezes the debt (exit 0), --baseline lets the
    frozen findings through and blocks only NEW ones."""
    base = tmp_path / "baseline.json"
    bad = str(CORPUS / "rpl001_bad.py")
    r = _run_cli(bad, "--write-baseline", str(base))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "written to" in r.stdout
    payload = json.loads(base.read_text())
    assert payload["schema_version"] == SCHEMA_VERSION
    assert payload["baseline"]    # non-empty (rule, file) counts
    # the frozen debt no longer blocks...
    r2 = _run_cli(bad, "--baseline", str(base))
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "0 new" in r2.stdout
    # ...but findings beyond the baseline still do
    r3 = _run_cli(bad, str(CORPUS / "rpl002_bad.py"),
                  "--baseline", str(base))
    assert r3.returncode == 1
    assert "new" in r3.stdout


def test_json_report_doubles_as_baseline(tmp_path):
    """A --json report round-trips as a --baseline input (same
    (rule, file) bucketing, suppressed findings excluded)."""
    out = tmp_path / "report.json"
    bad = str(CORPUS / "rpl001_bad.py")
    r = _run_cli(bad, "--json", str(out))
    assert r.returncode == 1
    assert json.loads(out.read_text())["schema_version"] == SCHEMA_VERSION
    r2 = _run_cli(bad, "--baseline", str(out))
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "0 new" in r2.stdout


def test_cli_exclude_skips_matching_paths():
    r = _run_cli("tests/analysis_corpus",
                 "--exclude", "tests/analysis_corpus")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "checked 0 files" in r.stdout


def test_lint_paths_exclude():
    report = lint_paths([str(CORPUS)],
                        exclude=["_bad", "pragmas_", "xmod_"])
    assert report.ok, [f.format() for f in report.active]
    assert all("_bad" not in f for f in report.files)


def test_cli_rules_subset_strict_composition():
    bad = str(CORPUS / "rpl007_bad.py")
    r = _run_cli(bad, "--rules", "RPL007", "--strict")
    assert r.returncode == 1
    assert "RPL007" in r.stdout
    # the same file under an unrelated rule subset is clean even --strict
    r2 = _run_cli(bad, "--rules", "RPL003", "--strict")
    assert r2.returncode == 0, r2.stdout + r2.stderr
    # unknown rules are a usage error, not a crash
    r3 = _run_cli(bad, "--rules", "RPL042")
    assert r3.returncode == 2
    assert "RPL042" in r3.stderr


def test_cross_module_salt_collision_needs_project_index():
    a = CORPUS / "xmod_salts_a.py"
    b = CORPUS / "xmod_salts_b.py"
    # standalone the imported salt is unresolvable -> RPL009 stays silent
    assert lint_file(str(b)).ok
    # linted together, the ProjectIndex resolves SHARED_SALT and the
    # collision fires at the literal lane in b
    report = lint_paths([str(a), str(b)])
    got = [(f.rule, Path(f.path).name, f.line) for f in report.active]
    assert got == [("RPL009", "xmod_salts_b.py", 15)]


def test_lint_run_is_stdlib_only():
    # the tier-0 CI lint job installs only ruff: a plain lint run (no
    # --contracts) must never import jax — the Layer-2 contracts exports
    # resolve lazily through repro.analysis.__getattr__
    code = (
        "import sys\n"
        "from repro.analysis.__main__ import main\n"
        "rc = main(['tests/analysis_corpus/rpl001_good.py', '--strict'])\n"
        "assert rc == 0, rc\n"
        "assert 'jax' not in sys.modules, 'plain lint run imported jax'\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=str(REPO))
    assert r.returncode == 0, r.stdout + r.stderr


def test_key_lineage_rules_are_stdlib_only():
    # the v2 lineage rules (RPL007-009, incl. the cross-module salt
    # index) ride the same stdlib-only path: they must fire without
    # jax ever being imported
    code = (
        "import sys\n"
        "from repro.analysis.__main__ import main\n"
        "rc = main(['tests/analysis_corpus/rpl007_bad.py',\n"
        "           'tests/analysis_corpus/rpl008_bad.py',\n"
        "           'tests/analysis_corpus/rpl009_bad.py'])\n"
        "assert rc == 1, rc\n"
        "assert 'jax' not in sys.modules, 'key-lineage lint imported jax'\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=str(REPO))
    assert r.returncode == 0, r.stdout + r.stderr
    for rid in ("RPL007", "RPL008", "RPL009"):
        assert rid in r.stdout


class TestLintReportApi:
    def test_extend_merges(self):
        a = lint_file(str(CORPUS / "rpl001_bad.py"))
        b = lint_file(str(CORPUS / "rpl002_bad.py"))
        merged = LintReport()
        merged.extend(a)
        merged.extend(b)
        assert len(merged.active) == len(a.active) + len(b.active)
        assert len(merged.files) == 2
