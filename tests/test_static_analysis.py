"""SPMD hygiene analyzer (bigdl_tpu/analysis): the tier-1 repo-wide
zero-findings gate, exact (line, code) parity against the EXPECT-marked
fixtures, the utils/compat.py no-false-positive guarantee, and the CLI
contract (exit codes, --select/--ignore, --json, baseline handling).

Pure AST — none of this traces or compiles anything, so the whole
module runs in milliseconds plus one subprocess for the `python -m`
entry point.
"""

import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bigdl_tpu.analysis import (
    DEFAULT_PATHS, analyze_paths, analyze_source, load_baseline, main,
    rule_codes, split_baselined,
)

pytestmark = pytest.mark.analysis

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"
BASELINE = REPO / "analysis_baseline.txt"

BAD_FIXTURES = sorted(FIXTURES.glob("bad_*.py"))
ALL_CODES = ("ASY301", "ASY302", "ASY303", "ASY304", "ASY305",
             "ASY306", "ASY307", "ASY308", "ASY309", "ASY310",
             "MH401", "MH402", "MH403", "MH404", "MH405",
             "SPMD101", "SPMD102", "SPMD103", "SPMD104", "SPMD105",
             "SPMD106", "SRV201", "SRV202", "SRV203", "SRV204", "SRV205",
             "SRV206", "SRV207", "SRV208")
ASY_CODES = ["ASY301", "ASY302", "ASY303", "ASY304", "ASY305",
             "ASY306", "ASY307", "ASY308", "ASY309", "ASY310"]
MH_CODES = ["MH401", "MH402", "MH403", "MH404", "MH405"]


def _expected(path: Path):
    """(line, code) pairs from the fixture's `# EXPECT: CODE` comments."""
    out = set()
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        m = re.search(r"#\s*EXPECT:\s*([A-Z]+\d+)", line)
        if m:
            out.add((i, m.group(1)))
    return out


# -- the tier-1 acceptance gate --------------------------------------------

def test_repo_has_zero_non_baselined_findings(monkeypatch):
    """`python -m bigdl_tpu.analysis bigdl_tpu benchmarks tests` must be
    clean: every finding either fixed or explicitly grandfathered in the
    committed baseline.  Re-introducing the PR-4 spec drift or a direct
    jax.shard_map import anywhere in those trees fails THIS test with
    the rule code and file:line."""
    monkeypatch.chdir(REPO)
    # analyze_paths silently skips nonexistent paths — guard against a
    # renamed tree turning this gate into a zero-file false green (the
    # CLI exits 2 on this; the API caller must check itself)
    for p in DEFAULT_PATHS:
        assert (REPO / p).is_dir(), f"analyzed tree missing: {p}"
    findings = analyze_paths(DEFAULT_PATHS)
    new, _ = split_baselined(findings, load_baseline(str(BASELINE)))
    assert not new, (
        "SPMD hygiene violations (fix them, or baseline each with a "
        "justification comment in analysis_baseline.txt — see "
        "docs/analysis.md):\n"
        + "\n".join(f.format() for f in new))


def test_rule_registry_is_complete():
    assert tuple(sorted(rule_codes())) == ALL_CODES


# -- fixture parity ---------------------------------------------------------

@pytest.mark.parametrize("fixture", BAD_FIXTURES,
                         ids=[p.stem for p in BAD_FIXTURES])
def test_bad_fixture_exact_findings(fixture):
    """Exact (line, code) parity with the EXPECT comments — unmarked
    lines in the bad files double as false-positive checks (static
    shape branches, getattr of unrelated attrs, legit multi-axis tuple
    specs, carry rebinding...)."""
    expected = _expected(fixture)
    assert expected, f"{fixture} has no EXPECT annotations"
    got = {(f.line, f.code) for f in analyze_paths([str(fixture)])}
    assert got == expected, (
        f"missing: {sorted(expected - got)}; "
        f"spurious: {sorted(got - expected)}")


def test_good_fixture_is_clean():
    assert analyze_paths([str(FIXTURES / "good_clean.py")]) == []


def test_compat_module_itself_is_clean():
    """utils/compat.py is the one module allowed to spell the moved APIs
    directly — the analyzer must not flag its own shim."""
    compat = REPO / "bigdl_tpu" / "utils" / "compat.py"
    assert analyze_paths([str(compat)]) == []


def test_compat_rule_fires_on_compat_body_elsewhere(tmp_path):
    """The compat exemption is PATH-based, not content-based: the same
    probes outside utils/compat.py are flagged."""
    clone = tmp_path / "not_compat.py"
    clone.write_text((REPO / "bigdl_tpu" / "utils"
                      / "compat.py").read_text())
    assert any(f.code == "SPMD101" for f in analyze_paths([str(clone)]))


def test_fixture_dir_excluded_from_tree_scans():
    """Repo-wide scans must skip analysis_fixtures/ (deliberate
    violations) while explicit file paths still reach inside."""
    findings = analyze_paths([str(FIXTURES.parent)],
                             select=["SPMD102"])
    assert not any("analysis_fixtures" in f.path for f in findings)


# -- acceptance: re-introducing the historical bugs ------------------------

def test_reintroduced_pr4_spec_drift_is_caught(tmp_path):
    src = (
        "from jax.sharding import PartitionSpec as P\n"
        "ROWS = P(('data',))\n"
    )
    fs = analyze_source(src, "drifted.py")
    assert [(f.code, f.line) for f in fs] == [("SPMD102", 2)]


def test_duplicate_lines_get_distinct_fingerprints():
    """Baselining one occurrence of a drifted line must not silence a
    second paste of the identical line — fingerprints are occurrence-
    indexed."""
    src = (  # analysis: no-embed — deliberate violations under test
        "from jax.sharding import PartitionSpec as P\n"
        "SPECS = [\n"
        "    P(('data',)),\n"
        "    P(('data',)),\n"
        "]\n"
    )
    fs = analyze_source(src, "dup.py")
    assert [f.code for f in fs] == ["SPMD102", "SPMD102"]
    assert fs[0].source == fs[1].source
    assert fs[0].fingerprint() != fs[1].fingerprint()
    new, old = split_baselined(fs, {fs[0].baseline_key()})
    assert [f.line for f in old] == [3] and [f.line for f in new] == [4]


def test_reintroduced_direct_shard_map_import_is_caught():
    fs = analyze_source(
        "from jax.experimental.shard_map import shard_map\n", "bad.py")
    assert [(f.code, f.line) for f in fs] == [("SPMD101", 1)]


# -- CLI contract -----------------------------------------------------------

def test_cli_exit_codes_and_select(capsys, monkeypatch):
    monkeypatch.chdir(REPO)
    bad = str(FIXTURES / "bad_spec_spelling.py")

    assert main([bad, "--no-baseline"]) == 1
    out = capsys.readouterr().out
    assert "SPMD102" in out and "bad_spec_spelling.py:19" in out

    # selecting a rule the file does not violate -> clean, exit 0
    assert main([bad, "--no-baseline", "--select", "SPMD104"]) == 0
    # ignoring the violated rule -> clean
    assert main([bad, "--no-baseline", "--ignore", "SPMD102"]) == 0
    capsys.readouterr()
    # unknown code -> usage error
    assert main([bad, "--select", "SPMD999"]) == 2
    # a typo'd / wrong-cwd path must be a usage error, never a false
    # green from scanning zero files
    assert main(["no_such_tree"]) == 2


def test_cli_json_report(capsys, monkeypatch):
    monkeypatch.chdir(REPO)
    rc = main([str(FIXTURES / "bad_donation.py"), "--no-baseline",
               "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["summary"]["new"] == 4
    assert {f["code"] for f in report["findings"]} == {"SPMD104"}
    assert all(f["fingerprint"] for f in report["findings"])

    rc = main([str(FIXTURES / "good_clean.py"), "--no-baseline", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0 and report["summary"] == {
        "new": 0, "baselined": 0, "total": 0}


def test_cli_baseline_roundtrip(tmp_path, capsys, monkeypatch):
    """--write-baseline output, committed as the baseline, silences
    exactly the current findings (and ONLY those: the fingerprint is
    content-addressed, so editing the offending line re-flags it)."""
    monkeypatch.chdir(REPO)
    bad = str(FIXTURES / "bad_tracer_leak.py")
    assert main([bad, "--write-baseline"]) == 0
    baseline = tmp_path / "baseline.txt"
    baseline.write_text(capsys.readouterr().out)

    assert main([bad, "--baseline", str(baseline)]) == 0
    assert "baselined" in capsys.readouterr().out

    # a NEW violation in the same file is not covered by the baseline
    drifted = tmp_path / "drifted_copy.py"
    drifted.write_text(Path(bad).read_text()
                       + "\n\nimport jax\nsm = jax.shard_map\n")
    assert main([str(drifted), "--baseline", str(baseline)]) == 1


def test_module_entrypoint_subprocess():
    """The `python -m bigdl_tpu.analysis` contract CI rides on: nonzero
    on findings, zero on clean, works from the repo root."""
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.analysis",
         str(FIXTURES / "bad_compat_drift.py"), "--no-baseline",
         "--quiet"],
        cwd=str(REPO), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stderr
    assert "SPMD101" in proc.stdout

    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.analysis", "--list-rules"],
        cwd=str(REPO), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    for code in ALL_CODES:
        assert code in proc.stdout


# -- whole-program: SRV201 coverage of the REAL dispatch sites --------------

SERVING_DIR = REPO / "bigdl_tpu" / "serving"
_DISPATCH_RE = re.compile(
    r'(?:self|eng)\._dispatch\(\s*"[a-z_]+",\s*([\w.]+),')


def _serving_tree(tmp_path: Path) -> Path:
    """Copy bigdl_tpu/serving into a path that keeps the
    bigdl_tpu/serving/ scope marker (the SRV201 rule's path scoping)."""
    dst = tmp_path / "bigdl_tpu" / "serving"
    dst.mkdir(parents=True)
    for f in SERVING_DIR.glob("*.py"):
        (dst / f.name).write_text(f.read_text())
    return dst


def test_srv201_real_dispatch_sites_enumerated():
    """Every serving module that dispatches compiled steps routes them
    through _dispatch — and the routed sites exist where we think."""
    counts = {f.name: len(_DISPATCH_RE.findall(f.read_text()))
              for f in sorted(SERVING_DIR.glob("*.py"))}
    sites = {k: v for k, v in counts.items() if v}
    assert sites == {"admission.py": 2, "chunked.py": 1,
                     "engine.py": 2, "speculative.py": 3}, sites


@pytest.mark.parametrize("fname", ["engine.py", "speculative.py",
                                   "admission.py", "chunked.py"])
def test_srv201_catches_every_unrouted_dispatch_site(tmp_path, fname):
    """THE SRV201 acceptance proof: deleting the _dispatch routing on
    any one decode/verify/draft/prefill call site in serving/ makes the
    scan fail — demonstrated against a copy of the REAL serving tree
    with each site's routing stripped in turn (the call shape stays
    exactly the real one).  The unmutated copy must scan SRV201-clean,
    so the coverage is exact, not vacuous."""
    tree = _serving_tree(tmp_path)
    clean = analyze_paths([str(tmp_path)], select=["SRV201"])
    assert clean == [], [f.format() for f in clean]

    src = (tree / fname).read_text()
    matches = list(_DISPATCH_RE.finditer(src))
    assert matches, f"{fname} has no dispatch sites?"
    for i, _ in enumerate(matches):
        mutated = []
        for j, m in enumerate(matches):
            if j == i:
                mutated.append((m.start(), m.end(), f"{m.group(1)}("))
        start, end, repl = mutated[0]
        (tree / fname).write_text(src[:start] + repl + src[end:])
        found = analyze_paths([str(tmp_path)], select=["SRV201"])
        assert [f.code for f in found] == ["SRV201"], (
            f"stripping dispatch site {i} in {fname} must yield exactly "
            f"one SRV201, got: {[f.format() for f in found]}")
        assert found[0].path.endswith(fname)
    (tree / fname).write_text(src)


# -- whole-program: cross-module donation lifting ---------------------------

def test_cross_module_donation_reuse():
    """SRV204 resolves a donating helper THROUGH the import graph: the
    helper module is clean alone, the caller only fires when both files
    are in the project."""
    caller = FIXTURES / "xmod_donation_caller.py"
    helper = FIXTURES / "xmod_donation_helper.py"
    assert analyze_paths([str(helper)]) == []
    # caller alone: the import cannot resolve — documented degradation
    assert analyze_paths([str(caller)]) == []
    got = [(Path(f.path).name, f.line, f.code)
           for f in analyze_paths([str(caller), str(helper)])]
    assert got == [("xmod_donation_caller.py", 11, "SRV204")]


# -- whole-program: schema extraction beats the fallback --------------------

def test_srv205_vocabulary_extracted_from_project():
    """The finish-reason vocabulary comes from the PROJECT's
    ServingMetrics.FINISH_REASONS when visible — not the built-in
    fallback (proved by overriding it)."""
    src = (  # analysis: no-embed — deliberate violations under test
        "from bigdl_tpu.serving.metrics import whatever\n"
        "class ServingMetrics:\n"
        "    FINISH_REASONS = frozenset({'weird'})\n"
        "def f(engine, req):\n"
        "    engine._shed(req, 'weird')\n"
        "    engine._shed(req, 'eos')\n"
    )
    got = [(f.line, f.code) for f in analyze_source(src, "mini.py")]
    assert got == [(6, "SRV205")]


def test_srv206_real_tree_clean_and_mutation_caught(tmp_path):
    """SRV206 census over the REAL serving tree: the unmutated copy
    scans clean (every removal from a running/partial table wears a
    requeue/handoff/disposition or lives in the table-owning
    scheduler), and stripping the row_state capture from the one
    direct removal outside the scheduler (PrefillWorker._release —
    the handoff release) yields exactly one SRV206 at disagg.py: the
    no-stranded-rows invariant is enforced where the failover
    machinery actually lives, not just on fixtures."""
    tree = _serving_tree(tmp_path)
    clean = analyze_paths([str(tmp_path)], select=["SRV206"])
    assert clean == [], [f.format() for f in clean]
    src = (tree / "disagg.py").read_text()
    needle = "payload = self.engine.row_state(slot)"
    assert needle in src, "_release moved — update the census"
    (tree / "disagg.py").write_text(
        src.replace(needle, "payload = None", 1))
    found = analyze_paths([str(tmp_path)], select=["SRV206"])
    assert [f.code for f in found] == ["SRV206"], \
        [f.format() for f in found]
    assert found[0].path.endswith("disagg.py")


def test_srv207_real_tree_clean_and_mutation_caught(tmp_path):
    """SRV207 census over the REAL serving tree: the unmutated copy
    scans clean (every block-store write of row state rides the
    pack_payload codec, and every spill site serializes BEFORE
    freeing), and stripping the codec call at THE row-spill site
    (TieredKVStore.put_row) yields exactly one SRV207 at kv_tier.py —
    the tier-codec discipline is enforced where the spill machinery
    actually lives, not just on fixtures."""
    tree = _serving_tree(tmp_path)
    clean = analyze_paths([str(tmp_path)], select=["SRV207"])
    assert clean == [], [f.format() for f in clean]
    src = (tree / "kv_tier.py").read_text()
    needle = "blob = pack_payload(request_meta(req), payload)"
    assert needle in src, "put_row moved — update the census"
    (tree / "kv_tier.py").write_text(
        src.replace(needle, "blob = payload", 1))
    found = analyze_paths([str(tmp_path)], select=["SRV207"])
    assert [f.code for f in found] == ["SRV207"], \
        [f.format() for f in found]
    assert found[0].path.endswith("kv_tier.py")


def test_srv208_real_tree_clean_and_mutation_caught(tmp_path):
    """SRV208 census over the REAL serving tree: the unmutated copy
    scans clean (every control-knob write lives in a constructor or a
    declared ACTUATION_SITES unit — the bus's setters, the engine's
    degrade pair, disagg's autoscale/kill paths), and adding a stray
    ``req.max_new_tokens`` write inside the admission replay helper
    yields exactly one SRV208 at engine.py — the declared-actuator
    discipline is enforced where the knobs actually live, not just on
    fixtures."""
    tree = _serving_tree(tmp_path)
    clean = analyze_paths([str(tmp_path)], select=["SRV208"])
    assert clean == [], [f.format() for f in clean]
    src = (tree / "engine.py").read_text()
    needle = "req.next_token = fed0[-1]"
    assert needle in src, "_admitted_prefill_tokens moved — update the census"
    (tree / "engine.py").write_text(
        src.replace(needle, needle + "\n        req.max_new_tokens = 1", 1))
    found = analyze_paths([str(tmp_path)], select=["SRV208"])
    assert [f.code for f in found] == ["SRV208"], \
        [f.format() for f in found]
    assert found[0].path.endswith("engine.py")


def test_srv208_reads_real_vocabulary():
    """The shipped autopilot.ACTUATION_SITES is what the repo gate
    checks against (extraction, not fallback, on the real tree) — and
    the fallback vocabulary stays in sync with it."""
    from bigdl_tpu.analysis.core import _parse_file, collect_file_facts
    from bigdl_tpu.analysis.rules import _DEFAULT_ACTUATION_SITES
    from bigdl_tpu.serving.autopilot import ACTUATION_SITES

    text = (REPO / "bigdl_tpu" / "serving" / "autopilot.py").read_text()
    ctx, err = _parse_file(text, "bigdl_tpu/serving/autopilot.py")
    assert err is None
    facts = collect_file_facts(ctx)
    assert set(facts["actuation_sites"]) == set(ACTUATION_SITES)
    assert _DEFAULT_ACTUATION_SITES == ACTUATION_SITES


def test_srv205_reads_real_vocabulary():
    """The shipped ServingMetrics.FINISH_REASONS is what the repo gate
    checks against (extraction, not fallback, on the real tree)."""
    from bigdl_tpu.analysis.core import (
        _parse_file, collect_file_facts, extract_embedded_units,
    )

    text = (REPO / "bigdl_tpu" / "serving" / "metrics.py").read_text()
    ctx, err = _parse_file(text, "bigdl_tpu/serving/metrics.py")
    assert err is None
    facts = collect_file_facts(ctx)
    assert set(facts.get("finish_reasons", [])) == {
        "eos", "stop", "length", "shed", "deadline", "infeasible",
        "error", "cancelled"}
    assert extract_embedded_units(ctx) == []


# -- embedded string programs (the PR-5 blind-spot closure) -----------------

def test_embedded_program_line_mapping(tmp_path):
    """Findings inside an assigned string program report HOST-file
    lines; format placeholders are unescaped first."""
    host = tmp_path / "host.py"
    host.write_text(
        'CHILD = r"""\n'
        "import jax\n"
        "from jax.experimental.shard_map import shard_map\n"
        "x = {repo!r}\n"
        '"""\n')
    got = [(f.code, f.line) for f in analyze_paths([str(host)])]
    assert got == [("SPMD101", 3)]


def test_docstrings_and_prose_are_not_embedded_units(tmp_path):
    host = tmp_path / "host.py"
    host.write_text(
        '"""Module docstring mentioning import jax and\n'
        "from jax.experimental.shard_map import shard_map\n"
        'across several lines of prose."""\n'
        "BANNER = (\n"
        "    'no import here'\n"
        ")\n")
    assert analyze_paths([str(host)]) == []


def test_pod_projection_child_scans_clean():
    """The historical blind spot itself: pod_projection's _CHILD is now
    parsed and scanned (it routes through compat, so it must be
    clean)."""
    target = REPO / "benchmarks" / "pod_projection.py"
    assert analyze_paths([str(target)]) == []


# -- baseline hygiene: stale warning + --prune-baseline ---------------------

def _staled_tree(tmp_path):
    """A tmp tree holding a copy of the spec-spelling fixture, plus a
    baseline with its LIVE entries, one STALE entry for a deleted file
    UNDER the tree, and one entry for a file OUTSIDE the tree."""
    from bigdl_tpu.analysis import format_baseline_entry

    tree = tmp_path / "tree"
    tree.mkdir()
    (tree / "bad_spec.py").write_text(
        (FIXTURES / "bad_spec_spelling.py").read_text())
    fs = analyze_paths([str(tree)])
    assert fs
    prefix = fs[0].path.rsplit("/", 1)[0]
    lines = ["# header comment"]
    for f in fs:
        lines.append(format_baseline_entry(f))
    lines += ["# stale justification",
              f"{prefix}/deleted_file.py:SPMD102:deadbeefdead",
              "# other-tree justification",
              "elsewhere/other.py:SPMD102:feedfacefeed"]
    baseline = tmp_path / "baseline.txt"
    baseline.write_text("\n".join(lines) + "\n")
    return tree, baseline


def test_stale_baseline_warning_preserves_exit_code(tmp_path, capsys,
                                                    monkeypatch):
    monkeypatch.chdir(REPO)
    tree, baseline = _staled_tree(tmp_path)
    assert main([str(tree), "--baseline", str(baseline)]) == 0
    err = capsys.readouterr().err
    assert "1 stale baseline entry" in err and "--prune-baseline" in err


def test_prune_baseline_drops_only_covered_stale_entries(
        tmp_path, capsys, monkeypatch):
    """Pruning removes the dead entry for the deleted file UNDER the
    scanned tree (justification comment included) but must NOT touch
    live entries or entries for files the scan never covered — a
    partial scan deleting other trees' grandfathered findings would
    un-baseline them on the next full run."""
    monkeypatch.chdir(REPO)
    tree, baseline = _staled_tree(tmp_path)
    assert main([str(tree), "--baseline", str(baseline),
                 "--prune-baseline"]) == 0
    out = capsys.readouterr()
    assert "pruned 1 stale baseline entry" in out.err
    text = baseline.read_text()
    assert "deadbeefdead" not in text
    assert "# stale justification" not in text     # justification went too
    assert "# header comment" in text
    assert "elsewhere/other.py" in text            # out of scope: kept
    # every live entry survived: the scan is still fully baselined,
    # and the out-of-scope entry is not warned about
    assert main([str(tree), "--baseline", str(baseline)]) == 0
    assert "stale" not in capsys.readouterr().err


def test_partial_scan_never_prunes_other_files(tmp_path, capsys,
                                               monkeypatch):
    """The review-found regression shape: scanning file B with a
    baseline full of file A's live entries must not warn about or
    prune A's entries."""
    monkeypatch.chdir(REPO)
    tree, baseline = _staled_tree(tmp_path)
    other = tmp_path / "clean.py"
    other.write_text("X = 1\n")
    before = baseline.read_text()
    assert main([str(other), "--baseline", str(baseline),
                 "--prune-baseline"]) == 0
    err = capsys.readouterr().err
    assert "pruned 0 stale" in err
    assert baseline.read_text() == before


# -- SARIF output -----------------------------------------------------------

def test_sarif_output(tmp_path, capsys, monkeypatch):
    monkeypatch.chdir(REPO)
    bad = str(FIXTURES / "bad_donation.py")
    rc = main([bad, "--no-baseline", "--format", "sarif"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["version"] == "2.1.0"
    run = report["runs"][0]
    assert run["tool"]["driver"]["name"] == "bigdl-tpu-analysis"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert set(ALL_CODES) <= rule_ids
    results = run["results"]
    assert results and all(r["ruleId"] == "SPMD104" for r in results)
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad_donation.py")
    assert loc["region"]["startLine"] > 0
    assert results[0]["partialFingerprints"]["bigdlAnalysis/v1"]
    # clean input -> empty results, exit 0, same schema
    rc = main([str(FIXTURES / "good_clean.py"), "--no-baseline",
               "--format", "sarif"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0 and report["runs"][0]["results"] == []


# -- the cached/parallel scan driver ----------------------------------------

def test_scan_cache_and_parallel_parity(tmp_path, monkeypatch):
    """scan() with the findings cache (cold AND warm) returns
    byte-identical findings to analyze_paths — the cache can never
    change results, only skip work.  (The FORK workers are pinned by
    the subprocess tests below — in-process pytest has jax loaded,
    which rightly disables fork.)"""
    from bigdl_tpu.analysis import scan

    monkeypatch.chdir(REPO)
    paths = ["bigdl_tpu/analysis", "bigdl_tpu/serving"]
    plain = [f.to_dict() for f in analyze_paths(paths)]
    cache = tmp_path / "cache.json"
    cold = [f.to_dict() for f in scan(paths, cache_path=str(cache))]
    warm = [f.to_dict() for f in scan(paths, cache_path=str(cache))]
    assert cold == plain and warm == plain
    assert cache.exists()


# -- the inline suppression idiom -------------------------------------------

def test_inline_suppression_idiom():
    """`# analysis: ok[: CODES]` silences a line that is legitimate
    despite matching a rule — scoped to the listed codes; unrelated
    codes on the line still fire."""
    base = "from jax.sharding import PartitionSpec as P\n"
    assert analyze_source(base + "R = P(('data',))\n", "s.py")
    assert analyze_source(
        base + "R = P(('data',))  # analysis: ok\n", "s.py") == []
    assert analyze_source(
        base + "R = P(('data',))  # analysis: ok: SPMD102\n",
        "s.py") == []
    # listing a DIFFERENT code does not suppress
    fs = analyze_source(
        base + "R = P(('data',))  # analysis: ok: SRV205\n", "s.py")
    assert [f.code for f in fs] == ["SPMD102"]


def test_scan_cache_never_pollutes_facts_across_edits(tmp_path):
    """Regression: merge_facts must not mutate per-file fact dicts that
    live inside cache entries — a polluted entry would keep replaying a
    STALE cross-module fact (e.g. a deleted step binding) and make
    cached scans diverge from --no-cache after an edit."""
    from bigdl_tpu.analysis import scan

    proj = tmp_path / "bigdl_tpu" / "serving"
    proj.mkdir(parents=True)
    (proj / "other.py").write_text("X = 1\n")
    binder = (
        "from bigdl_tpu.models.transformer import get_prefill_step\n"
        "class A:\n"
        "    def __init__(self, m):\n"
        "        self._b_fn = get_prefill_step(m, None)\n")
    (proj / "f.py").write_text(binder)
    (proj / "g.py").write_text(
        "class B:\n"
        "    def run(self, x):\n"
        "        return self._b_fn(x)\n")
    cache = tmp_path / "cache.json"
    run1 = scan([str(tmp_path)], cache_path=str(cache))
    assert [f.code for f in run1] == ["SRV201"]
    # delete the binding: the bypass callsite is no longer a step call
    (proj / "f.py").write_text("def unrelated():\n    return 0\n")
    fresh = scan([str(tmp_path)])
    cached = scan([str(tmp_path)], cache_path=str(cache))
    assert fresh == [] and cached == [], (
        [f.format() for f in cached])


def test_cli_parallel_scan_matches_library(tmp_path):
    """The fork-worker path (CLI subprocess — in-process pytest has jax
    loaded, which rightly disables fork) returns the same findings as
    the serial library API, including cross-module facts split across
    workers."""
    lib = analyze_paths([str(FIXTURES / "bad_dispatch_bypass.py"),
                         str(FIXTURES / "bad_finish_reason.py")])
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.analysis",
         str(FIXTURES / "bad_dispatch_bypass.py"),
         str(FIXTURES / "bad_finish_reason.py"),
         "--no-baseline", "--json", "--jobs", "2",
         "--no-cache"],
        cwd=str(REPO), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stderr
    report = json.loads(proc.stdout)
    got = {(Path(f["path"]).name, f["line"], f["code"])
           for f in report["findings"]}
    want = {(Path(f.path).name, f.line, f.code) for f in lib}
    assert got == want


def test_prune_baseline_conflicts_with_no_baseline(tmp_path, capsys,
                                                   monkeypatch):
    """--no-baseline makes every entry look stale — the combination
    must be a usage error, never an empty baseline file."""
    monkeypatch.chdir(REPO)
    tree, baseline = _staled_tree(tmp_path)
    before = baseline.read_text()
    rc = main([str(tree), "--baseline", str(baseline),
               "--no-baseline", "--prune-baseline"])
    assert rc == 2
    assert "conflicts" in capsys.readouterr().err
    assert baseline.read_text() == before


def test_subset_scan_keeps_whole_repo_cache(tmp_path):
    """A subset scan must MERGE into the cache, not evict the other
    trees' entries — alternating full and subset scans would otherwise
    pay the cold cost every time."""
    import json as _json

    from bigdl_tpu.analysis import scan

    a = tmp_path / "a"
    b = tmp_path / "b"
    a.mkdir(), b.mkdir()
    (a / "x.py").write_text("X = 1\n")
    (b / "y.py").write_text("Y = 2\n")
    cache = tmp_path / "cache.json"
    scan([str(a), str(b)], cache_path=str(cache))
    full = set(_json.loads(cache.read_text())["files"])
    assert len(full) == 2
    scan([str(a)], cache_path=str(cache))          # subset
    assert set(_json.loads(cache.read_text())["files"]) == full


def test_cli_parallel_workers_resolve_cross_file_facts(tmp_path):
    """Fork workers over a REAL multi-file serving tree: the SRV201
    binding lives in engine.py while the stripped call site is in
    admission.py — different worker slices, so the finding only
    survives if the phase-1 fact exchange merges across workers."""
    tree = _serving_tree(tmp_path)
    src = (tree / "admission.py").read_text()
    m = next(_DISPATCH_RE.finditer(src))
    (tree / "admission.py").write_text(
        src[:m.start()] + f"{m.group(1)}(" + src[m.end():])
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.analysis", str(tmp_path),
         "--no-baseline", "--select", "SRV201", "--json",
         "--jobs", "2", "--no-cache"],
        cwd=str(REPO), capture_output=True, text=True, timeout=180)
    assert proc.returncode == 1, proc.stderr
    report = json.loads(proc.stdout)
    assert [(Path(f["path"]).name, f["code"])
            for f in report["findings"]] == [("admission.py", "SRV201")]


# -- the ASY3xx call graph: hot-path reachability ---------------------------

def test_hotpath_annotation_and_self_method_edges():
    """`# analysis: hotpath-root` marks a root; `self.` method edges
    carry hotness; an identical method NOT reachable from any root
    stays exempt — reachability, not path glob."""
    src = (
        "class Loop:\n"
        "    def run(self):  # analysis: hotpath-root\n"
        "        return self.helper()\n"
        "    def helper(self):\n"
        "        return float(self.carry['pos'][0])\n"
        "    def cold(self):\n"
        "        return float(self.carry['pos'][0])\n")
    got = [(f.line, f.code) for f in analyze_source(src, "mini.py")]
    assert got == [(5, "ASY301")]


@pytest.mark.parametrize("cls,meth", [
    ("ServingEngine", "step"), ("Speculator", "step"),
    ("ChunkedAdmissionController", "pump")])
def test_builtin_roots_cover_the_serving_surface(cls, meth):
    """Each built-in hot-path root is picked up by (class, method)
    name with no annotation; the same body on a non-root class stays
    cold."""
    body = "        return float(self.carry['pos'][0])\n"
    hot = f"class {cls}:\n    def {meth}(self):\n{body}"
    assert [f.code for f in analyze_source(hot, "m.py")] == ["ASY301"]
    cold = f"class Unrelated:\n    def {meth}(self):\n{body}"
    assert analyze_source(cold, "m.py") == []


def test_cross_module_call_edge_resolution(tmp_path):
    """A hot root in one file reaches a readback in ANOTHER file
    through an import-qualified call edge; the helper alone (no root
    in sight) scans clean."""
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "rootmod.py").write_text(
        "from helper import readback\n"
        "class ServingEngine:\n"
        "    def step(self):\n"
        "        return readback(self.carry)\n")
    helper = proj / "helper.py"
    helper.write_text(
        "def readback(carry):\n"
        "    return float(carry['pos'][0])\n")
    assert analyze_paths([str(helper)]) == []
    got = [(Path(f.path).name, f.line, f.code)
           for f in analyze_paths([str(proj)])]
    assert got == [("helper.py", 2, "ASY301")]


def test_scan_cache_invalidates_on_call_edge_change(tmp_path):
    """Editing ONLY the edge-defining file must re-judge the OTHER
    file: the call-graph facts feed the cache key, so a cached scan
    after the edit matches --no-cache exactly."""
    from bigdl_tpu.analysis import scan

    proj = tmp_path / "proj"
    proj.mkdir()
    root = proj / "rootmod.py"
    root.write_text(
        "from helper import readback\n"
        "class ServingEngine:\n"
        "    def step(self):\n"
        "        return readback(self.carry)\n")
    (proj / "helper.py").write_text(
        "def readback(carry):\n"
        "    return float(carry['pos'][0])\n")
    cache = tmp_path / "cache.json"
    run1 = scan([str(proj)], cache_path=str(cache))
    assert [f.code for f in run1] == ["ASY301"]
    # drop the edge: helper is no longer reachable from any root
    root.write_text(
        "class ServingEngine:\n"
        "    def step(self):\n"
        "        return 0\n")
    fresh = scan([str(proj)])
    cached = scan([str(proj)], cache_path=str(cache))
    assert fresh == [] and cached == [], [f.format() for f in cached]


def test_cli_parallel_workers_resolve_call_graph_facts(tmp_path):
    """Fork workers split the root file and the readback file across
    slices — the finding survives only if the phase-1 fact exchange
    merges call edges and roots across workers."""
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "rootmod.py").write_text(
        "from helper import readback\n"
        "class ServingEngine:\n"
        "    def step(self):\n"
        "        return readback(self.carry)\n")
    (proj / "helper.py").write_text(
        "def readback(carry):\n"
        "    return float(carry['pos'][0])\n")
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.analysis", str(proj),
         "--no-baseline", "--select", "ASY301", "--json",
         "--jobs", "2", "--no-cache"],
        cwd=str(REPO), capture_output=True, text=True, timeout=180)
    assert proc.returncode == 1, proc.stderr
    report = json.loads(proc.stdout)
    assert [(Path(f["path"]).name, f["code"])
            for f in report["findings"]] == [("helper.py", "ASY301")]


# -- the ASY acceptance census over the REAL serving tree -------------------

_FENCE_SITE_RE = re.compile(r'\bfence(_wait)?\(\s*"')


def _fence_sites_in(tree: Path):
    """(file, regex match) for every declared fence call in a serving
    tree copy (the fence module itself excluded — it IS the idiom)."""
    out = []
    for f in sorted(tree.glob("*.py")):
        if f.name == "fences.py":
            continue
        for m in _FENCE_SITE_RE.finditer(f.read_text()):
            out.append((f, m))
    return out


def test_async_census_sites_enumerated():
    """The real serving plane's declared sync points exist where we
    think: one decode readback + one verify readback + the transfer
    readback + the draft completion fence.  The five prefill
    completion fences the PR 12 worksheet marked deletable are GONE
    (cashed in — prefill dispatches overlap the decode step and the
    step's decode/verify fence absorbs their completion; their phase
    timers went with them, docs/async_readiness.md)."""
    counts = {}
    for f, m in _fence_sites_in(SERVING_DIR):
        counts[f.name] = counts.get(f.name, 0) + 1
    assert counts == {"disagg.py": 1, "engine.py": 1,
                      "speculative.py": 2}, counts


def test_async_census_every_fence_site_individually_detected(tmp_path):
    """THE ASY acceptance census: strip each declared fence in the real
    serving tree back to its raw spelling (`fence(` -> `jax.device_get(`,
    `fence_wait(` -> `jax.block_until_ready(`) in turn — each mutation
    must yield exactly ONE ASY finding at the right file, and the
    unmutated copy scans ASY-clean, so the coverage is exact, not
    vacuous."""
    tree = _serving_tree(tmp_path)
    clean = analyze_paths([str(tmp_path)], select=ASY_CODES)
    assert clean == [], [f.format() for f in clean]
    by_file = {}
    for f, m in _fence_sites_in(tree):
        by_file.setdefault(f, []).append(m)
    assert sum(len(v) for v in by_file.values()) >= 4
    for fpath, matches in by_file.items():
        src = fpath.read_text()
        for m in matches:
            paren = src.index("(", m.start())
            repl = "jax.block_until_ready(" if m.group(1) \
                else "jax.device_get("
            mutated = src[:m.start()] + repl + src[paren + 1:]
            if "import jax" not in mutated:
                # the raw spelling must RESOLVE for the census to be a
                # fair counterfactual — a file whose only jax touch was
                # the fence idiom (disagg.py) never binds the name
                mutated = "import jax\n" + mutated
            fpath.write_text(mutated)
            found = analyze_paths([str(tmp_path)], select=ASY_CODES)
            want = "ASY302" if m.group(1) else "ASY301"
            assert [f.code for f in found] == [want], (
                f"stripping fence at {fpath.name}:{m.start()} must "
                f"yield exactly one {want}, got: "
                f"{[f.format() for f in found]}")
            assert found[0].path.endswith(fpath.name)
        fpath.write_text(src)


def test_async_census_deleting_a_fence_line_flags_the_timer(tmp_path):
    """Deleting a completion fence outright (not just un-routing it)
    surfaces as ASY305 on the now-lying timer read (the draft-chain
    fence — the remaining completion wait after the prefill fences
    were cashed in)."""
    tree = _serving_tree(tmp_path)
    spec = tree / "speculative.py"
    src = spec.read_text()
    line = '        fence_wait("draft", u)\n'
    assert line in src
    spec.write_text(src.replace(line, ""))
    found = analyze_paths([str(tmp_path)], select=ASY_CODES)
    assert [f.code for f in found] == ["ASY305"], (
        [f.format() for f in found])
    assert found[0].path.endswith("speculative.py")


# -- the sync-point inventory (--report sync-points) ------------------------

def test_sync_points_report_text_and_json(capsys, monkeypatch):
    monkeypatch.chdir(REPO)
    rc = main(["bigdl_tpu/serving", "--report", "sync-points"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "fence:decode" in out and "ServingEngine.step" in out
    assert "0 un-fenced finding(s)" in out

    rc = main(["bigdl_tpu/serving", "--report", "sync-points",
               "--format", "json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert rep["report"] == "sync-points"
    assert rep["summary"]["findings"] == 0
    assert rep["summary"]["declared"] == 4
    kinds = {e["kind"] for e in rep["entries"]}
    assert {"fence:decode", "fence:verify", "fence_wait:draft",
            "fence:transfer"} == kinds
    # every declared site carries its root chain back to a hot root
    for e in rep["entries"]:
        assert e["chain"], e
        assert e["chain"][0].rsplit(".", 2)[-2:] in (
            ["ServingEngine", "step"], ["Speculator", "step"],
            ["ChunkedAdmissionController", "pump"],
            ["ServingEngine", "_dispatch"]), e["chain"]


def test_sync_points_report_lists_unfenced_findings(tmp_path, capsys,
                                                    monkeypatch):
    """An un-fenced readback shows up IN the inventory (classification
    = the ASY code), not just in the failing scan."""
    monkeypatch.chdir(REPO)
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "mini.py").write_text(
        "class ServingEngine:\n"
        "    def step(self):\n"
        "        return float(self.carry['pos'][0])\n")
    rc = main([str(proj), "--report", "sync-points", "--format", "json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert rep["summary"]["findings"] == 1
    assert rep["entries"][0]["kind"] == "ASY301"

    # an unknown-site fence is the ASY302 violation, not a declared
    # site — it must appear exactly once, never double-counted as both
    (proj / "mini.py").write_text(
        "from bigdl_tpu.serving.fences import fence_wait\n"
        "class ServingEngine:\n"
        "    def step(self):\n"
        "        return fence_wait('warmup', self.out)\n")
    rc = main([str(proj), "--report", "sync-points", "--format", "json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert rep["summary"]["declared"] == 0
    assert [e["kind"] for e in rep["entries"]] == ["ASY302"]


# -- the MH4xx lockstep census over the REAL serving tree --------------------

def test_multihost_census_real_tree_clean_and_mutations_caught(tmp_path):
    """THE MH acceptance census: the unmutated serving tree scans
    MH-clean, and stripping each machine-encoded determinism
    discipline in turn yields exactly one finding at the right file —
    clock threading (an engine-clock read becomes a raw perf_counter),
    seed derivation (the request-keyed fold_in becomes a fresh
    PRNGKey), and the lockstep dispatch guard (a divergent branch
    around a dispatch)."""
    tree = _serving_tree(tmp_path)
    clean = analyze_paths([str(tmp_path)], select=MH_CODES)
    assert clean == [], [f.format() for f in clean]

    # 1. clock threading: ONE engine-clock read per file becomes a raw
    # wall-clock read -> exactly one MH403 at that file
    for fname, spelled in [("engine.py", "self._clock()"),
                           ("disagg.py", "self._clock()"),
                           ("health.py", "self._clock()")]:
        src = (tree / fname).read_text()
        assert spelled in src, f"{fname} lost its engine-clock reads?"
        (tree / fname).write_text(
            "import time\n" + src.replace(spelled,
                                          "time.perf_counter()", 1))
        found = analyze_paths([str(tmp_path)], select=MH_CODES)
        assert [f.code for f in found] == ["MH403"], (
            f"stripping clock threading in {fname} must yield exactly "
            f"one MH403, got: {[f.format() for f in found]}")
        assert found[0].path.endswith(fname)
        (tree / fname).write_text(src)

    # 2. seed derivation: the request-keyed lane (fold_in of
    # lane_key(engine seed)) becomes a fresh ambient PRNGKey ->
    # exactly one MH404 at engine.py
    eng = tree / "engine.py"
    src = eng.read_text()
    needle = "jax.random.fold_in(lane_key(self.seed), req.req_id)"
    assert needle in src, "_lane_key moved — update the census"
    eng.write_text(src.replace(needle, "jax.random.PRNGKey(0)", 1))
    found = analyze_paths([str(tmp_path)], select=MH_CODES)
    assert [f.code for f in found] == ["MH404"], \
        [f.format() for f in found]
    assert found[0].path.endswith("engine.py")
    eng.write_text(src)

    # 3. divergent-branch dispatch: rank-gating a compiled-step
    # dispatch -> exactly one MH401 at engine.py
    eng.write_text(src + (
        "\n\ndef _divergent_probe(eng, x):\n"
        "    import jax\n"
        "    if jax.process_index() == 0:\n"
        "        return eng._dispatch(\"decode\", eng._step_fn, x)\n"
        "    return x\n"))
    found = analyze_paths([str(tmp_path)], select=MH_CODES)
    assert [f.code for f in found] == ["MH401"], \
        [f.format() for f in found]
    assert found[0].path.endswith("engine.py")
    eng.write_text(src)


def test_clock_vocabulary_extracted_from_real_declaration():
    """MH403's vocabulary comes from serving/faults.py CLOCK_SITES by
    extraction (not the built-in fallback), and names exactly the two
    shipped raw-read units."""
    from bigdl_tpu.analysis.core import _parse_file, collect_file_facts

    text = (REPO / "bigdl_tpu" / "serving" / "faults.py").read_text()
    ctx, err = _parse_file(text, "bigdl_tpu/serving/faults.py")
    assert err is None
    facts = collect_file_facts(ctx)
    assert set(facts.get("clock_sites", [])) == {
        "faults.default_clock", "metrics.ServingMetrics.on_step"}
    assert facts.get("clock_modules") == ["bigdl_tpu.serving.faults"]


def test_clock_vocabulary_extraction_beats_fallback():
    """A project-local CLOCK_SITES declaration overrides the fallback:
    its site is exempt, a fallback site is not."""
    src = (  # analysis: no-embed — deliberate violations under test
        "import time\n"
        'CLOCK_SITES = frozenset({"mini.now"})\n'
        "def now():\n"
        "    return time.perf_counter()\n"
        "def default_clock():\n"
        "    return time.perf_counter()\n"
        "def _dispatch(site, fn):\n"
        "    return fn()\n"
    )
    got = [(f.line, f.code) for f in analyze_source(src, "mini.py")]
    assert got == [(6, "MH403")]


def test_divergence_taint_cross_module_reachability(tmp_path):
    """MH401 resolves the guarded collective THROUGH the import graph:
    the collective module is clean alone, the divergent caller fires
    only when both files are in the project."""
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "collmod.py").write_text(
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def shard_norm(g):\n"
        "    return lax.psum(jnp.sum(g * g), 'data')\n")
    (proj / "rootmod.py").write_text(
        "import jax\n"
        "from collmod import shard_norm\n"
        "def decide(g):\n"
        "    pid = jax.process_index()\n"
        "    if pid == 0:\n"
        "        return shard_norm(g)\n"
        "    return g\n")
    assert analyze_paths([str(proj / "collmod.py")]) == []
    # caller alone: the callee's collective is invisible — documented
    # degradation of single-file runs
    assert analyze_paths([str(proj / "rootmod.py")]) == []
    got = [(Path(f.path).name, f.line, f.code)
           for f in analyze_paths([str(proj)])]
    assert got == [("rootmod.py", 5, "MH401")]


def test_scan_cache_invalidates_on_collective_fact_change(tmp_path):
    """Editing ONLY the collective-defining file must re-judge the
    divergent caller: the lockstep facts feed the cache key, so a
    cached scan after the edit matches --no-cache exactly."""
    from bigdl_tpu.analysis import scan

    proj = tmp_path / "proj"
    proj.mkdir()
    coll = proj / "collmod.py"
    coll.write_text(
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def shard_norm(g):\n"
        "    return lax.psum(jnp.sum(g * g), 'data')\n")
    (proj / "rootmod.py").write_text(
        "import jax\n"
        "from collmod import shard_norm\n"
        "def decide(g):\n"
        "    pid = jax.process_index()\n"
        "    if pid == 0:\n"
        "        return shard_norm(g)\n"
        "    return g\n")
    cache = tmp_path / "cache.json"
    run1 = scan([str(proj)], cache_path=str(cache))
    assert [f.code for f in run1] == ["MH401"]
    # the helper stops being a collective: the branch is now pure host
    coll.write_text(
        "import jax.numpy as jnp\n"
        "def shard_norm(g):\n"
        "    return jnp.sum(g * g)\n")
    fresh = scan([str(proj)])
    cached = scan([str(proj)], cache_path=str(cache))
    assert fresh == [] and cached == [], [f.format() for f in cached]


def test_cli_parallel_workers_resolve_divergence_facts(tmp_path):
    """Fork workers split the collective module and the divergent
    caller across slices — the MH401 finding survives only if the
    phase-1 fact exchange merges collective_units and call edges
    across workers."""
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "collmod.py").write_text(
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def shard_norm(g):\n"
        "    return lax.psum(jnp.sum(g * g), 'data')\n")
    (proj / "rootmod.py").write_text(
        "import jax\n"
        "from collmod import shard_norm\n"
        "def decide(g):\n"
        "    pid = jax.process_index()\n"
        "    if pid == 0:\n"
        "        return shard_norm(g)\n"
        "    return g\n")
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.analysis", str(proj),
         "--no-baseline", "--select", "MH401", "--json",
         "--jobs", "2", "--no-cache"],
        cwd=str(REPO), capture_output=True, text=True, timeout=180)
    assert proc.returncode == 1, proc.stderr
    report = json.loads(proc.stdout)
    assert [(Path(f["path"]).name, f["code"])
            for f in report["findings"]] == [("rootmod.py", "MH401")]


# -- the lockstep inventory (--report lockstep) ------------------------------

def test_lockstep_report_text_and_json(capsys, monkeypatch):
    monkeypatch.chdir(REPO)
    rc = main(["bigdl_tpu/serving", "--report", "lockstep"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 MH finding(s)" in out
    assert "2 declared clock site(s)" in out

    rc = main(["bigdl_tpu/serving", "--report", "lockstep",
               "--format", "json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert rep["report"] == "lockstep"
    assert rep["summary"]["findings"] == 0
    assert rep["summary"]["clock_sites"] == 2
    # every routed _dispatch call site is an agreement point the pod
    # must execute in lockstep
    assert rep["summary"]["agreement"] >= 8
    kinds = {e["kind"] for e in rep["entries"]}
    assert "agreement:dispatch" in kinds
    assert "clock:time.perf_counter" in kinds
    # the disaggregated transfer channel's per-peer read is a recorded
    # divergence root
    assert "divergence:peer-read" in kinds


def test_lockstep_report_lists_mh_findings(tmp_path, capsys, monkeypatch):
    """An un-fixed lockstep violation shows up IN the worksheet
    (classification = the MH code), not just in the failing scan."""
    monkeypatch.chdir(REPO)
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "mini.py").write_text(
        "import jax\n"
        "from jax import lax\n"
        "def decide(g):\n"
        "    if jax.process_index() == 0:\n"
        "        return lax.psum(g, 'data')\n"
        "    return g\n")
    rc = main([str(proj), "--report", "lockstep", "--format", "json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert rep["summary"]["findings"] == 1
    mh = [e for e in rep["entries"] if e["kind"] == "MH401"]
    assert len(mh) == 1 and mh[0]["suggestion"]
