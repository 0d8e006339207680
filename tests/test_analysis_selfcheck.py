"""The repo gates itself on graftlint (fast lane, < 5 s, no jax import).

Tier-1 guarantee: ``python -m hpbandster_tpu.analysis hpbandster_tpu tests``
exits 0 on the committed tree, and exits non-zero the moment any rule's
known-bad fixture (or code like it) is introduced.
"""

import shutil
import time
from pathlib import Path

import pytest

from hpbandster_tpu.analysis import all_rules, format_report, run
from hpbandster_tpu.analysis.__main__ import main

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).parent / "analysis_fixtures"
SCAN = [str(REPO / "hpbandster_tpu"), str(REPO / "tests")]
OBS_TREE = REPO / "hpbandster_tpu" / "obs"

RULE_TO_BAD_FIXTURE = {
    "jit-host-sync": "jit_host_sync_bad.py",
    "prng-reuse": "prng_bad.py",
    "lock-coverage": "locks_bad.py",
    "swallowed-exception": "exceptions_bad.py",
    "pytest-marker": "test_markers_bad.py",
    "obs-emit-in-jit": "obs_emit_bad.py",
    "obs-reserved-fields": "obs_reserved_bad.py",
    "jit-in-loop": "jit_loop_bad.py",
    "jit-donation": "donation_bad.py",
    "lock-order": "lockorder_bad.py",
    "lock-blocking": "lockblock_bad.py",
    "trace-escape": "trace_escape_bad.py",
}


def test_rule_pack_is_registered():
    assert set(RULE_TO_BAD_FIXTURE) <= set(all_rules())


def test_repo_tree_is_clean():
    findings = run(SCAN)
    assert findings == [], "\n" + format_report(findings)


def test_obs_tree_is_scanned_and_clean():
    """The obs subsystem is inside the gate's scan paths (no new package
    may silently fall outside the walk) and graftlint-clean on its own.
    ISSUE 20 pins the SLO layer explicitly: slo.py and alerts.py must be
    in the walk, not just whatever the glob happens to pick up."""
    from hpbandster_tpu.analysis import collect_files

    scanned = set(collect_files(SCAN))
    obs_files = {str(p) for p in OBS_TREE.glob("*.py")}
    assert obs_files, "hpbandster_tpu/obs has no python files?"
    assert str(OBS_TREE / "slo.py") in obs_files
    assert str(OBS_TREE / "alerts.py") in obs_files
    assert obs_files <= scanned, sorted(obs_files - scanned)
    findings = run([str(OBS_TREE)])
    assert findings == [], "\n" + format_report(findings)


def test_serve_tree_is_scanned_and_clean():
    """Same coverage guarantee for the serving tier: every serve/ module
    is inside the gate's walk and clean under the full rule pack."""
    from hpbandster_tpu.analysis import collect_files

    serve_tree = REPO / "hpbandster_tpu" / "serve"
    scanned = set(collect_files(SCAN))
    serve_files = {str(p) for p in serve_tree.glob("*.py")}
    assert serve_files, "hpbandster_tpu/serve has no python files?"
    assert serve_files <= scanned, sorted(serve_files - scanned)
    findings = run([str(serve_tree)])
    assert findings == [], "\n" + format_report(findings)


def test_workloads_tree_is_scanned_and_clean():
    """ISSUE 17 coverage extension: the workloads tree (now carrying the
    vmapped-SGD ensemble and its jit sites) is inside the gate's walk and
    clean under the full rule pack — including jit-donation, which
    requires every new ``tracked_jit`` site to take an explicit
    ``donate_argnums`` stance."""
    from hpbandster_tpu.analysis import collect_files

    workloads_tree = REPO / "hpbandster_tpu" / "workloads"
    scanned = set(collect_files(SCAN))
    workloads_files = {str(p) for p in workloads_tree.glob("*.py")}
    assert str(workloads_tree / "ensemble.py") in workloads_files
    assert workloads_files <= scanned, sorted(workloads_files - scanned)
    findings = run([str(workloads_tree)])
    assert findings == [], "\n" + format_report(findings)


def test_cli_exits_zero_on_clean_tree(capsys):
    assert main(SCAN) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_exits_nonzero_when_bad_fixture_introduced(tmp_path, capsys):
    """Acceptance criterion: drop any known-bad fixture into a scanned tree
    and the gate must trip, attributed to the right rule."""
    for rule, fixture in RULE_TO_BAD_FIXTURE.items():
        tree = tmp_path / rule
        tree.mkdir()
        shutil.copy(FIXTURES / fixture, tree / fixture)
        assert main([str(tree)]) == 1, f"{fixture} did not trip the gate"
        out = capsys.readouterr().out
        assert f"[{rule}]" in out, f"{fixture} tripped the wrong rule:\n{out}"


def test_cli_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULE_TO_BAD_FIXTURE:
        assert rule in out


def test_cli_unknown_rule_is_usage_error(capsys):
    assert main(["--rules", "definitely-not-a-rule", str(FIXTURES)]) == 2


@pytest.fixture
def parses(monkeypatch):
    """``{path: times parsed}`` while the test runs: what the fast lane's
    5 s stood for, in counts. A scan's cost is its parses and its project
    build; a wall-clock bound on them read the load of a shared CPU."""
    from hpbandster_tpu.analysis.core import SourceModule

    counts = {}
    init = SourceModule.__init__

    def counting(self, path, text):
        counts[path] = counts.get(path, 0) + 1
        init(self, path, text)

    monkeypatch.setattr(SourceModule, "__init__", counting)
    return counts


def test_selfcheck_is_fast_lane_material(parses):
    """The gate must stay cheap enough to run on every PR: a scan of both
    trees after an earlier one parses no file and builds no project."""
    from hpbandster_tpu.analysis import graph

    run(SCAN)
    projects = list(graph._PROJECT_CACHE.values())
    parses.clear()
    assert run(SCAN) == []
    assert parses == {}
    assert list(graph._PROJECT_CACHE.values()) == projects


def test_interprocedural_scan_is_cold_fast(parses):
    """Guard for the interprocedural pass specifically: a genuinely COLD
    full scan (module + project caches dropped) of both trees, all rules
    including the call-graph ones, parses each source file once and builds
    one project for all of them."""
    from hpbandster_tpu.analysis import graph

    graph.clear_caches()
    findings = run(SCAN)
    assert findings == []
    assert parses and set(parses.values()) == {1}
    assert set(parses) == set(graph._MODULE_CACHE)
    (project,) = graph._PROJECT_CACHE.values()
    assert set(project.modules) == set(parses)


@pytest.mark.slow
def test_changed_mode_single_file_is_fast():
    """The pre-commit latency bar: a cold CLI invocation scanning one
    changed source file against the whole-program graph in under 1.5 s
    (interpreter startup included)."""
    import subprocess
    import sys

    target = str(REPO / "hpbandster_tpu" / "serve" / "continuous.py")
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "hpbandster_tpu.analysis", "--changed", target],
        cwd=str(REPO),
        capture_output=True,
        text=True,
    )
    elapsed = time.perf_counter() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert elapsed < 1.5, f"--changed scan took {elapsed:.2f}s"


class TestCliFormats:
    def test_json_format(self, tmp_path, capsys):
        bad = tmp_path / "lockblock_bad.py"
        shutil.copy(FIXTURES / "lockblock_bad.py", bad)
        assert main(["--format=json", str(bad)]) == 1
        rows = __import__("json").loads(capsys.readouterr().out)
        assert any(r["rule"] == "lock-blocking" for r in rows)
        # two-location findings carry the sink as a related location
        related = [r for r in rows if "related" in r]
        assert related, "no two-location finding in lockblock_bad.py?"
        assert related[0]["related"]["line"] > 0

    def test_sarif_format(self, tmp_path, capsys):
        bad = tmp_path / "trace_escape_bad.py"
        shutil.copy(FIXTURES / "trace_escape_bad.py", bad)
        assert main(["--format=sarif", str(bad)]) == 1
        sarif = __import__("json").loads(capsys.readouterr().out)
        assert sarif["version"] == "2.1.0"
        results = sarif["runs"][0]["results"]
        assert any(r["ruleId"] == "trace-escape" for r in results)
        assert any("relatedLocations" in r for r in results)

    def test_sarif_clean_tree_is_valid_and_empty(self, tmp_path, capsys):
        mod = tmp_path / "ok.py"
        mod.write_text("def f():\n    return 1\n")
        assert main(["--format=sarif", str(mod)]) == 0
        sarif = __import__("json").loads(capsys.readouterr().out)
        assert sarif["runs"][0]["results"] == []


class TestBaselineRatchet:
    def test_baseline_tolerates_frozen_then_gates_new(self, tmp_path, capsys):
        tree = tmp_path / "tree"
        tree.mkdir()
        shutil.copy(FIXTURES / "lockblock_bad.py", tree / "legacy.py")
        baseline = tmp_path / "baseline.json"

        # freeze the legacy findings
        assert main([str(tree), "--write-baseline", str(baseline)]) == 0
        capsys.readouterr()

        # frozen tree passes under the baseline
        assert main([str(tree), "--baseline", str(baseline)]) == 0
        capsys.readouterr()

        # a NEW finding still gates
        shutil.copy(FIXTURES / "lockorder_bad.py", tree / "fresh.py")
        assert main([str(tree), "--baseline", str(baseline)]) == 1
        out = capsys.readouterr().out
        assert "[lock-order]" in out
        # ...and the frozen legacy findings stay muted
        assert "legacy.py" not in out

    def test_unreadable_baseline_is_usage_error(self, tmp_path, capsys):
        assert main([str(tmp_path), "--baseline", str(tmp_path / "nope.json")]) == 2


class TestChangedMode:
    def test_changed_clean_file_exits_zero(self, capsys):
        target = str(REPO / "hpbandster_tpu" / "analysis" / "core.py")
        assert main(["--changed", target]) == 0

    def test_changed_missing_path_is_usage_error(self, capsys):
        assert main(["--changed", "no/such/file.py"]) == 2

    def test_changed_still_sees_cross_module_callees(self, tmp_path, capsys):
        """The point of --changed: the reported file calls a helper whose
        sink lives in an UNCHANGED sibling — the finding must still
        surface, anchored in the changed file."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "helpers.py").write_text(
            "def to_host(v):\n    return float(v)\n"
        )
        (pkg / "entry.py").write_text(
            "import jax\n"
            "from pkg.helpers import to_host\n"
            "\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return to_host(x)\n"
        )
        findings = run(
            [str(pkg / "entry.py")], graph_roots=[str(pkg)], rules=["trace-escape"]
        )
        assert len(findings) == 1
        assert findings[0].path == str(pkg / "entry.py")
        assert findings[0].related_path == str(pkg / "helpers.py")
