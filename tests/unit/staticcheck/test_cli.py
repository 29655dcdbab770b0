"""dstpu-lint CLI: exit codes, JSON format, baseline update, rule selection."""

import json
import os
import textwrap

import pytest

from deepspeed_tpu.tools.staticcheck.cli import main

DIRTY = textwrap.dedent("""
    def f():
        try:
            g()
        except Exception:
            pass
    """)

CLEAN = "def f():\n    return 1\n"


@pytest.fixture
def tree(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "dirty.py").write_text(DIRTY)
    (pkg / "clean.py").write_text(CLEAN)
    return tmp_path


def run_cli(args, capsys):
    rc = main(args)
    out = capsys.readouterr().out
    return rc, out


def test_exit_one_on_findings_and_zero_when_clean(tree, capsys):
    rc, out = run_cli([str(tree / "pkg" / "dirty.py"), "--root", str(tree)], capsys)
    assert rc == 1 and "silent-except" in out
    rc, out = run_cli([str(tree / "pkg" / "clean.py"), "--root", str(tree)], capsys)
    assert rc == 0


def test_json_format_is_machine_readable(tree, capsys):
    rc, out = run_cli([str(tree / "pkg"), "--root", str(tree), "--format", "json"], capsys)
    assert rc == 1
    data = json.loads(out)
    assert data["summary"]["findings"] == 1
    (finding, ) = data["findings"]
    assert finding["rule"] == "silent-except"
    assert finding["path"] == "pkg/dirty.py"
    assert finding["fingerprint"]


def test_update_baseline_then_clean_then_new_finding(tree, capsys):
    pkg = str(tree / "pkg")
    rc, out = run_cli([pkg, "--root", str(tree), "--update-baseline"], capsys)
    assert rc == 0
    assert os.path.exists(str(tree / ".dslint-baseline.json"))
    rc, _ = run_cli([pkg, "--root", str(tree)], capsys)
    assert rc == 0  # grandfathered
    (tree / "pkg" / "more.py").write_text(DIRTY.replace("def f", "def q"))
    rc, out = run_cli([pkg, "--root", str(tree)], capsys)
    assert rc == 1 and "more.py" in out  # new finding not masked


def test_no_baseline_flag_reports_everything(tree, capsys):
    pkg = str(tree / "pkg")
    run_cli([pkg, "--root", str(tree), "--update-baseline"], capsys)
    rc, out = run_cli([pkg, "--root", str(tree), "--no-baseline"], capsys)
    assert rc == 1


def test_select_and_disable(tree, capsys):
    pkg = str(tree / "pkg")
    rc, _ = run_cli([pkg, "--root", str(tree), "--disable", "silent-except"], capsys)
    assert rc == 0
    rc, _ = run_cli([pkg, "--root", str(tree), "--select", "silent-except"], capsys)
    assert rc == 1
    assert main([pkg, "--root", str(tree), "--select", "no-such-rule"]) == 2


def test_update_baseline_refuses_rule_restriction(tree, capsys):
    rc = main([str(tree / "pkg"), "--root", str(tree), "--update-baseline",
               "--select", "silent-except"])
    assert rc == 2
    rc = main([str(tree / "pkg"), "--root", str(tree), "--update-baseline",
               "--disable", "silent-except"])
    assert rc == 2


def test_update_baseline_on_subset_preserves_other_files(tree, capsys):
    pkg = str(tree / "pkg")
    (tree / "pkg" / "other.py").write_text(DIRTY.replace("def f", "def other_f"))
    run_cli([pkg, "--root", str(tree), "--update-baseline"], capsys)
    # re-baselining ONLY dirty.py must not delete other.py's entry
    rc, out = run_cli([str(tree / "pkg" / "dirty.py"), "--root", str(tree),
                       "--update-baseline"], capsys)
    assert rc == 0 and "preserved" in out
    rc, _ = run_cli([pkg, "--root", str(tree)], capsys)
    assert rc == 0  # both files still grandfathered


def test_subset_lint_sees_whole_package_schema(capsys):
    """Linting ONE file of the real package must still know the ConfigModel
    fields + DECLARED_EXTRA_KEYS declared elsewhere (runtime/config.py)."""
    import deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler as cs
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    rc, out = run_cli([cs.__file__, "--root", root], capsys)
    assert rc == 0, out


def test_missing_path_is_usage_error(tree):
    assert main([str(tree / "nope"), "--root", str(tree)]) == 2


def test_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("host-sync-in-hot-path", "traced-control-flow", "donation-after-use",
                 "nondeterministic-rng", "silent-except", "float64-in-compute",
                 "undeclared-config-key", "bad-suppression", "unused-suppression",
                 "unknown-mesh-axis", "sharding-dropped-at-boundary",
                 "spec-rank-mismatch", "recompile-risk",
                 "donation-sharding-mismatch", "cross-thread-mutation",
                 "atomic-publish", "handler-holds-engine",
                 "blocking-under-lock", "lock-order"):
        assert rule in out


# ---------------------------------------------------------------- SARIF
def test_sarif_format_round_trips(tree, capsys):
    """SARIF output parses, carries every active finding with its location
    and fingerprint, and maps severities to SARIF levels — what a CI
    annotator needs to render findings inline."""
    rc, out = run_cli([str(tree / "pkg"), "--root", str(tree),
                       "--format", "sarif"], capsys)
    assert rc == 1
    sarif = json.loads(out)
    assert sarif["version"] == "2.1.0"
    (run, ) = sarif["runs"]
    assert run["tool"]["driver"]["name"] == "dslint"
    (res, ) = run["results"]
    assert res["ruleId"] == "silent-except"
    assert res["level"] == "error"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "pkg/dirty.py"
    assert loc["region"]["startLine"] == 5
    assert res["partialFingerprints"]["dslintFingerprint/v1"]
    # the rule catalog rides along and the result indexes into it
    rules = run["tool"]["driver"]["rules"]
    assert rules[res["ruleIndex"]]["id"] == "silent-except"
    # compare against the JSON reporter: same findings, same fingerprints
    rc, jout = run_cli([str(tree / "pkg"), "--root", str(tree),
                        "--format", "json"], capsys)
    jdata = json.loads(jout)
    assert [r["partialFingerprints"]["dslintFingerprint/v1"]
            for r in run["results"]] == \
        [f["fingerprint"] for f in jdata["findings"]]


def test_sarif_clean_tree_has_empty_results(tree, capsys):
    rc, out = run_cli([str(tree / "pkg" / "clean.py"), "--root", str(tree),
                       "--format", "sarif"], capsys)
    assert rc == 0
    assert json.loads(out)["runs"][0]["results"] == []


# -------------------------------------------------------------- --changed
def _git(tree, *args):
    import subprocess
    subprocess.run(["git", *args], cwd=str(tree), check=True,
                   capture_output=True, timeout=60,
                   env={**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                        "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"})


def test_changed_mode_lints_only_files_changed_vs_base(tree, capsys):
    _git(tree, "init", "-q")
    _git(tree, "add", "-A")
    _git(tree, "commit", "-qm", "seed")
    # clean working tree: nothing to lint, exit 0 even though dirty.py has a
    # (committed) finding
    rc, out = run_cli(["--root", str(tree), "--changed"], capsys)
    assert rc == 0 and "no python files changed" in out
    # touch ONLY the clean file: still exits 0 (dirty.py is out of scope)
    (tree / "pkg" / "clean.py").write_text(CLEAN + "\n# edited\n")
    rc, out = run_cli(["--root", str(tree), "--changed"], capsys)
    assert rc == 0 and "1 files" in out
    # a new (untracked) dirty file is in scope
    (tree / "pkg" / "fresh.py").write_text(DIRTY.replace("def f", "def fresh"))
    rc, out = run_cli(["--root", str(tree), "--changed"], capsys)
    assert rc == 1 and "fresh.py" in out and "dirty.py" not in out
    # an explicit git base works too: vs HEAD~0 (== HEAD) same result
    rc, out = run_cli(["--root", str(tree), "--changed", "HEAD"], capsys)
    assert rc == 1 and "fresh.py" in out


def test_changed_mode_refuses_explicit_paths_and_bad_base(tree, capsys):
    assert main([str(tree / "pkg"), "--root", str(tree), "--changed"]) == 2
    _git(tree, "init", "-q")
    _git(tree, "add", "-A")
    _git(tree, "commit", "-qm", "seed")
    assert main(["--root", str(tree), "--changed", "no-such-ref"]) == 2


# ------------------------------------------------------- mesh manifest CLI
def test_update_mesh_manifest_and_refusals(tmp_path, capsys):
    pkg = tmp_path / "deepspeed_tpu"
    pkg.mkdir()
    (pkg / "mesh.py").write_text(textwrap.dedent("""
        from jax.sharding import Mesh
        DATA_AXIS = "data"

        def build(devs):
            return Mesh(devs, axis_names=("data", "model"))
        """))
    rc, out = run_cli(["--root", str(tmp_path), "--update-mesh-manifest"], capsys)
    assert rc == 0 and "2 axis name(s)" in out
    data = json.loads((tmp_path / ".dslint-mesh-manifest.json").read_text())
    assert data == {"version": 1, "axes": ["data", "model"]}
    # same hardening as the other two manifests: no partial-view re-pins
    assert main(["--root", str(tmp_path), "--update-mesh-manifest",
                 "--select", "unknown-mesh-axis"]) == 2
    assert main(["--root", str(tmp_path), "--update-mesh-manifest",
                 "--disable", "silent-except"]) == 2
    # unparseable package refuses the update
    (pkg / "broken.py").write_text("def broken(:\n")
    assert main(["--root", str(tmp_path), "--update-mesh-manifest"]) == 2


def test_lint_against_regenerated_mesh_manifest_is_clean(tmp_path, capsys):
    pkg = tmp_path / "deepspeed_tpu"
    pkg.mkdir()
    (pkg / "mesh.py").write_text(textwrap.dedent("""
        from jax.sharding import Mesh, PartitionSpec
        DATA_AXIS = "data"

        SPEC = PartitionSpec(DATA_AXIS)

        def build(devs):
            return Mesh(devs, axis_names=("data", ))
        """))
    run_cli(["--root", str(tmp_path), "--update-mesh-manifest"], capsys)
    run_cli(["--root", str(tmp_path), "--update-api-surface"], capsys)
    rc, out = run_cli([str(pkg), "--root", str(tmp_path)], capsys)
    assert rc == 0, out
    # now introduce the typo class: a spec axis no mesh declares
    (pkg / "user.py").write_text(textwrap.dedent("""
        from jax.sharding import PartitionSpec
        SPEC = PartitionSpec("dataa")
        """))
    rc, out = run_cli([str(pkg), "--root", str(tmp_path)], capsys)
    assert rc == 1 and "unknown-mesh-axis" in out and "'dataa'" in out


def test_relative_path_subset_lint_is_not_shadowed_by_context(tmp_path, capsys,
                                                              monkeypatch):
    """A linted file given as a RELATIVE path must not re-enter as a
    whole-package context duplicate: the duplicate's parse tree would shadow
    the linted module's per-relpath facts (mesh model spec sites, jit roots)
    and every id()-keyed node lookup on them would silently stop matching —
    spec-rank-mismatch missed real findings exactly this way."""
    pkg = tmp_path / "deepspeed_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        def build(mesh):
            spec = PartitionSpec("data", None, None)
            x = jnp.zeros((4, 8))
            return jax.device_put(x, NamedSharding(mesh, spec))

        def mk(devs):
            return Mesh(devs, axis_names=("data", ))
        """))
    run_cli(["--root", str(tmp_path), "--update-mesh-manifest"], capsys)
    run_cli(["--root", str(tmp_path), "--update-api-surface"], capsys)
    monkeypatch.chdir(tmp_path)
    rc, out = run_cli(["deepspeed_tpu/bad.py", "--root", str(tmp_path)], capsys)
    assert rc == 1 and "spec-rank-mismatch" in out, out
    # and identical to the absolute-path run
    rc_abs, out_abs = run_cli([str(pkg / "bad.py"), "--root", str(tmp_path)],
                              capsys)
    assert rc_abs == 1 and "spec-rank-mismatch" in out_abs


def test_changed_mode_monorepo_subroot_and_scan_root_scoping(tmp_path, capsys):
    """Two --changed contracts at once: `git diff --name-only` prints paths
    relative to the git TOPLEVEL (not --root), so a package living in a
    monorepo subdir must still see its committed changes; and changed files
    OUTSIDE the default scan roots (bench/scripts) stay out of the set —
    the full `make lint` never lints them, so lint-changed must not fail on
    findings the full run would never report."""
    root = tmp_path / "sub"
    pkg = root / "deepspeed_tpu"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(CLEAN)
    (root / "bench.py").write_text(CLEAN)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    # dirty BOTH vs HEAD: only the package file may enter the lint set
    (pkg / "mod.py").write_text(DIRTY)
    (root / "bench.py").write_text(DIRTY.replace("def f", "def bench"))
    rc, out = run_cli(["--root", str(root), "--changed", "HEAD"], capsys)
    assert rc == 1, out
    assert "mod.py" in out and "silent-except" in out
    assert "bench.py" not in out


def test_changed_mode_diffs_against_merge_base(tmp_path, capsys):
    """BASE=origin/main on a branch that is BEHIND upstream: files changed
    only upstream must not enter the changed set — the lane lints what the
    developer touched, not upstream drift."""
    pkg = tmp_path / "deepspeed_tpu"
    pkg.mkdir()
    (pkg / "mine.py").write_text(CLEAN)
    (pkg / "upstream.py").write_text(CLEAN)
    _git(tmp_path, "init", "-q", "-b", "main")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    _git(tmp_path, "checkout", "-q", "-b", "feature")
    # upstream moves on without us (a finding lands in upstream.py on main)
    _git(tmp_path, "checkout", "-q", "main")
    (pkg / "upstream.py").write_text(DIRTY)
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "upstream drift")
    _git(tmp_path, "checkout", "-q", "feature")
    # the developer's own change is clean
    (pkg / "mine.py").write_text(CLEAN + "\n# edited\n")
    run_cli(["--root", str(tmp_path), "--update-api-surface"], capsys)
    rc, out = run_cli(["--root", str(tmp_path), "--changed", "main"], capsys)
    assert rc == 0, out
    assert "1 files" in out and "upstream.py" not in out


def test_changed_mode_refuses_update_modes(tree, capsys):
    for flag in ("--update-baseline", "--update-api-surface",
                 "--update-mesh-manifest"):
        assert main(["--root", str(tree), "--changed", flag]) == 2


def test_changed_mode_empty_set_emits_valid_json_and_sarif(tree, capsys):
    """A CI consumer piping --format json/sarif must get a valid EMPTY
    document on a no-change run, not a prose line (or a traceback)."""
    _git(tree, "init", "-q")
    _git(tree, "add", "-A")
    _git(tree, "commit", "-qm", "seed")
    rc, out = run_cli(["--root", str(tree), "--changed", "--format", "json"],
                      capsys)
    assert rc == 0
    data = json.loads(out)
    assert data["findings"] == [] and data["summary"]["files_checked"] == 0
    rc, out = run_cli(["--root", str(tree), "--changed", "--format", "sarif"],
                      capsys)
    assert rc == 0
    sarif = json.loads(out)
    assert sarif["version"] == "2.1.0"
    assert sarif["runs"][0]["results"] == []


def test_changed_mode_surfaces_ls_files_failure(tree, capsys, monkeypatch):
    """A failed `git ls-files` (stale index.lock, corrupt index) must be a
    usage error, not an empty untracked set — new files silently dropping
    out of the lint set is the false-green class --changed hardens against."""
    import subprocess as sp
    _git(tree, "init", "-q")
    _git(tree, "add", "-A")
    _git(tree, "commit", "-qm", "seed")
    real_run = sp.run

    def failing_ls_files(cmd, **kwargs):
        if "ls-files" in cmd:
            return sp.CompletedProcess(cmd, 128, stdout="",
                                       stderr="fatal: index file corrupt")
        return real_run(cmd, **kwargs)

    monkeypatch.setattr(sp, "run", failing_ls_files)
    rc = main(["--root", str(tree), "--changed"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "ls-files" in err and "index file corrupt" in err


# ------------------------------------------- --changed catches thread rules
THREADED_RACE = textwrap.dedent("""
    import threading


    class Writer:
        def __init__(self):
            self._err = None
            self._t = threading.Thread(target=self._worker)

        def _worker(self):
            self._err = ValueError("boom")

        def take(self):
            exc, self._err = self._err, None
            return exc
    """)


def test_changed_mode_fails_prepush_on_a_thread_rule_finding(tmp_path, capsys):
    """ISSUE 18 CI contract: a concurrency finding introduced in a TOUCHED
    file must fail the `--changed` pre-push lane — the thread rules ride the
    same changed-file scoping as every other rule."""
    pkg = tmp_path / "deepspeed_tpu"
    pkg.mkdir()
    (pkg / "worker.py").write_text(CLEAN)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    rc, out = run_cli(["--root", str(tmp_path), "--changed"], capsys)
    assert rc == 0 and "no python files changed" in out
    # the touched file now carries the AsyncCheckpointEngine-class race
    (pkg / "worker.py").write_text(THREADED_RACE)
    rc, out = run_cli(["--root", str(tmp_path), "--changed"], capsys)
    assert rc == 1
    assert "cross-thread-mutation" in out and "worker.py" in out


# ----------------------------------------------------------------- --jobs
def test_jobs_parallel_results_match_sequential(tree, capsys):
    (tree / "pkg" / "race.py").write_text(THREADED_RACE)
    rc1, out1 = run_cli([str(tree / "pkg"), "--root", str(tree),
                         "--format", "json"], capsys)
    rc2, out2 = run_cli([str(tree / "pkg"), "--root", str(tree),
                         "--format", "json", "--jobs", "2"], capsys)
    assert rc1 == rc2 == 1
    d1, d2 = json.loads(out1), json.loads(out2)
    for d in (d1, d2):
        d["summary"].pop("seconds")
    assert d1 == d2
    assert {f["rule"] for f in d1["findings"]} == {"silent-except",
                                                   "cross-thread-mutation"}


def test_jobs_zero_means_cpu_count_and_negative_is_usage_error(tree, capsys):
    rc, _ = run_cli([str(tree / "pkg" / "clean.py"), "--root", str(tree),
                     "--jobs", "0"], capsys)
    assert rc == 0
    assert main([str(tree / "pkg"), "--root", str(tree), "--jobs", "-1"]) == 2


# ----------------------------------------------------- --list-suppressions
SUPPRESSED = textwrap.dedent("""
    def f():
        try:
            g()
        except Exception:  # dslint: disable=silent-except  # teardown guard
            pass
    """)

STALE_SUP = "# dslint: disable-file=silent-except  # nothing to silence\nx = 1\n"

REASONLESS = textwrap.dedent("""
    def f():
        try:
            g()
        except Exception:  # dslint: disable=silent-except
            pass
    """)


def test_list_suppressions_reports_reasons_stale_and_reasonless(tmp_path, capsys):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "good.py").write_text(SUPPRESSED)
    (pkg / "stale.py").write_text(STALE_SUP)
    (pkg / "bad.py").write_text(REASONLESS)
    rc, out = run_cli([str(pkg), "--root", str(tmp_path),
                       "--list-suppressions"], capsys)
    assert rc == 1  # stale + reasonless entries need attention
    assert "3 suppression(s)" not in out  # reasonless ones are inert, not counted
    assert "2 suppression(s)" in out and "1 stale" in out
    assert "teardown guard" in out
    assert "pkg/stale.py:1 [STALE]" in out
    assert "pkg/bad.py:5 [NO REASON]" in out


def test_list_suppressions_clean_exits_zero(tmp_path, capsys):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "good.py").write_text(SUPPRESSED)
    rc, out = run_cli([str(pkg), "--root", str(tmp_path),
                       "--list-suppressions"], capsys)
    assert rc == 0
    assert "0 stale, 0 without a reason" in out
    assert "silent-except (1)" in out


def test_list_suppressions_refuses_update_modes(tree):
    for flag in ("--update-baseline", "--update-api-surface",
                 "--update-mesh-manifest"):
        assert main(["--root", str(tree), "--list-suppressions", flag]) == 2
