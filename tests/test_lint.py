"""Tier-1 gate for the dl4jlint static-analysis suite.

Covers, per the PR-9 acceptance criteria:

- every rule has a true-positive fixture (the violation is found) and a
  clean-negative fixture (no findings) under ``tests/lint_fixtures/``;
- suppression comments silence findings (line + next-line + file);
- the ratcheting baseline: new findings fail, ``--update-baseline``
  bootstraps, refuses to grow, and shrinks when debt is paid;
- the full-repo run exits 0 against the committed baseline, without
  importing jax, inside the time budget;
- a synthetic violation introduced in a fixture COPY of a real repo
  file turns the exit code to 1.

The linter is stdlib-only and loaded as a package from the repo root
(``scripts`` is importable); everything here runs in-process except the
no-jax check, which needs a subprocess with a poisoned ``jax`` module.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from scripts.dl4jlint import baseline as baseline_mod  # noqa: E402
from scripts.dl4jlint import cli  # noqa: E402
from scripts.dl4jlint.rules import ALL_RULES, get_rules  # noqa: E402


def lint(files, rules=()):
    """Findings (post-suppression) for explicit fixture files."""
    paths = [os.path.join(FIXTURES, f) for f in files]
    return cli.run(paths, rules).findings


def fixture_pair(rule, bad, ok):
    bad_findings = lint([bad], (rule,))
    ok_findings = lint([ok], (rule,))
    assert bad_findings, f"{rule}: no findings in {bad}"
    assert all(f.rule == rule for f in bad_findings)
    assert ok_findings == [], (
        f"{rule}: false positives in {ok}: "
        + "; ".join(f.format() for f in ok_findings))
    return bad_findings


# ------------------------------------------------------------------- rules
def test_host_sync_rule():
    found = fixture_pair("host-sync-in-hot-path",
                         "host_sync_bad.py", "host_sync_ok.py")
    lines = {f.line for f in found}
    # .item() in the decorated jit, float() in the wrapped jit, the
    # per-step np.asarray + block_until_ready in the hot loop, and the
    # per-tensor readback in a loop driving a DECORATED jit helper (the
    # StatsListener sync-storm shape — decorated names are jitted
    # symbols too)
    assert len(lines) >= 5
    assert any("item" in f.message for f in found)
    assert any("block_until_ready" in f.message for f in found)
    assert any(f.symbol == "fit_loop" for f in found)
    assert any(f.symbol == "per_tensor_stats" for f in found)


def test_recompile_rule():
    found = fixture_pair("recompile-hazard",
                         "recompile_bad.py", "recompile_ok.py")
    msgs = " | ".join(f.message for f in found)
    assert "invoked immediately" in msgs
    assert "inside a loop" in msgs
    assert "static_argnums" in msgs


def test_lock_discipline_rule():
    found = fixture_pair("lock-discipline", "lock_bad.py", "lock_ok.py")
    symbols = {f.symbol for f in found}
    assert "Registry.lookup._active" in symbols     # unlocked dict read
    assert "Registry.evict._active" in symbols      # unlocked .pop()
    assert "Registry.size._count" in symbols        # unlocked scalar read


def test_rng_reuse_rule():
    found = fixture_pair("rng-key-reuse", "rng_bad.py", "rng_ok.py")
    symbols = {f.symbol for f in found}
    assert "double_draw" in symbols
    assert "loop_carried" in symbols                # caught on 2nd pass


def test_dtype_widening_rule():
    found = fixture_pair("implicit-dtype-widening",
                         "dtype_widening_bad.py", "dtype_widening_ok.py")
    msgs = " | ".join(f.message for f in found)
    assert "inside a jit-traced function" in msgs
    assert "host-numpy np.mean()" in msgs
    assert "dtype=float64" in msgs
    symbols = {f.symbol for f in found}
    assert "decorated_step" in symbols      # astype/dtype kw/np.mean
    assert "wrapped" in symbols             # np.float64() via jax.jit(wrapped)
    assert "build_reference" in symbols     # corpus-wide jnp dtype check


def test_thread_hygiene_rule():
    found = fixture_pair("thread-hygiene", "thread_bad.py", "thread_ok.py")
    msgs = " | ".join(f.message for f in found)
    assert "non-daemon thread is never joined" in msgs
    assert "daemon thread bound to self._thread" in msgs


def test_metrics_docs_rule():
    found = fixture_pair("metrics-docs",
                         "metrics_docs_bad.py", "metrics_docs_ok.py")
    assert any("help text" in f.message for f in found)
    assert all(f.symbol == "dl4j_fixture_only_total" for f in found)


def test_metrics_docs_help_drift_rule():
    """One dl4j_* family registered in two modules with diverging help
    text is flagged (federated HELP lines need one agreed string);
    whitespace-only rewraps inside one module are not drift."""
    bad = lint(["metrics_docs_drift_bad.py", "metrics_docs_drift_bad2.py"],
               ("metrics-docs",))
    drift = [f for f in bad if "diverges" in f.message]
    assert drift, "no drift finding for diverging help across modules"
    assert all(f.symbol == "dl4j_fixture_drift_total" for f in drift)
    # each drift-bad file alone has ONE help string -> no drift finding
    solo = lint(["metrics_docs_drift_bad.py"], ("metrics-docs",))
    assert not any("diverges" in f.message for f in solo)
    ok = lint(["metrics_docs_drift_ok.py"], ("metrics-docs",))
    assert not any("diverges" in f.message for f in ok), (
        "whitespace rewrap flagged as drift: "
        + "; ".join(f.format() for f in ok))


def test_rule_registry_complete():
    names = {r.name for r in ALL_RULES}
    assert names == {"host-sync-in-hot-path", "recompile-hazard",
                     "lock-discipline", "rng-key-reuse", "thread-hygiene",
                     "implicit-dtype-widening", "metrics-docs"}
    with pytest.raises(KeyError):
        get_rules(["no-such-rule"])


# ------------------------------------------------------------ suppressions
def test_suppressions(tmp_path):
    src = (tmp_path / "s.py")
    src.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    a = x.item()  # dl4jlint: disable=host-sync-in-hot-path -- why\n"
        "    # dl4jlint: disable-next-line=host-sync-in-hot-path -- why\n"
        "    b = x.item()\n"
        "    return a + b\n")
    res = cli.run([str(src)], ("host-sync-in-hot-path",))
    assert res.findings == [] and res.suppressed == 2
    src.write_text(
        "# dl4jlint: disable-file=all -- fixture\n"
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item()\n")
    res = cli.run([str(src)], ("host-sync-in-hot-path",))
    assert res.findings == [] and res.suppressed == 1


# ---------------------------------------------------------------- baseline
def _violation(n=1):
    body = "import jax\n"
    for i in range(n):
        body += f"def use{i}(x):\n    return jax.jit(lambda a: a)(x)\n"
    return body


def test_baseline_ratchet(tmp_path):
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    mod = corpus / "m.py"
    base = tmp_path / "baseline.json"
    args = [str(corpus), "--baseline", str(base),
            "--rules", "recompile-hazard"]

    mod.write_text(_violation(1))
    assert cli.main(args) == 1                      # no baseline yet: new
    assert cli.main(args + ["--update-baseline"]) == 0   # bootstrap
    assert cli.main(args) == 0                      # debt accepted

    mod.write_text(_violation(2))
    assert cli.main(args) == 1                      # NEW finding fails
    # the ratchet refuses to absorb growth
    assert cli.main(args + ["--update-baseline"]) == 1
    doc = json.loads(base.read_text())
    assert sum(e["count"] for e in doc["entries"]) == 1

    mod.write_text("X = 1\n")                       # debt paid off
    assert cli.main(args) == 0                      # stale entries pass...
    assert cli.main(args + ["--update-baseline"]) == 0
    doc = json.loads(base.read_text())
    assert doc["entries"] == []                     # ...and ratchet DOWN


def test_baseline_keys_survive_line_drift(tmp_path):
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    mod = corpus / "m.py"
    base = tmp_path / "baseline.json"
    args = [str(corpus), "--baseline", str(base),
            "--rules", "recompile-hazard"]
    mod.write_text(_violation(1))
    assert cli.main(args + ["--update-baseline"]) == 0
    # unrelated edits above the finding shift its line, not its key
    mod.write_text("# comment\n# comment\n\n" + _violation(1))
    assert cli.main(args) == 0


def test_baseline_why_preserved(tmp_path):
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    (corpus / "m.py").write_text(_violation(1))
    res = cli.run([str(corpus)], ("recompile-hazard",))
    doc = baseline_mod.update(res.findings, None)
    doc["entries"][0]["why"] = "cold path: fixture"
    doc2 = baseline_mod.update(res.findings, doc)
    assert doc2["entries"][0]["why"] == "cold path: fixture"


def test_committed_baseline_has_justifications():
    """Every accepted finding in the committed baseline carries a why —
    the satellite-task contract: no silent debt."""
    path = os.path.join(REPO, "scripts", "dl4jlint", "baseline.json")
    doc = baseline_mod.load(path)
    missing = [e for e in doc["entries"] if not e.get("why")]
    assert missing == [], f"baseline entries without why: {missing}"


# ------------------------------------------------------------ repo contract
def test_full_repo_clean_fast_and_jaxless(tmp_path):
    """`python -m scripts.dl4jlint` exits 0 against the committed
    baseline, never imports jax (a poisoned jax module would crash it),
    and stays inside the time budget (<5s unloaded; asserted with
    headroom for a loaded CI box)."""
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text(
        "raise ImportError('dl4jlint must not import jax')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{poison}{os.pathsep}{REPO}"
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "scripts.dl4jlint"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120)
    dt = time.perf_counter() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "must not import jax" not in proc.stdout + proc.stderr
    assert dt < 20.0, f"lint run took {dt:.1f}s"


def test_synthetic_violation_in_fixture_copy_fails(tmp_path):
    """Copy a REAL repo file, introduce one violation, and the driver
    (same rules, same committed baseline) exits 1 on the copy."""
    victim = os.path.join(REPO, "deeplearning4j_tpu", "serving",
                          "batcher.py")
    copy = tmp_path / "batcher_copy.py"
    shutil.copy(victim, copy)
    assert cli.main([str(copy)]) == 0       # the copy starts clean
    with open(copy, "a") as f:
        f.write("\nimport jax\n"
                "def _synthetic(x):\n"
                "    return jax.jit(lambda a: a)(x)\n")
    assert cli.main([str(copy)]) == 1


def test_ci_checks_lists_all_gates():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "ci_checks.py"),
         "--list"], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0
    assert "dl4jlint" in proc.stdout
    assert "kernel_trust.json" in proc.stdout
    assert "placement_selftest" in proc.stdout
    assert "check_metrics_docs" in proc.stdout
