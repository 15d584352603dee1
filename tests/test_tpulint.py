"""tpulint tier-1 gate: every rule fires on its known-bad fixture, stays
quiet on its known-good twin, and the whole tree is clean.

Runs the analyzer in-process (pure ast — no JAX needed) plus one
subprocess check that the CLI's exit code wiring works, so CI can rely
on ``python -m tools.tpulint deepspeed_tpu tests`` as a gate.  The
whole-program pass (tools/tpulint/graph.py + dataflow.py) gets its own
unit tests: import resolution, method binding, jit-reachability,
cross-file dataflow, baseline/changed CLI modes, and a wall-clock +
no-JAX budget so the analyzer can't quietly become a test-suite tax.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "tpulint_fixtures"

sys.path.insert(0, str(REPO))

from tools.tpulint import (RULES, Finding, collect_files,  # noqa: E402
                           find_mesh_axes, lint_paths)
from tools.tpulint.core import _axes_from_source, parse_context  # noqa: E402
from tools.tpulint.graph import build_program, module_name_for  # noqa: E402
from tools.tpulint.concurrency import (EXECUTOR, LOOP, THREAD,  # noqa: E402
                                       function_domains)

ALL_RULES = sorted(RULES)
PROGRAM_RULES = sorted(n for n, r in RULES.items() if r.scope == "program")


def _make_pkg(tmp_path, files):
    """Write a package tree {relpath: source} and return its root."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def _program_for(tmp_path, files):
    root = _make_pkg(tmp_path, files)
    ctxs = [parse_context(f, set()) for f in collect_files([str(root)])]
    return build_program(ctxs)


def _lint(path):
    return lint_paths([str(path)])


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_fires_on_known_bad(rule):
    bad = FIXTURES / f"bad_{rule.replace('-', '_')}.py"
    assert bad.exists(), f"missing known-bad fixture for {rule}"
    findings = _lint(bad)
    assert findings, f"{rule} produced no findings on {bad.name}"
    assert {f.rule for f in findings} == {rule}, \
        f"unexpected rules on {bad.name}: {findings}"
    # every documented BAD line is caught
    n_bad_markers = sum("# BAD" in line
                        for line in bad.read_text().splitlines())
    assert len(findings) >= n_bad_markers, \
        f"{rule}: {len(findings)} findings < {n_bad_markers} BAD markers"


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_quiet_on_known_good(rule):
    good = FIXTURES / f"good_{rule.replace('-', '_')}.py"
    assert good.exists(), f"missing known-good fixture for {rule}"
    findings = _lint(good)
    assert findings == [], \
        f"false positives on {good.name}: {[f.human() for f in findings]}"


def test_draft_window_key_fixtures():
    """Speculative-decode draft windows sample up to ``1 + k`` positions
    per sequence per step; the rng rules must catch a verify step that
    re-consumes one row key across window columns (the bug class
    ``sampler.window_keys``' per-(uid, position) fold exists to prevent)
    while staying quiet on the real derivation.  Named off-rule
    (``*_rng_draft_window``) so the per-rule parametrized fixtures keep
    their one-bad-one-good pairing; this pair is scenario coverage for
    rng-discipline."""
    bad = FIXTURES / "bad_rng_draft_window.py"
    findings = _lint(bad)
    assert findings, "rng rules missed the draft-window key reuse"
    assert {f.rule for f in findings} == {"rng-discipline"}
    n_bad = sum("# BAD" in line for line in bad.read_text().splitlines())
    assert len(findings) >= n_bad
    good = _lint(FIXTURES / "good_rng_draft_window.py")
    assert good == [], [f.human() for f in good]


def test_fleet_metric_label_fixtures():
    """Fleet re-export label hygiene (PR 14): the metric-name rule's
    registration-site check extends to FleetRegistry receivers
    (``fleet_registry`` / ``freg``), where an f-string metric NAME is
    always a finding — per-replica identity is the ``replica=`` label
    from the handle, never part of the name.  Named off-rule
    (``*_fleet_metric_label``) so the per-rule parametrized fixtures
    keep their one-bad-one-good pairing; this pair is scenario
    coverage for metric-name."""
    bad = FIXTURES / "bad_fleet_metric_label.py"
    findings = _lint(bad)
    assert findings, "metric-name missed the fleet f-string names"
    assert {f.rule for f in findings} == {"metric-name"}
    n_bad = sum("# BAD" in line for line in bad.read_text().splitlines())
    assert len(findings) >= n_bad
    assert all("replica= label" in f.message for f in findings)
    good = _lint(FIXTURES / "good_fleet_metric_label.py")
    assert good == [], [f.human() for f in good]


def test_whole_tree_is_clean_fast_and_jax_free():
    """The enforced gate, every invariant in ONE whole-tree run (the
    four-pass analyzer costs ~10 s — running it once keeps the gate
    itself inside the suite's time budget):

    * the pass-3 concurrency families AND the pass-4 contract families
      are registered and armed;
    * deepspeed_tpu + tests carry zero findings (all 27 rules,
      concurrency and contracts included);
    * the run stays under 15 s of its own CPU time — measured ~8-10 s
      (per-file rules ~4 s + program passes ~6 s; the analyzer is one
      thread, so alone its wall time is the same); the assert leaves
      headroom without letting the analyzer quietly become a
      multi-minute tax, and does not read the load of the suite's other
      workers, which a wall clock does (it failed once at 6 workers);
    * the analyzer never imports JAX (pure ast), checked in a fresh
      interpreter where nothing else has imported it.

    (tools/lint_gate.sh runs the same analyzer over deepspeed_tpu +
    tests + tools as the CI entry point; the tools/ files are linted by
    their own fixture-free pass and stay out of this timed run.)
    """
    code = (
        "import sys, time; t0 = time.process_time()\n"
        "from tools.tpulint.core import RULES, lint_paths\n"
        "conc = {'shared-state-race', 'lock-order-cycle',\n"
        "        'await-under-lock', 'seam-freeze'}\n"
        "assert conc <= set(RULES), 'concurrency pass not armed'\n"
        "contracts = {'seam-conformance', 'terminal-exhaustive',\n"
        "             'acquire-release', 'counter-pairing',\n"
        "             'raise-escape'}\n"
        "assert contracts <= set(RULES), 'contract pass not armed'\n"
        "fs = lint_paths(['deepspeed_tpu', 'tests'])\n"
        "dt = time.process_time() - t0\n"
        "assert 'jax' not in sys.modules, 'tpulint imported JAX'\n"
        "assert not fs, '\\n'.join(f.human() for f in fs)\n"
        "print(dt)\n"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert float(r.stdout.strip()) < 15.0, \
        f"tpulint took {r.stdout.strip()}s of CPU (budget 15s)"


def test_fixture_corpus_not_swept_into_tree_runs():
    files = collect_files([str(REPO / "tests")])
    assert not any("tpulint_fixtures" in str(f) for f in files)


def test_mesh_axes_match_runtime_mesh():
    """The axis vocabulary the linter enforces == the axes the real
    MeshTopology declares (parsed, not imported — but cross-checked
    against the live module when importable)."""
    axes = find_mesh_axes([str(REPO / "deepspeed_tpu")])
    src = (REPO / "deepspeed_tpu" / "comm" / "mesh.py").read_text()
    assert axes == _axes_from_source(src)
    try:
        from deepspeed_tpu.comm.mesh import AXIS_ORDER
    except Exception:
        pytest.skip("deepspeed_tpu not importable here")
    assert set(AXIS_ORDER) <= axes


def test_line_suppression_pragma(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text("def go(x):\n"
                 "    print(x)  # tpulint: disable=print\n"
                 "    print(x)\n")
    findings = _lint(f)
    assert len(findings) == 1 and findings[0].line == 3


def test_pragma_in_docstring_not_honored(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text('"""Docs: suppress with `# tpulint: disable-file=print`."""\n'
                 "def go(x):\n"
                 "    print(x)\n")
    assert len(_lint(f)) == 1      # the docstring must not disable anything


def test_unknown_path_errors():
    with pytest.raises(FileNotFoundError):
        lint_paths([str(REPO / "no_such_dir_xyz")])


def test_file_suppression_pragma(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text("# tpulint: disable-file=print\n"
                 "def go(x):\n"
                 "    print(x)\n"
                 "    print(x)\n")
    assert _lint(f) == []


def test_rules_are_documented():
    doc = (REPO / "docs" / "TPULINT.md").read_text()
    for rule in ALL_RULES:
        assert f"`{rule}`" in doc, f"rule {rule} missing from docs/TPULINT.md"


def test_finding_json_roundtrip():
    f = Finding("print", "a.py", 3, 0, "msg")
    assert json.loads(json.dumps(f.json()))["rule"] == "print"


def test_new_rule_families_present():
    """The four PR-3 dataflow families exist and are program-scoped."""
    assert {"rng-discipline", "dtype-flow", "donation-lifetime",
            "retrace-hazard"} <= set(PROGRAM_RULES)


def test_concurrency_rule_families_present():
    """The four pass-3 concurrency families exist and are
    program-scoped (they need the cross-file call graph + spawn
    edges, not one file's AST)."""
    assert {"shared-state-race", "lock-order-cycle",
            "await-under-lock", "seam-freeze"} <= set(PROGRAM_RULES)


def test_contract_rule_families_present():
    """The five pass-4 contract families exist, are program-scoped
    (seam conformance and raise-escape walk the cross-file call graph)
    and library-only (contracts bind the runtime, not the tests)."""
    contracts = {"seam-conformance", "terminal-exhaustive",
                 "acquire-release", "counter-pairing", "raise-escape"}
    assert contracts <= set(PROGRAM_RULES)
    assert all(RULES[n].library_only for n in contracts)


def test_fixture_corpus_is_complete_and_isolated():
    """Corpus meta-test: every registered rule has its bad_/good_ pair,
    every bad fixture in the directory fires EXACTLY ONE rule, and —
    for the per-rule pairs — that rule is the one named by the file
    stem.  A fixture that trips a second rule is cross-contamination:
    the per-rule tests would then prove nothing about isolation."""
    stems = {p.stem for p in FIXTURES.glob("*.py")}
    for rule in ALL_RULES:
        base = rule.replace("-", "_")
        assert f"bad_{base}" in stems, f"no bad fixture for {rule}"
        assert f"good_{base}" in stems, f"no good fixture for {rule}"
    registered = {r.replace("-", "_"): r for r in ALL_RULES}
    for bad in sorted(FIXTURES.glob("bad_*.py")):
        findings = _lint(bad)
        fired = {f.rule for f in findings}
        assert len(fired) == 1, \
            f"{bad.name} fires {sorted(fired) or 'nothing'} " \
            f"(want exactly one rule)"
        stem = bad.stem[len("bad_"):]
        if stem in registered:
            assert fired == {registered[stem]}, \
                f"{bad.name} fires {fired}, not its own rule"
        # scenario fixtures (bad_rng_draft_window, ...) are pinned to
        # their rule by their dedicated tests; singleton-fired is the
        # corpus-wide invariant
        assert (FIXTURES / f"good_{stem}.py").exists(), \
            f"{bad.name} has no good_ twin"


# --------------------------------------------------------------------------
# pass 4 contracts: mutation tests — deleting the pairing half of a real
# contract in the REAL tree must produce exactly the expected finding
# --------------------------------------------------------------------------

def _mutate_and_lint(tmp_path, src_rel, needle, rule):
    """Copy one real module, assert the rule is quiet on the pristine
    copy, replace the single line containing ``needle`` with ``pass``
    (deleting the call while keeping the file parseable), and return
    the findings the rule produces on the mutant."""
    src = (REPO / src_rel).read_text()
    lines = src.splitlines(keepends=True)
    hits = [i for i, ln in enumerate(lines) if needle in ln]
    assert len(hits) == 1, \
        f"expected exactly one '{needle}' line in {src_rel}, " \
        f"got {len(hits)} — update the mutation test"
    clean = tmp_path / "clean.py"
    clean.write_text(src)
    assert lint_paths([str(clean)], rules=[rule]) == [], \
        f"{rule} not quiet on pristine {src_rel}"
    ln = lines[hits[0]]
    indent = ln[:len(ln) - len(ln.lstrip())]
    lines[hits[0]] = indent + "pass\n"
    mutant = tmp_path / "mutant.py"
    mutant.write_text("".join(lines))
    return lint_paths([str(mutant)], rules=[rule])


def test_mutation_deleted_close_out_is_caught(tmp_path):
    """Delete the terminal ``on_finish`` from the engine's ``_forget``
    teardown: ``_forget`` falls out of the close-out family, so its pop
    of the ``self._pending`` live set becomes a uid vanishing without a
    terminal status — terminal-exhaustive must see the severed pairing.
    This is the PR-13/PR-15 leak shape: a request dropped from live
    tracking with no lifecycle close."""
    findings = _mutate_and_lint(
        tmp_path, "deepspeed_tpu/inference/engine.py",
        "self.requests.on_finish(uid, status=status)",
        "terminal-exhaustive")
    assert len(findings) == 1, [f.human() for f in findings]
    f = findings[0]
    assert "_pending" in f.message and "_forget" in f.message
    assert f.end_line is not None      # points back at the live-set decl


def test_mutation_deleted_allocator_free_is_caught(tmp_path):
    """Delete the allocator release from ``RaggedState.release``: the
    descriptor leaves the ``ledger=allocator``-marked ``self.seqs``
    with its blocks never freed — acquire-release must flag the
    removal site (the PR-17 revive over-commit shape: blocks leaking
    on a lifecycle path)."""
    findings = _mutate_and_lint(
        tmp_path, "deepspeed_tpu/inference/ragged/state.py",
        "self.allocator.free(list(reversed(seq.blocks)))",
        "acquire-release")
    assert len(findings) == 1, [f.human() for f in findings]
    f = findings[0]
    assert "seqs" in f.message and "allocator" in f.message
    assert f.end_line is not None      # points back at the ledger decl


def test_mutation_deleted_slo_eval_bump_is_caught(tmp_path):
    """Delete the evaluated-side increment from ``SloTracker._observe``
    (the ONE paired-counter site the scorecard's "attainment == counter
    quotient by construction" claim rests on): ``_c_good`` then bumps
    without its declared pair ``_c_eval`` — counter-pairing must see
    the severed ``# tpulint: pair=_c_good/_c_eval`` contract."""
    findings = _mutate_and_lint(
        tmp_path, "deepspeed_tpu/telemetry/slo.py",
        "self._c_eval.inc(**labels)",
        "counter-pairing")
    assert len(findings) == 1, [f.human() for f in findings]
    f = findings[0]
    assert "_c_good" in f.message and "_c_eval" in f.message
    assert f.end_line is not None      # points back at the pair decl

# --------------------------------------------------------------------------
# pass 1: module/symbol table + call graph
# --------------------------------------------------------------------------

def test_module_name_from_package_layout(tmp_path):
    _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/sub/__init__.py": "",
        "pkg/sub/mod.py": "x = 1\n",
    })
    assert module_name_for(tmp_path / "pkg" / "sub" / "mod.py") \
        == "pkg.sub.mod"
    assert module_name_for(tmp_path / "pkg" / "sub" / "__init__.py") \
        == "pkg.sub"


def test_import_resolution_absolute_and_relative(tmp_path):
    prog = _program_for(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/a.py": "def helper(k):\n    return k\n",
        "pkg/b.py": """\
            from .a import helper as h2
            import pkg.a as amod

            def go(x):
                return h2(x) + amod.helper(x)
        """,
    })
    b = prog.modules["pkg.b"]
    assert b.imports["h2"] == "pkg.a.helper"
    assert b.imports["amod"] == "pkg.a"
    helper = prog.functions["pkg.a::helper"]
    assert prog.resolve_symbol(b, "h2") is helper
    assert prog.calls["pkg.b::go"] == {"pkg.a::helper"}


def test_method_binding_across_modules(tmp_path):
    prog = _program_for(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/base.py": """\
            class Base:
                def shared(self):
                    return 1
        """,
        "pkg/impl.py": """\
            from .base import Base

            class Impl(Base):
                def run(self):
                    return self.shared() + self.own()

                def own(self):
                    return 2

            def drive():
                eng = Impl()
                return eng.run()
        """,
    })
    assert prog.calls["pkg.impl::Impl.run"] == {
        "pkg.base::Base.shared", "pkg.impl::Impl.own"}
    # var.meth() binds through the constructed class
    assert "pkg.impl::Impl.run" in prog.calls["pkg.impl::drive"]


def test_jit_reachability_transitive(tmp_path):
    prog = _program_for(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/math.py": """\
            def inner(x):
                return x * 2

            def outer(x):
                return inner(x) + 1
        """,
        "pkg/entry.py": """\
            import jax
            from .math import outer

            step = jax.jit(outer)

            def cold(x):
                return x
        """,
    })
    assert "pkg.math::outer" in prog.jit_roots
    assert "pkg.math::inner" in prog.jit_reachable      # transitive
    assert "pkg.entry::cold" not in prog.jit_reachable


def test_self_attr_donating_binding_collected(tmp_path):
    prog = _program_for(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/eng.py": """\
            import jax

            def step(p, kv):
                return kv, p

            class Engine:
                def __init__(self):
                    self._fn = jax.jit(step, donate_argnums=(1,))
        """,
    })
    cls = prog.modules["pkg.eng"].classes["Engine"]
    assert cls.attr_bindings["_fn"].donate_argnums == (1,)
    assert cls.attr_bindings["_fn"].fn is prog.functions["pkg.eng::step"]


# --------------------------------------------------------------------------
# pass 2: the dataflow rules are really cross-file
# --------------------------------------------------------------------------

def test_rng_consumption_crosses_modules(tmp_path):
    root = _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/sampler.py": """\
            import jax

            def draw(k):
                return jax.random.normal(k, (2,))
        """,
        "pkg/driver.py": """\
            from .sampler import draw

            def go(key):
                x = draw(key)
                y = draw(key)
                return x, y
        """,
    })
    findings = lint_paths([str(root)], mesh_axes=set(),
                          rules=["rng-discipline"])
    assert len(findings) == 1 and "driver.py" in findings[0].path
    assert "draw()" in findings[0].message


def test_dtype_flow_through_imported_callee(tmp_path):
    root = _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/ops.py": """\
            def mm(h, w):
                return h @ w
        """,
        "pkg/model.py": """\
            import jax
            import jax.numpy as jnp
            from .ops import mm

            @jax.jit
            def fwd(x):
                h = x.astype(jnp.bfloat16)
                w = jnp.ones((4, 4), dtype=jnp.float32)
                return mm(h, w)
        """,
    })
    findings = lint_paths([str(root)], mesh_axes=set(),
                          rules=["dtype-flow"])
    assert len(findings) == 1 and "ops.py" in findings[0].path
    assert "called from fwd()" in findings[0].message


def test_donation_crosses_methods(tmp_path):
    root = _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/eng.py": """\
            import jax
            import jax.numpy as jnp

            def step(p, kv):
                return kv, p

            class Engine:
                def __init__(self):
                    self.kv = jnp.zeros((2, 2))
                    self._fn = jax.jit(step, donate_argnums=(1,))

                def run(self, p):
                    out, _ = self._fn(p, self.kv)
                    return out + self.kv
        """,
    })
    findings = lint_paths([str(root)], mesh_axes=set(),
                          rules=["donation-lifetime"])
    assert len(findings) == 1 and "self.kv" in findings[0].message


def test_report_only_keeps_whole_program_context(tmp_path):
    """--changed semantics: the report is filtered to the dirty file but
    the analysis still sees every module — the cross-file finding in
    driver.py survives even when sampler.py is filtered out."""
    root = _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/sampler.py": """\
            import jax

            def draw(k):
                return jax.random.normal(k, (2,))
        """,
        "pkg/driver.py": """\
            from .sampler import draw

            def go(key):
                return draw(key), draw(key)
        """,
    })
    driver = str(root / "pkg" / "driver.py")
    sampler = str(root / "pkg" / "sampler.py")
    hits = lint_paths([str(root)], mesh_axes=set(),
                      rules=["rng-discipline"], report_only={driver})
    assert len(hits) == 1 and "driver.py" in hits[0].path
    assert lint_paths([str(root)], mesh_axes=set(),
                      rules=["rng-discipline"],
                      report_only={sampler}) == []


# --------------------------------------------------------------------------
# CI ergonomics: baseline + changed modes, perf/no-JAX budget
# --------------------------------------------------------------------------

def test_baseline_mode(tmp_path):
    from tools.tpulint.__main__ import main as cli
    bl = tmp_path / "baseline.json"
    bad_print = str(FIXTURES / "bad_print.py")
    bad_host = str(FIXTURES / "bad_host_sync.py")
    assert cli([bad_print, "--write-baseline", str(bl)]) == 0
    assert json.loads(bl.read_text())         # non-empty snapshot
    # every current finding is absorbed -> green gate
    assert cli([bad_print, "--baseline", str(bl)]) == 0
    # a NEW finding (another file) still fails
    assert cli([bad_print, bad_host, "--baseline", str(bl)]) == 1


def test_changed_mode_in_git_repo(tmp_path, monkeypatch):
    from tools.tpulint.__main__ import main as cli
    git = ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
    mod = tmp_path / "mod.py"
    mod.write_text("def go(x):\n    print(x)\n")
    monkeypatch.chdir(tmp_path)
    assert cli(["--changed", "mod.py"]) == 1            # dirty: reported
    subprocess.run(git + ["add", "."], cwd=tmp_path, check=True)
    subprocess.run(git + ["commit", "-qm", "x"], cwd=tmp_path, check=True)
    assert cli(["--changed", "mod.py"]) == 0            # clean tree: green


def test_changed_mode_sees_both_sides_of_a_rename(tmp_path, monkeypatch):
    """The rename blind spot: ``git status --porcelain`` renders a
    rename as ``R  old -> new`` and the old parser kept only the new
    side — a finding anchored at the OLD path (baseline entries,
    cross-file endpoints) silently left the changed set.  The ``-z``
    record parser must surface BOTH paths, plus ordinary adds and
    untracked files around the rename record."""
    from tools.tpulint.__main__ import git_dirty_files
    git = ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
    (tmp_path / "orig.py").write_text("def go():\n    return 1\n")
    (tmp_path / "keep.py").write_text("def keep():\n    return 2\n")
    subprocess.run(git + ["add", "."], cwd=tmp_path, check=True)
    subprocess.run(git + ["commit", "-qm", "x"], cwd=tmp_path, check=True)
    subprocess.run(git + ["mv", "orig.py", "moved.py"],
                   cwd=tmp_path, check=True)
    (tmp_path / "fresh.py").write_text("def fresh():\n    return 3\n")
    monkeypatch.chdir(tmp_path)
    dirty = git_dirty_files()
    names = {Path(p).name for p in dirty}
    assert {"orig.py", "moved.py", "fresh.py"} <= names, names
    assert "keep.py" not in names                       # clean file stays out


def test_lint_gate_script_shape():
    """tools/lint_gate.sh is the CI entry point: it must cover all
    three roots (library, tests, tools — the timed in-suite gate only
    runs the first two), emit SARIF, and honor a baseline snapshot
    when one exists.  Content-checked, not executed: running the
    four-pass analyzer a second time would double the suite's lint
    cost for no added coverage."""
    gate = REPO / "tools" / "lint_gate.sh"
    assert gate.exists()
    assert gate.stat().st_mode & 0o111, "lint_gate.sh not executable"
    src = gate.read_text()
    assert "deepspeed_tpu tests tools" in src
    assert "--format sarif" in src
    assert "tpulint_baseline.json" in src
    assert '"$@"' in src               # passthrough for --changed etc.


def test_cli_exit_codes():
    """Non-zero on findings, zero on clean input — the CI contract.
    (The whole-tree clean run lives in
    test_whole_tree_is_clean_fast_and_jax_free; repeating the ~9 s
    two-pass run here would double the gate's cost for no coverage.)"""
    bad = FIXTURES / "bad_print.py"
    r = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", str(bad), "--json"],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 1, r.stderr
    payload = json.loads(r.stdout)
    assert payload and all(d["rule"] == "print" for d in payload)

    good = FIXTURES / "good_print.py"
    r = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", str(good)],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, \
        f"tpulint flagged the clean fixture:\n{r.stdout}\n{r.stderr}"


def test_async_blocking_nested_coroutine_no_duplicates():
    """A coroutine nested inside another coroutine is its OWN scope:
    ast.walk visits both, so the outer walk must not descend into the
    inner AsyncFunctionDef or its calls get reported twice (and
    misattributed to the outer function).  Exact-count check — the
    shared fixture test only asserts >= BAD markers, which duplicates
    would satisfy."""
    bad = FIXTURES / "bad_async_blocking.py"
    findings = _lint(bad)
    n_bad = sum("# BAD" in line for line in bad.read_text().splitlines())
    assert len(findings) == n_bad, \
        [f.human() for f in findings]
    nested = [f for f in findings if "backend.step" in f.message]
    assert len(nested) == 1
    assert "async def inner" in nested[0].message


# --------------------------------------------------------------------------
# pass 3: execution-domain inference (graph.py spawn edges)
# --------------------------------------------------------------------------

def test_domain_inference_spawn_kinds(tmp_path):
    """Every spawn edge kind lands its target in the right domain:
    Thread(target=) -> thread, run_in_executor -> executor,
    create_task -> loop (coroutines are always loop), and a sync
    helper called from a coroutine inherits loop."""
    prog = _program_for(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/w.py": """\
            import asyncio
            import threading

            def thread_target():
                return 1

            def thunk():
                return 2

            async def coro_helper():
                sync_from_loop()

            def sync_from_loop():
                return 3

            async def main_entry():
                loop = asyncio.get_running_loop()
                t = threading.Thread(target=thread_target)
                t.start()
                await loop.run_in_executor(None, thunk)
                asyncio.create_task(coro_helper())
        """,
    })
    doms = function_domains(prog)
    assert THREAD in doms["pkg.w::thread_target"]
    assert EXECUTOR in doms["pkg.w::thunk"]
    assert doms["pkg.w::coro_helper"] == {LOOP}
    assert LOOP in doms["pkg.w::sync_from_loop"]
    assert doms["pkg.w::main_entry"] == {LOOP}
    kinds = {e.kind for e in prog.spawn_edges}
    assert {"thread", "executor", "task"} <= kinds


def test_domain_cross_module_thread_target(tmp_path):
    """A thread spawned in one module over a callable imported from
    another: the TARGET module's function goes thread-domain, and the
    spawn edge remembers the spawning site for dual-endpoint
    findings."""
    prog = _program_for(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/a.py": """\
            def tick():
                return 7
        """,
        "pkg/b.py": """\
            import threading
            from .a import tick

            def watch():
                threading.Thread(target=tick, daemon=True).start()
        """,
    })
    doms = function_domains(prog)
    assert THREAD in doms["pkg.a::tick"]
    edge = next(e for e in prog.spawn_edges if e.kind == "thread")
    assert edge.target == "pkg.a::tick"
    assert edge.path.endswith("b.py")


def test_executor_seam_forwarding_sanctions_engine_calls(tmp_path):
    """The Gateway._call idiom: a callable handed to a forwarder whose
    parameter feeds run_in_executor runs in the EXECUTOR domain — so
    engine calls inside it are sanctioned and seam-freeze stays
    quiet."""
    files = {
        "pkg/__init__.py": "",
        "pkg/g.py": """\
            import asyncio
            import functools

            class Gate:
                def __init__(self, engine, ex):
                    self.engine = engine
                    self._exec = ex

                async def _call(self, fn, *args):
                    loop = asyncio.get_running_loop()
                    return await loop.run_in_executor(
                        self._exec, functools.partial(fn, *args))

                async def go(self):
                    await self._call(self._work)

                def _work(self):
                    return self.engine.step({})
        """,
    }
    prog = _program_for(tmp_path / "p1", files)
    doms = function_domains(prog)
    assert EXECUTOR in doms["pkg.g::Gate._work"]
    root = _make_pkg(tmp_path / "p2", files)
    assert lint_paths([str(root)], mesh_axes=set(),
                      rules=["seam-freeze"]) == []


# --------------------------------------------------------------------------
# pass 3: lock-order / await-under-lock / seam-freeze units
# --------------------------------------------------------------------------

def test_lock_order_cycle_interprocedural(tmp_path):
    """The cycle only exists through a CALL made while holding a lock —
    no single function nests the two ``with`` blocks in both orders."""
    root = _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/bank.py": """\
            import threading

            class Bank:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def credit(self):
                    with self._a:
                        self._grab_b()

                def _grab_b(self):
                    with self._b:
                        pass

                def debit(self):
                    with self._b:
                        with self._a:
                            pass
        """,
    })
    findings = lint_paths([str(root)], mesh_axes=set(),
                          rules=["lock-order-cycle"])
    assert len(findings) == 1
    assert "Bank._a" in findings[0].message
    assert "Bank._b" in findings[0].message
    assert findings[0].end_path is not None   # the reversed acquisition


def test_lock_order_consistent_is_clean(tmp_path):
    root = _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/bank.py": """\
            import threading

            class Bank:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def credit(self):
                    with self._a:
                        with self._b:
                            pass

                def debit(self):
                    with self._a:
                        with self._b:
                            pass
        """,
    })
    assert lint_paths([str(root)], mesh_axes=set(),
                      rules=["lock-order-cycle"]) == []


def test_await_under_lock_endpoints(tmp_path):
    """The finding anchors at the await and carries the acquisition
    site as its second endpoint; an asyncio.Lock (``async with``) is
    the sanctioned form and stays quiet."""
    root = _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/m.py": """\
            import asyncio
            import threading

            _lock = threading.Lock()
            _alock = asyncio.Lock()

            async def bad():
                with _lock:
                    await asyncio.sleep(0)

            async def good():
                async with _alock:
                    await asyncio.sleep(0)
        """,
    })
    findings = lint_paths([str(root)], mesh_axes=set(),
                          rules=["await-under-lock"])
    assert len(findings) == 1
    f = findings[0]
    assert f.line == 9                       # the await
    assert f.end_line == 8                   # the with
    assert f.end_path == f.path


def _seam_split_pkg(tmp_path):
    """Engine call in a.py, thread spawn in b.py — the seam-freeze
    finding anchors where the call lives and ends where the thread is
    spawned (two files, one finding)."""
    return _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/a.py": """\
            class Relay:
                def __init__(self, engine):
                    self.engine = engine

                def _probe(self):
                    return self.engine.query(0)
        """,
        "pkg/b.py": """\
            import threading
            from .a import Relay

            def watch(engine):
                r = Relay(engine)
                threading.Thread(target=r._probe, daemon=True).start()
        """,
    })


def test_seam_freeze_dual_endpoints_in_json(tmp_path):
    root = _seam_split_pkg(tmp_path)
    findings = lint_paths([str(root)], mesh_axes=set(),
                          rules=["seam-freeze"])
    assert len(findings) == 1
    f = findings[0]
    assert f.path.endswith("a.py") and f.end_path.endswith("b.py")
    d = f.json()                             # both locations on the wire
    assert d["end_path"].endswith("b.py") and d["end_line"] == 6
    assert "b.py:6" in f.human()


def test_changed_keeps_finding_when_either_endpoint_dirty(tmp_path):
    """The --changed blind spot: editing ONLY the spawn site must still
    surface the cross-file finding anchored in the untouched module
    (and vice versa); a dirty bystander file surfaces nothing."""
    root = _seam_split_pkg(tmp_path)
    a = str(root / "pkg" / "a.py")
    b = str(root / "pkg" / "b.py")
    init = str(root / "pkg" / "__init__.py")
    for dirty in ({a}, {b}):
        hits = lint_paths([str(root)], mesh_axes=set(),
                          rules=["seam-freeze"], report_only=dirty)
        assert len(hits) == 1, f"finding lost with dirty={dirty}"
    assert lint_paths([str(root)], mesh_axes=set(),
                      rules=["seam-freeze"], report_only={init}) == []


def test_race_detected_across_modules(tmp_path):
    """Shared-state race with the spawn in another module: the writer
    runs thread-domain because of b.py's spawn, the reader stays
    main-domain — one finding, carrying both access sites."""
    root = _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/a.py": """\
            class Counter:
                def __init__(self):
                    self.n = 0

                def bump(self):
                    self.n += 1

                def stats(self):
                    return self.n
        """,
        "pkg/b.py": """\
            import threading
            from .a import Counter

            def drive():
                c = Counter()
                threading.Thread(target=c.bump, daemon=True).start()
                return c.stats()
        """,
    })
    findings = lint_paths([str(root)], mesh_axes=set(),
                          rules=["shared-state-race"])
    assert len(findings) == 1
    f = findings[0]
    assert "Counter.n" in f.message and "thread" in f.message
    assert f.end_line is not None


def test_race_quiet_under_lock_and_queue_disciplines(tmp_path):
    """The two main sanctioned shapes in one package: a lock shared by
    every conflicting access, and a queue.Queue hand-off."""
    root = _make_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/a.py": """\
            import queue
            import threading

            class Feed:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.total = 0
                    self.inbox = queue.Queue()

                def bump(self):
                    with self._lock:
                        self.total += 1

                def stats(self):
                    with self._lock:
                        return self.total

                def submit(self, item):
                    self.inbox.put(item)

                def drain(self):
                    return self.inbox.get_nowait()
        """,
        "pkg/b.py": """\
            import threading
            from .a import Feed

            def drive():
                f = Feed()
                threading.Thread(target=f.bump, daemon=True).start()
                threading.Thread(target=f.drain, daemon=True).start()
                f.submit(3)
                return f.stats()
        """,
    })
    assert lint_paths([str(root)], mesh_axes=set(),
                      rules=["shared-state-race"]) == []


def test_loadgen_clean_under_concurrency_families():
    """tools/loadgen.py spawns real worker threads over shared
    bookkeeping — it must hold the line under the new families (its
    per-worker result lists are disjoint by construction)."""
    findings = lint_paths(
        [str(REPO / "tools" / "loadgen.py")],
        rules=["shared-state-race", "lock-order-cycle",
               "await-under-lock", "seam-freeze"])
    assert findings == [], [f.human() for f in findings]


def test_sarif_roundtrip_against_json_formatter():
    """--format sarif carries exactly the native JSON formatter's
    content: same order, ruleId == rule, 1-based startColumn, and the
    optional second endpoint as a relatedLocation."""
    from tools.tpulint.__main__ import to_sarif
    f1 = Finding("print", "a.py", 3, 2, "msg")
    f2 = Finding("seam-freeze", "a.py", 5, 0, "m2",
                 end_path="b.py", end_line=9)
    doc = json.loads(json.dumps(to_sarif([f1, f2])))
    assert doc["version"] == "2.1.0" and "$schema" in doc
    results = doc["runs"][0]["results"]
    for native, sar in zip([f1.json(), f2.json()], results):
        assert sar["ruleId"] == native["rule"]
        assert sar["message"]["text"] == native["message"]
        loc = sar["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == native["path"]
        assert loc["region"]["startLine"] == native["line"]
        assert loc["region"]["startColumn"] == native["col"] + 1
    assert "relatedLocations" not in results[0]
    rel = results[1]["relatedLocations"][0]["physicalLocation"]
    assert rel["artifactLocation"]["uri"] == "b.py"
    assert rel["region"]["startLine"] == 9
    ids = [r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]]
    assert ids == ["print", "seam-freeze"]       # sorted, deduped


def test_sarif_cli_mode(capsys):
    from tools.tpulint.__main__ import main as cli
    rc = cli([str(FIXTURES / "bad_print.py"), "--format", "sarif"])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    assert all(r["ruleId"] == "print"
               for r in doc["runs"][0]["results"])
