"""tools/ksimlint — the AST contract analyzer (docs/lint.md).

Three layers:

- per-rule fixture tests under tests/fixtures/lint/: one seeded-bad,
  one suppressed, one clean sample per rule, proving each checker
  actually FIRES and honors ``# ksimlint: disable=`` suppressions;
- the full-tree scan, in-process, asserting the real codebase carries
  zero unsuppressed findings (the same gate as ``make lint``);
- cross-checks pinning the analyzer's AST-side views (kernel registry,
  taxonomy registries) to the runtime objects the process imports.

The analyzer itself is stdlib-only, so everything here except the
runtime cross-check runs without touching jax.
"""

from __future__ import annotations

import os

import pytest

from tools.ksimlint.core import DEFAULT_TARGETS, Project, mark_suppressed, run
from tools.ksimlint.rules import (
    env_contract,
    exception_flow,
    import_boundary,
    kernel_purity,
    lock_discipline,
    lock_order,
    registry_literals,
    thread_role,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "lint")


def _project(*names: str) -> Project:
    return Project.load(FIXTURES, tuple(names))


def _run_rule(check, project: Project, **kw):
    """check() + suppression marking; returns (open, suppressed)."""
    findings = mark_suppressed(project, check(project, **kw))
    return (
        [f for f in findings if not f.suppressed],
        [f for f in findings if f.suppressed],
    )


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------


def test_lock_discipline_fires_on_seeded_violations():
    open_, suppressed = _run_rule(lock_discipline.check, _project("lock_bad.py"))
    assert not suppressed
    lines = {f.line for f in open_}
    messages = "\n".join(f.message for f in open_)
    # Unlocked module-global, unlocked read, unlocked write, closure
    # escape, worker self-write — and nothing from the disciplined
    # methods.
    assert len(open_) == 5, messages
    assert "_registry" in messages
    assert "self._items" in messages
    assert "worker-thread function '_run' writes self.counter" in messages
    # Exactly the seeded lines fired — the with-block, lock-held and
    # main-thread-read accesses produced nothing.
    assert lines == {16, 33, 36, 41, 46}, sorted(lines)


def test_lock_discipline_suppression_and_clean():
    open_, suppressed = _run_rule(lock_discipline.check, _project("lock_suppressed.py"))
    assert not open_ and len(suppressed) == 1
    open_, suppressed = _run_rule(lock_discipline.check, _project("lock_clean.py"))
    assert not open_ and not suppressed


# ---------------------------------------------------------------------------
# kernel-purity
# ---------------------------------------------------------------------------


def test_kernel_purity_fires_on_seeded_violations():
    open_, suppressed = _run_rule(kernel_purity.check, _project("kernel_bad.py"))
    assert not suppressed
    messages = [f.message for f in open_]
    joined = "\n".join(messages)
    assert sum("Python branch on a traced value" in m for m in messages) == 2
    assert "print() inside a traced body" in joined
    assert "float() coerces a traced value" in joined
    assert "host numpy op np.sum" in joined
    assert "64-bit dtype literal 'float64'" in joined
    assert ".item() on a traced value" in joined
    # The static-arg branch (cfg.preempt) did NOT fire.
    assert len(open_) == 7, joined


def test_kernel_purity_suppression_and_clean():
    open_, suppressed = _run_rule(kernel_purity.check, _project("kernel_suppressed.py"))
    assert not open_ and len(suppressed) == 1
    open_, suppressed = _run_rule(kernel_purity.check, _project("kernel_clean.py"))
    assert not open_ and not suppressed


# ---------------------------------------------------------------------------
# import-boundary
# ---------------------------------------------------------------------------


def _boundary(target, scope):
    return (
        import_boundary.Boundary(
            target, frozenset({"jax", "jaxlib", "numpy"}), scope
        ),
    )


def test_import_boundary_fires_per_scope():
    project = _project("import_bad.py")
    # import-time: the module-scope numpy import (function bodies and
    # child payloads are invisible to this scope).
    open_, _ = _run_rule(
        import_boundary.check, project,
        boundaries=_boundary("import_bad.py", "import-time"),
    )
    assert len(open_) == 1 and "numpy" in open_[0].message
    # parent-child: module scope AND the non-child parent function; the
    # child payload stays sanctioned.
    open_, _ = _run_rule(
        import_boundary.check, project,
        boundaries=_boundary("import_bad.py", "parent-child"),
    )
    assert len(open_) == 2
    assert any("parent_helper" in f.message for f in open_)
    assert not any("child_payload" in f.message for f in open_)


def test_import_boundary_suppression_and_clean():
    open_, suppressed = _run_rule(
        import_boundary.check, _project("import_suppressed.py"),
        boundaries=_boundary("import_suppressed.py", "everywhere"),
    )
    assert not open_ and len(suppressed) == 1
    open_, suppressed = _run_rule(
        import_boundary.check, _project("import_clean.py"),
        boundaries=_boundary("import_clean.py", "import-time"),
    )
    assert not open_ and not suppressed  # lazy bridge + TYPE_CHECKING legal


# ---------------------------------------------------------------------------
# registry-literals
# ---------------------------------------------------------------------------


def _registry_cfg(replay: str) -> registry_literals.RegistryConfig:
    return registry_literals.RegistryConfig(
        faults_module="registry_regs.py",
        obs_module="registry_regs.py",
        replay_module=replay,
    )


def test_registry_literals_fires_on_seeded_violations():
    project = _project(
        "registry_regs.py", "registry_replay_bad.py", "registry_caller_bad.py"
    )
    open_, suppressed = _run_rule(
        registry_literals.check, project, cfg=_registry_cfg("registry_replay_bad.py")
    )
    assert not suppressed
    joined = "\n".join(f.message for f in open_)
    assert "'rogue.site' is not declared in SITES" in joined
    assert "SITES entry 'wired.site' has no FAULTS.check call site" in joined
    assert "'rogue.span' is not in obs.SPAN_NAMES" in joined
    # The helpers that open spans by another spelling are scanned too.
    assert "'rogue.phase' is not in obs.SPAN_NAMES" in joined
    assert "'rogue.lap' is not in obs.SPAN_NAMES" in joined
    assert "'rogue.stage' is not in obs.SPAN_NAMES" in joined
    assert joined.count("TRACE.span with a non-literal name") == 1
    assert "'rogue.event' is not in obs.EVENT_NAMES" in joined
    assert "non-literal name" in joined
    assert "'rogue_metric' is not in obs.METRIC_NAMES" in joined
    assert "non-literal family name" in joined
    assert "'rogue_reason' not in FALLBACK_REASONS" in joined
    assert "'host_hook:' not covered by FALLBACK_REASON_PREFIXES" in joined
    assert "'dead_entry' appears nowhere" in joined


def test_registry_literals_suppression_and_clean():
    project = _project(
        "registry_regs.py", "registry_replay_clean.py", "registry_caller_suppressed.py"
    )
    open_, suppressed = _run_rule(
        registry_literals.check, project, cfg=_registry_cfg("registry_replay_clean.py")
    )
    # The two rogue call sites are suppressed; the unwired-site finding
    # for wired.site remains structural (the suppressed calls don't
    # count as wiring) — assert exactly that split.
    assert len(suppressed) == 2
    assert len(open_) == 1 and "no FAULTS.check call site" in open_[0].message

    project = _project(
        "registry_regs.py", "registry_replay_clean.py", "registry_caller_clean.py"
    )
    open_, suppressed = _run_rule(
        registry_literals.check, project, cfg=_registry_cfg("registry_replay_clean.py")
    )
    assert not open_ and not suppressed


def test_registry_literals_dead_metric_entry_fires():
    """A METRIC_NAMES entry with no _expo_family declaration is a dead
    registry entry — a family dashboards would scrape for in vain."""
    project = _project(
        "registry_regs_deadmetric.py",
        "registry_replay_clean.py",
        "registry_caller_clean.py",
    )
    cfg = registry_literals.RegistryConfig(
        faults_module="registry_regs_deadmetric.py",
        obs_module="registry_regs_deadmetric.py",
        replay_module="registry_replay_clean.py",
    )
    open_, suppressed = _run_rule(registry_literals.check, project, cfg=cfg)
    assert not suppressed
    assert len(open_) == 1, [f.message for f in open_]
    assert "'ksim_dead_total'" in open_[0].message
    assert "dead registry entry" in open_[0].message


# ---------------------------------------------------------------------------
# env-contract
# ---------------------------------------------------------------------------


def test_env_contract_fires_both_directions():
    open_, _ = _run_rule(
        env_contract.check, _project("env_bad.py"),
        cfg=env_contract.EnvConfig(docs_rel="env_docs.md"),
    )
    joined = "\n".join(f"{f.path}: {f.message}" for f in open_)
    assert "env_bad.py: KSIM_LINTFIXTURE_UNDOCUMENTED" in joined
    assert "env_docs.md: documented variable KSIM_LINTFIXTURE_DEAD" in joined


def test_env_contract_suppression_and_clean():
    open_, suppressed = _run_rule(
        env_contract.check, _project("env_suppressed.py"),
        cfg=env_contract.EnvConfig(docs_rel="env_docs_clean.md"),
    )
    assert not open_ and len(suppressed) == 1
    open_, suppressed = _run_rule(
        env_contract.check, _project("env_clean.py"),
        cfg=env_contract.EnvConfig(docs_rel="env_docs_clean.md"),
    )
    assert not open_ and not suppressed


def test_env_contract_missing_docs_is_a_finding():
    open_, _ = _run_rule(
        env_contract.check, _project("env_bad.py"),
        cfg=env_contract.EnvConfig(docs_rel="no_such_docs.md"),
    )
    assert len(open_) == 1 and "missing" in open_[0].message


# ---------------------------------------------------------------------------
# lock-order (interprocedural — tools/ksimlint/callgraph.py)
# ---------------------------------------------------------------------------


def test_lock_order_seeded_deadlock_is_exactly_one_cycle():
    """The ABBA fixture declares BOTH orders, so the only finding is
    the cycle itself — visible only through the call graph (neither
    function nests two with-blocks lexically)."""
    open_, suppressed = _run_rule(lock_order.check, _project("lockorder_bad.py"))
    assert not suppressed
    assert len(open_) == 1, [f.message for f in open_]
    assert "cycle" in open_[0].message
    assert "Pair._a" in open_[0].message and "Pair._b" in open_[0].message


def test_lock_order_suppression_waives_and_clean():
    # Suppressing EVERY witness of an edge also waives it out of the
    # cycle graph — one suppressed finding, nothing open.
    open_, suppressed = _run_rule(
        lock_order.check, _project("lockorder_suppressed.py")
    )
    assert not open_ and len(suppressed) == 1
    assert "undeclared lock nesting" in suppressed[0].message
    # Declared acyclic nesting + RLock reentrancy: nothing at all.
    open_, suppressed = _run_rule(lock_order.check, _project("lockorder_clean.py"))
    assert not open_ and not suppressed


def test_lock_order_graph_covers_annotated_domains():
    """Every annotated lock domain in the tree is a node the analyzer
    can reason about — the coverage claim behind the zero-cycle gate."""
    graph = Project.load(REPO, DEFAULT_TARGETS).callgraph()
    required = {
        "ClusterStore._lock",
        "TracePlane._lock",
        "FaultPlane._lock",
        "JobQueue._cond",
        "Job._cond",
        "JobManager._lock",
        "JobJournal._lock",
        "CompileCache._lock",
        "replay._PREWARM_LOCK",
        "replay._TP_MESH_LOCK",
    }
    assert required <= set(graph.lock_kinds), sorted(graph.lock_kinds)
    # The documented compaction chain is OBSERVED, not just declared:
    # the qualified lock-held on JobManager._journal_records is what
    # makes the dynamic snapshot_fn callback visible.
    assert ("JobJournal._lock", "JobManager._lock") in graph.observed_edges()


# ---------------------------------------------------------------------------
# thread-role
# ---------------------------------------------------------------------------


def test_thread_role_seeded_worker_store_is_exactly_one_finding():
    """The store lives in a helper the round-8 lexical check cannot
    see; the interprocedural propagation reaches it."""
    open_, suppressed = _run_rule(thread_role.check, _project("role_bad.py"))
    assert not suppressed
    assert len(open_) == 1, [f.message for f in open_]
    assert "store to self.done" in open_[0].message
    assert "reachable from dispatch-worker root" in open_[0].message


def test_thread_role_suppression_and_clean():
    open_, suppressed = _run_rule(thread_role.check, _project("role_suppressed.py"))
    assert not open_ and len(suppressed) == 1
    open_, suppressed = _run_rule(thread_role.check, _project("role_clean.py"))
    assert not open_ and not suppressed


def test_thread_role_unknown_role_and_missing_role_fire():
    """A typo'd role would silently opt out of every propagated check;
    an unannotated resolved Thread target is the same hazard."""
    import textwrap

    from tools.ksimlint.core import SourceFile

    src = textwrap.dedent(
        """
        import threading


        class D:
            def start(self):
                threading.Thread(target=self._work).start()
                threading.Thread(target=self._other).start()

            def _work(self):  # ksimlint: thread-role(cowboy)
                pass

            def _other(self):
                pass
        """
    )
    sf = SourceFile("m.py", "m.py", src)
    findings = thread_role.check(Project("/tmp", {"m.py": sf}, ("m.py",)))
    joined = "\n".join(f.message for f in findings)
    assert "unknown thread-role 'cowboy'" in joined
    assert "has no role annotation" in joined


# ---------------------------------------------------------------------------
# exception-flow
# ---------------------------------------------------------------------------


def test_exception_flow_seeded_absorption_is_exactly_one_finding():
    """run_all's broad handler absorbs the RunCancelled its callee may
    raise — known only through the call graph."""
    open_, suppressed = _run_rule(exception_flow.check, _project("exc_bad.py"))
    assert not suppressed
    assert len(open_) == 1, [f.message for f in open_]
    assert "broad except absorbs RunCancelled" in open_[0].message
    assert "_step" in open_[0].message


def test_exception_flow_suppression_and_clean():
    open_, suppressed = _run_rule(exception_flow.check, _project("exc_suppressed.py"))
    assert not open_ and len(suppressed) == 1
    # Explicit RunCancelled arm, capture-box pattern, _reject-raised
    # ReplayFallback: all compliant shapes, zero findings.
    open_, suppressed = _run_rule(exception_flow.check, _project("exc_clean.py"))
    assert not open_ and not suppressed


def test_exception_flow_fault_and_fallback_channels():
    """except InjectedFault outside the containment scopes and a direct
    ReplayFallback raise outside _reject/_Unsupported both fire."""
    import textwrap

    from tools.ksimlint.core import SourceFile

    src = textwrap.dedent(
        """
        class InjectedFault(Exception):
            pass


        class ReplayFallback(Exception):
            pass


        def contain(op):
            try:
                return op()
            except InjectedFault:
                return None


        def bail(reason):
            raise ReplayFallback(reason)
        """
    )
    sf = SourceFile("m.py", "m.py", src)
    findings = exception_flow.check(Project("/tmp", {"m.py": sf}, ("m.py",)))
    joined = "\n".join(f.message for f in findings)
    assert "explicit `except InjectedFault` outside" in joined
    assert "direct `raise ReplayFallback(...)`" in joined


# ---------------------------------------------------------------------------
# The full tree (the same gate as `make lint`)
# ---------------------------------------------------------------------------


def test_full_tree_has_zero_unsuppressed_findings():
    """The tier-1 in-process equivalent of `make lint`: every rule over
    ksim_tpu/, chip_smoke.py and tools/ — zero unsuppressed findings.  The
    analyzer is stdlib-only, so this needs no jax and no subprocess."""
    findings = run(REPO, DEFAULT_TARGETS)
    open_ = [f for f in findings if not f.suppressed]
    assert not open_, "\n" + "\n".join(f.format() for f in open_)
    # The suppressions that exist are the documented, justified ones;
    # a new suppression should be a conscious reviewable event, so pin
    # the count: two round-11 lock-discipline snapshots, the fleet
    # driver's deliberate on-worker mesh-failure store (round 19's
    # _mesh_lock rework left one flagged write where round 18 had two),
    # and the waived construction-time JobManager._recover journal edge.
    assert len(findings) - len(open_) == 4, [f.format() for f in findings if f.suppressed]


def test_cli_human_and_json(tmp_path, capsys):
    """The CLI surface `make lint` drives: exit 0 + summary on the real
    tree, exit 1 on a tree with a finding, --json parses."""
    import json as json_mod

    from tools.ksimlint.__main__ import main

    assert main(["--root", REPO]) == 0
    capsys.readouterr()
    bad = tmp_path / "mod.py"
    bad.write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._d = {}  # guarded-by: _lock\n"
        "    def f(self):\n"
        "        return self._d\n"
    )
    assert main(["--root", str(tmp_path), "mod.py"]) == 1
    out = capsys.readouterr().out
    assert "mod.py:7" in out and "lock-discipline" in out
    assert main(["--root", str(tmp_path), "mod.py", "--json"]) == 1
    doc = json_mod.loads(capsys.readouterr().out)
    assert doc["unsuppressed"] == 1 and doc["findings"][0]["rule"] == "lock-discipline"


def test_cli_exits_1_on_seeded_concurrency_fixtures(capsys):
    """The gate the ISSUE pins: the analyzer run on either seeded
    fixture fails the build (exit 1) with exactly one finding."""
    from tools.ksimlint.__main__ import main

    assert main(["--root", REPO, "tests/fixtures/lint/lockorder_bad.py"]) == 1
    out = capsys.readouterr().out
    assert out.count("[lock-order]") == 1 and "cycle" in out
    assert main(["--root", REPO, "tests/fixtures/lint/role_bad.py"]) == 1
    out = capsys.readouterr().out
    assert out.count("[thread-role]") == 1


def test_trace_ingest_role_fixtures(capsys):
    """Round 20: ``trace-ingest`` is in the role vocabulary — the
    stream.py-shaped clean fixture passes, the seeded cross-thread
    write (producer storing to a main-thread-guarded attr through a
    helper) fails, and the real producer module itself is clean under
    the rule."""
    from tools.ksimlint.__main__ import main

    assert main(["--root", REPO, "tests/fixtures/lint/role_ingest_clean.py"]) == 0
    capsys.readouterr()
    assert main(["--root", REPO, "tests/fixtures/lint/role_ingest_bad.py"]) == 1
    out = capsys.readouterr().out
    assert out.count("[thread-role]") == 1 and "trace-ingest" in out
    assert (
        main(["--root", REPO, "--rule", "thread-role", "ksim_tpu/traces/stream.py"])
        == 0
    )


def test_cli_rule_flag_filters(capsys):
    """--rule is the repeatable single-rule spelling of --rules; an
    unknown rule is still a loud exit 2."""
    from tools.ksimlint.__main__ import main

    assert (
        main(
            [
                "--root", REPO, "--rule", "exception-flow",
                "tests/fixtures/lint/exc_bad.py",
            ]
        )
        == 1
    )
    assert "[exception-flow]" in capsys.readouterr().out
    assert main(["--root", REPO, "--rule", "lock-ordr"]) == 2


def test_cli_sarif_output(capsys):
    """--format sarif: schema-shaped SARIF 2.1.0 with rule metadata,
    physical locations, and in-source suppression objects."""
    import json as json_mod

    from tools.ksimlint.__main__ import main

    assert main(["--root", REPO, "--format", "sarif"]) == 0
    doc = json_mod.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0" and doc["$schema"].endswith("sarif-2.1.0.json")
    run0 = doc["runs"][0]
    driver = run0["tool"]["driver"]
    assert driver["name"] == "ksimlint"
    rule_ids = [r["id"] for r in driver["rules"]]
    assert len(rule_ids) == 8 and "lock-order" in rule_ids
    assert all(r["shortDescription"]["text"] for r in driver["rules"])
    # The real tree's findings are all suppressed: each result carries
    # the in-source suppression object so an upload stays green.
    assert run0["results"], "expected the audited suppressions to appear"
    for res in run0["results"]:
        assert res["ruleId"] in rule_ids
        assert res["ruleIndex"] == rule_ids.index(res["ruleId"])
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"]
        assert loc["region"]["startLine"] >= 1
        assert res["suppressions"][0]["kind"] == "inSource"
    # An OPEN finding has no suppressions key (SARIF viewers would
    # otherwise hide it).
    assert main(
        ["--root", REPO, "--format", "sarif", "tests/fixtures/lint/exc_bad.py"]
    ) == 1
    doc = json_mod.loads(capsys.readouterr().out)
    (res,) = doc["runs"][0]["results"]
    assert "suppressions" not in res


def test_cli_partial_target_and_typo(capsys):
    """A single-file run must not mass-flag docs rows the slice doesn't
    mention (the dead-row direction needs the whole tree), and a typo'd
    target is a loud usage error (exit 2), never a vacuously green
    scan of nothing."""
    from tools.ksimlint.__main__ import main

    assert main(["--root", REPO, "ksim_tpu/obs.py"]) == 0
    capsys.readouterr()
    assert main(["--root", REPO, "ksim_tpu/no_such_file.py"]) == 2
    assert "not found" in capsys.readouterr().err
    # A typo'd rule name is the same vacuously-green hazard: exit 2.
    assert main(["--root", REPO, "--rules", "lock-disclipine"]) == 2
    assert "unknown rule" in capsys.readouterr().err


def test_import_boundary_relative_imports_resolve(tmp_path):
    """A relative import is just spelling — it must not bypass the
    boundary: `from .engine import replay` from pkg/obs.py reaches
    pkg/engine/replay.py, whose module-scope jax import breaks the
    import-time contract transitively."""
    pkg = tmp_path / "pkg"
    (pkg / "engine").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "obs.py").write_text("from .engine import replay\n")
    (pkg / "engine" / "__init__.py").write_text("")
    (pkg / "engine" / "replay.py").write_text("import jax\n")
    project = Project.load(str(tmp_path), ("pkg",))
    findings = import_boundary.check(
        project,
        boundaries=(
            import_boundary.Boundary(
                "pkg/obs.py", frozenset({"jax"}), "import-time"
            ),
        ),
    )
    assert len(findings) == 1
    assert "pkg/engine/replay.py:1 imports jax" in findings[0].message


def test_lock_discipline_module_guards_cover_methods():
    """A class method touching a guarded module global without its lock
    is a finding too (the obs provider-registry shape)."""
    import textwrap

    from tools.ksimlint.core import SourceFile

    src = textwrap.dedent(
        """
        import threading

        _providers = {}  # guarded-by: _providers_lock
        _providers_lock = threading.Lock()


        class Plane:
            def sneaky(self):
                return dict(_providers)

            def polite(self):
                with _providers_lock:
                    return dict(_providers)
        """
    )
    sf = SourceFile("m.py", "m.py", src)
    findings = lock_discipline.check(Project("/tmp", {"m.py": sf}, ("m.py",)))
    assert len(findings) == 1 and findings[0].line == 10


def test_kernel_purity_scans_match_statements():
    """No statement type escapes the kernel scan: a match on a traced
    subject is host control flow, and case bodies are checked."""
    import textwrap

    from tools.ksimlint.core import SourceFile

    src = textwrap.dedent(
        """
        def device_kernel(fn=None, *, static=()):
            return fn if fn is not None else (lambda f: f)


        @device_kernel
        def k(x):
            match x:
                case 0:
                    print("zero")
                case _:
                    pass
            return x
        """
    )
    sf = SourceFile("m.py", "m.py", src)
    findings = kernel_purity.check(Project("/tmp", {"m.py": sf}, ("m.py",)))
    messages = "\n".join(f.message for f in findings)
    assert "Python branch on a traced value" in messages
    assert "print() inside a traced body" in messages


# ---------------------------------------------------------------------------
# Runtime cross-checks (these import the engine, hence jax)
# ---------------------------------------------------------------------------


def test_kernel_registry_matches_ast_scan():
    """The runtime KERNELS registry (decorator side) and the analyzer's
    AST scan (enforcement side) see the same kernels with the same
    static names — a kernel marked but unparsable, or scanned but
    unregistered, cannot drift silently."""
    import ksim_tpu.engine.core  # noqa: F401 - registers kernels on import
    import ksim_tpu.engine.replay  # noqa: F401
    from ksim_tpu.engine.kernelreg import KERNELS

    project = Project.load(
        REPO, ("ksim_tpu/engine/core.py", "ksim_tpu/engine/replay.py")
    )
    ast_view = {
        (fn.name, statics)
        for sf in project.files.values()
        for fn, statics in kernel_purity.scan_kernels(sf)
    }
    runtime_view = {(f.__name__, f.__ksim_kernel_static__) for f in KERNELS}
    assert runtime_view == ast_view
    assert ("_segment_fn", ("st", "prog")) in runtime_view
    assert ("_schedule_fn", ("self",)) in runtime_view


def test_device_kernel_decorator_is_identity():
    from ksim_tpu.engine.kernelreg import KERNELS, device_kernel

    before = len(KERNELS)

    @device_kernel
    def bare(x):
        return x

    @device_kernel(static=("cfg",))
    def with_args(cfg, x):
        return x

    try:
        assert bare(1) == 1 and with_args(None, 2) == 2
        assert bare.__ksim_kernel_static__ == ()
        assert with_args.__ksim_kernel_static__ == ("cfg",)
        assert KERNELS[-2:] == [bare, with_args]
    finally:
        del KERNELS[before:]
