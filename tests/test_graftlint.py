"""graftlint: fixture coverage per pass, suppressions, baseline
round-trip, and the repo-clean gate.

The gate test IS the tier-1 enforcement: it fails the suite whenever
``python scripts/graftlint.py`` would exit non-zero at HEAD.
"""

import ast
import importlib.util
import json
import os
import subprocess
import textwrap
import time

import pytest

from ray_tpu._private.lint import (
    Baseline, registered_passes, run_lint,
)
from ray_tpu._private.lint.cli import changed_files, main as lint_main
from ray_tpu._private.lint.dataflow import (
    build_cfg, held_locksets, lexical_locks, yield_points,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(FIXTURES)))


@pytest.fixture(scope="module")
def package_run():
    """One graftlint run over the whole package, through the CLI and
    under the repo's own baseline (20 s of CPU alone, 40 under six
    workers), for the cases that only read it: its exit code and output,
    how often each pass visited each module, and its CPU seconds beside
    those of parsing the same sources once and walking them once a pass,
    taken in this process right after it."""
    import collections
    import contextlib
    import io
    import types
    from unittest import mock

    from ray_tpu._private.lint import core

    visits = collections.Counter()

    def counted_passes(select=None, real=core.all_passes):
        passes = real(select)
        for p in passes:
            def check(mod, p=p, inner=p.check_module):
                visits[p.name, mod.relpath] += 1
                return inner(mod)
            p.check_module = check
        return passes

    out = io.StringIO()
    t0 = time.process_time()
    with mock.patch.object(core, "all_passes", counted_passes), \
            contextlib.redirect_stdout(out):
        rc = lint_main([])
    t1 = time.process_time()
    modules = core.iter_modules([os.path.join(REPO, "ray_tpu")], rel_to=REPO)
    for _ in registered_passes():
        nodes = sum(1 for m in modules
                    if getattr(m, "parse_error", None) is None
                    for _ in ast.walk(m.tree))
    t2 = time.process_time()
    return types.SimpleNamespace(
        rc=rc, out=out.getvalue(), visits=visits, modules=modules,
        nodes=nodes, lint_cpu_s=t1 - t0, parse_and_walk_cpu_s=t2 - t1)


def _lint(fixture, passname, **kw):
    return run_lint([os.path.join(FIXTURES, fixture)],
                    select=[passname], **kw)


# One (pass, bad fixture, clean twin, expected rule set) row per pass.
PASS_CASES = [
    ("jit-hygiene", "jit_bad.py", "jit_clean.py",
     {"jit-impure-call", "jit-global-mutation",
      "jit-unhashable-static", "jit-traced-branch"}),
    ("jit-tracking", "jit_untracked_bad.py", "jit_untracked_clean.py",
     {"jit-untracked"}),
    ("async-blocking", "async_bad.py", "async_clean.py",
     {"async-blocking-call", "async-unawaited-wait",
      "async-blocking-transitive"}),
    ("distributed-deadlock", "deadlock_bad.py", "deadlock_clean.py",
     {"deadlock-self-get", "deadlock-unbounded-wait"}),
    ("collective-consistency", "collectives_bad.py",
     "collectives_clean.py",
     {"collective-unknown-axis", "collective-divergent-branches",
      "collective-member-mismatch", "collective-dtype-drift",
      "collective-quantized-nonfloat", "collective-ef-nonfloat"}),
    ("splitphase-dataflow", "splitphase_bad.py", "splitphase_clean.py",
     {"splitphase-unwaited", "splitphase-double-wait",
      "splitphase-mismatched-wait"}),
    ("donation-use-after", "donation_bad.py", "donation_clean.py",
     {"donation-use-after"}),
    ("sharding-axis-consistency", "sharding_axis_bad.py",
     "sharding_axis_clean.py",
     {"sharding-axis-undeclared", "sharding-spec-axis-undeclared"}),
    ("objectref-leak", "objectref_bad.py", "objectref_clean.py",
     {"objectref-dropped", "objectref-leak"}),
    ("lock-discipline", "locks_bad.py", "locks_clean.py",
     {"lock-cycle", "lock-blocking-call"}),
    ("metric-declarations", "metrics_bad.py", "metrics_clean.py",
     {"metric-name", "metric-family", "metric-histogram-suffix",
      "metric-gauge-pid-tag", "metric-redeclared", "metric-exposition",
      "metric-exemplar-tag", "metric-ratio-gauge",
      "metric-label-cardinality"}),
    ("event-schema", "events_bad", "events_clean",
     {"event-unregistered-emit", "event-dead-type",
      "event-undocumented-type"}),
    ("control-loop", "control_loop_bad.py", "control_loop_clean.py",
     {"ctrl-busy-spin", "ctrl-unjittered-period",
      "ctrl-unawaited-policy"}),
    ("await-atomicity", "atomicity_bad.py", "atomicity_clean.py",
     {"await-atomicity"}),
    ("lockset-consistency", "lockset_bad.py", "lockset_clean.py",
     {"lockset-cross-origin-write", "lockset-inconsistent-write"}),
    ("actor-reentrancy", "reentrancy_bad.py", "reentrancy_clean.py",
     {"actor-reentrant-await", "actor-reentrant-chain"}),
]


class TestPassFixtures:
    @pytest.mark.parametrize(
        "passname,bad,clean,expected",
        PASS_CASES, ids=[c[0] for c in PASS_CASES])
    def test_bad_fixture_catches_every_rule(self, passname, bad, clean,
                                            expected):
        result = _lint(bad, passname)
        assert {f.rule for f in result.findings} == expected, \
            [f.render() for f in result.findings]

    @pytest.mark.parametrize(
        "passname,bad,clean,expected",
        PASS_CASES, ids=[c[0] for c in PASS_CASES])
    def test_clean_twin_is_silent(self, passname, bad, clean, expected):
        result = _lint(clean, passname)
        assert result.findings == [], \
            [f.render() for f in result.findings]

    def test_donation_pass_reads_the_engines_programs_pattern(self):
        """`serve/llm/programs.py`: an attribute of the object that
        wraps the program, donated and not rebound, then read."""
        found = [f for f in _lint("donation_bad.py",
                                  "donation-use-after").findings
                 if "'self._cache'" in f.message]
        assert len(found) == 1 and "in tick()" in found[0].message

    def test_at_least_five_passes_registered(self):
        assert len(registered_passes()) >= 5

    def test_parse_error_is_a_finding(self, tmp_path):
        broken = tmp_path / "broken.py"
        broken.write_text("def nope(:\n")
        result = run_lint([str(broken)])
        assert [f.rule for f in result.findings] == ["parse-error"]


class TestSuppressions:
    def test_per_line_by_rule_and_by_pass_name(self):
        result = _lint("suppress_fixture.py", "async-blocking")
        # Three sleeps: rule-id and pass-name suppressions kill two,
        # the third stays live.
        assert len(result.findings) == 1
        assert result.findings[0].context.startswith("time.sleep(1)")
        assert "live" in result.findings[0].message
        assert len(result.suppressed) == 2

    def test_disable_file(self):
        result = _lint("suppress_file_fixture.py", "async-blocking")
        assert result.findings == []
        assert len(result.suppressed) == 2

    def test_disable_all(self, tmp_path):
        src = textwrap.dedent("""\
            import time

            async def h():
                time.sleep(1)  # graftlint: disable=all
        """)
        p = tmp_path / "mod.py"
        p.write_text(src)
        result = run_lint([str(p)], select=["async-blocking"])
        assert result.findings == []
        assert len(result.suppressed) == 1


class TestBaseline:
    def _bad(self, baseline=None):
        return _lint("async_bad.py", "async-blocking", baseline=baseline)

    def test_round_trip_grandfathers_everything(self, tmp_path):
        first = self._bad()
        assert first.findings
        path = tmp_path / "baseline.json"
        Baseline.from_findings(first.findings).save(str(path))

        second = self._bad(baseline=str(path))
        assert second.findings == []
        assert len(second.baselined) == len(first.findings)
        assert second.stale_baseline == []

    def test_stale_entries_are_reported_not_fatal(self, tmp_path):
        first = self._bad()
        base = Baseline.from_findings(first.findings)
        base.entries.append({
            "rule": "async-blocking-call",
            "path": "something/fixed_long_ago.py",
            "context": "time.sleep(99)",
            "justification": "was real once",
        })
        path = tmp_path / "baseline.json"
        base.save(str(path))
        result = self._bad(baseline=str(path))
        assert result.findings == []
        assert len(result.stale_baseline) == 1

    def test_update_preserves_justifications(self, tmp_path):
        first = self._bad()
        base = Baseline.from_findings(first.findings)
        for e in base.entries:
            e["justification"] = "intentional: reviewed"
        regenerated = Baseline.from_findings(first.findings,
                                             previous=base)
        assert all(e["justification"] == "intentional: reviewed"
                   for e in regenerated.entries)

    def test_baseline_matching_survives_line_moves(self, tmp_path):
        src = textwrap.dedent("""\
            import time

            async def h():
                time.sleep(1)
        """)
        p = tmp_path / "mod.py"
        p.write_text(src)
        first = run_lint([str(p)], select=["async-blocking"])
        bpath = tmp_path / "baseline.json"
        Baseline.from_findings(first.findings).save(str(bpath))
        # Push the finding down 3 lines: (rule, path, context) still
        # matches even though the line number changed.
        p.write_text("# one\n# two\n# three\n" + src)
        moved = run_lint([str(p)], select=["async-blocking"],
                         baseline=str(bpath))
        assert moved.findings == []
        assert len(moved.baselined) == 1


class TestRepoGate:
    """The tier-1 gate: the repo itself lints clean at HEAD."""

    def test_repo_lints_clean(self, package_run):
        assert package_run.rc == 0, package_run.out
        assert "graftlint: OK" in package_run.out

    def test_baseline_entries_are_justified(self):
        path = os.path.join(REPO, ".graftlint-baseline.json")
        if not os.path.exists(path):
            pytest.skip("no baseline at HEAD")
        with open(path) as f:
            data = json.load(f)
        for e in data["findings"]:
            just = e.get("justification", "")
            assert just and not just.startswith("TODO"), e

    def test_list_passes(self, capsys):
        assert lint_main(["--list-passes"]) == 0
        out = capsys.readouterr().out
        for name in ("jit-hygiene", "async-blocking",
                     "distributed-deadlock", "collective-consistency",
                     "lock-discipline", "metric-declarations",
                     "event-schema", "control-loop",
                     "splitphase-dataflow", "donation-use-after",
                     "sharding-axis-consistency", "objectref-leak",
                     "await-atomicity", "lockset-consistency",
                     "actor-reentrancy"):
            assert name in out


def _cfg(src, name="f"):
    tree = ast.parse(textwrap.dedent(src))
    fn = next(n for n in ast.walk(tree)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
              and n.name == name)
    return build_cfg(fn)


def _reaches(cfg, src_block, dst_block):
    seen, stack = {src_block}, [src_block]
    while stack:
        for succ, _ in stack.pop().succs:
            if succ not in seen:
                seen.add(succ)
                stack.append(succ)
    return dst_block in seen


class TestCFG:
    """Shape checks for the dataflow engine's control-flow graphs."""

    def test_if_elif_else_branches_are_distinct_and_join(self):
        cfg = _cfg("""\
            def f(x):
                if x == 1:
                    a = 1
                elif x == 2:
                    b = 2
                else:
                    c = 3
                d = 4
        """)
        blocks = [cfg.block_at(n) for n in (3, 5, 7, 8)]
        assert all(b is not None for b in blocks)
        a, b, c, d = blocks
        assert len({id(a), id(b), id(c), id(d)}) == 4
        for branch in (a, b, c):
            assert _reaches(cfg, branch, d)
        # No branch flows into a sibling branch.
        assert not _reaches(cfg, a, b) and not _reaches(cfg, b, c)

    def test_while_else_runs_on_normal_exit_only(self):
        cfg = _cfg("""\
            def f(xs):
                while xs:
                    if xs.pop():
                        break
                else:
                    cleanup = 1
                done = 2
        """)
        head = cfg.block_at(2)
        els = cfg.block_at(6)
        done = cfg.block_at(7)
        assert els is not None
        # else hangs off the loop test, break bypasses it.
        assert els in [s for s, _ in head.succs]
        brk = cfg.block_at(3)   # the if-test block; break follows it
        assert _reaches(cfg, brk, done)
        assert _reaches(cfg, els, done)

    def test_try_finally_runs_on_both_exits(self):
        cfg = _cfg("""\
            def f(x):
                try:
                    if x:
                        return 1
                    y = 2
                finally:
                    release = 3
                return y
        """)
        # Both the early return and the fall-through reach exit, and
        # every such path passes a copy of the finally body.
        assert cfg.exit.preds
        for path_start in (cfg.block_at(4), cfg.block_at(5)):
            assert path_start is not None
            seen, stack = {path_start}, [path_start]
            hit_finally = False
            while stack:
                blk = stack.pop()
                if any(getattr(s, "lineno", 0) == 7 for s in blk.stmts):
                    hit_finally = True
                for succ, _ in blk.succs:
                    if succ not in seen:
                        seen.add(succ)
                        stack.append(succ)
            assert hit_finally
            assert cfg.exit in seen

    def test_early_return_skips_the_rest(self):
        cfg = _cfg("""\
            def f(x):
                if x:
                    return 0
                tail = 1
        """)
        ret = cfg.block_at(3)
        tail = cfg.block_at(4)
        assert not _reaches(cfg, ret, tail)
        assert _reaches(cfg, ret, cfg.exit)
        assert _reaches(cfg, tail, cfg.exit)

    def test_with_statement_is_linear(self):
        cfg = _cfg("""\
            def f(lock):
                with lock:
                    a = 1
                b = 2
        """)
        assert cfg.block_at(2) is cfg.block_at(3)
        assert _reaches(cfg, cfg.block_at(3), cfg.block_at(4))

    def test_for_body_runs_at_least_once(self):
        # The overlap idiom starts chunk 0 before the loop; a zero-trip
        # edge from the head would flag it on an infeasible path, so
        # loop exit flows only from iteration end.
        cfg = _cfg("""\
            def f(xs):
                for x in xs:
                    body = 1
                after = 2
        """)
        head = cfg.block_at(2)
        after = cfg.block_at(4)
        assert head not in [p for p, _ in after.preds]
        assert _reaches(cfg, cfg.block_at(3), after)


class TestConcurrencyHelpers:
    """Yield points, lexical lock extents, and acquire/release
    locksets — the engine pieces under the race passes."""

    def _fn(self, src, name="f"):
        tree = ast.parse(textwrap.dedent(src))
        return next(n for n in ast.walk(tree)
                    if isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))
                    and n.name == name)

    def test_yield_points_awaits_and_async_blocks(self):
        fn = self._fn("""\
            async def f(self):
                x = await g()
                async with h():
                    pass
                y = 1
        """)
        assign, awith, plain = fn.body
        assert len(yield_points(assign)) == 1
        assert awith in yield_points(awith)
        assert yield_points(plain) == []

    def test_yield_points_skip_nested_defs(self):
        fn = self._fn("""\
            async def f(self):
                async def inner():
                    await g()
                return inner
        """)
        inner, ret = fn.body
        assert yield_points(inner) == []
        assert yield_points(ret) == []

    def test_lexical_locks_cover_with_bodies_only(self):
        fn = self._fn("""\
            async def f(self):
                async with self._lock:
                    a = 1
                with open("p") as fh:
                    b = 2
                c = 3
        """)
        lex = lexical_locks(fn)
        a = fn.body[0].body[0]
        b = fn.body[1].body[0]
        c = fn.body[2]
        assert lex[id(a)] == frozenset({"self._lock"})
        assert lex.get(id(b), frozenset()) == frozenset()
        assert lex.get(id(c), frozenset()) == frozenset()

    def test_held_locksets_track_acquire_release(self):
        fn = self._fn("""\
            def f(self):
                self._lock.acquire()
                a = 1
                self._lock.release()
                b = 2
        """)
        held = held_locksets(build_cfg(fn))
        by_line = {stmt.lineno: held.get(id(stmt), frozenset())
                   for stmt in fn.body}
        assert by_line[3] == frozenset({"self._lock"})
        assert by_line[5] == frozenset()

    def test_held_locksets_are_must_not_may(self):
        fn = self._fn("""\
            def f(self, x):
                if x:
                    self._lock.acquire()
                c = 3
        """)
        held = held_locksets(build_cfg(fn))
        c = fn.body[1]
        # Only one branch acquires: the join must drop the lock.
        assert held.get(id(c), frozenset()) == frozenset()


class TestObligationTracking:
    """The engine follows values across aliasing and rebinds."""

    def _split(self, tmp_path, body):
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent(body))
        return run_lint([str(p)], select=["splitphase-dataflow"])

    def test_rebind_while_live_is_flagged(self, tmp_path):
        r = self._split(tmp_path, """\
            def f(x, y):
                h = start_ring_allgather(x)
                h = start_ring_allgather(y)
                wait_ring_allgather(h)
        """)
        assert [f.rule for f in r.findings] == ["splitphase-unwaited"]
        assert "overwritten" in r.findings[0].message

    def test_alias_keeps_the_obligation_alive(self, tmp_path):
        r = self._split(tmp_path, """\
            def f(x):
                h = start_ring_allgather(x)
                h2 = h
                h = None
                wait_ring_allgather(h2)
        """)
        assert r.findings == [], [f.render() for f in r.findings]

    def test_del_of_last_binding_is_flagged(self, tmp_path):
        r = self._split(tmp_path, """\
            def f(x):
                h = start_ring_allgather(x)
                del h
        """)
        assert [f.rule for f in r.findings] == ["splitphase-unwaited"]
        assert "deleted" in r.findings[0].message

    def test_loop_rebind_after_consume_is_clean(self, tmp_path):
        # Regression: a creation site re-executed on a loop back edge
        # must not see its own fresh value when judging the rebind.
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent("""\
            import ray_tpu

            def f(actor, xs):
                outs = []
                for x in xs:
                    out = actor.f.remote(x)
                    outs.append(out)
                return ray_tpu.get(outs)
        """))
        r = run_lint([str(p)], select=["objectref-leak"])
        assert r.findings == [], [f.render() for f in r.findings]

    def test_closure_capture_is_an_escape(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text(textwrap.dedent("""\
            import ray_tpu

            def f(actor, xs):
                refs = [actor.f.remote(x) for x in xs]

                def drain():
                    return ray_tpu.get(refs)
                return drain
        """))
        r = run_lint([str(p)], select=["objectref-leak"])
        assert r.findings == [], [f.render() for f in r.findings]


class TestCallGraph:
    """Resolution edge cases: bounded re-export chains, re-export
    cycles, ambiguity, and methods inherited through base classes."""

    def _graph(self, tmp_path, files):
        from ray_tpu._private.lint.callgraph import get_call_graph

        for rel, src in files.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
        r = run_lint([str(tmp_path)], select=["jit-hygiene"],
                     rel_to=str(tmp_path))
        return get_call_graph(r.modules)

    def _resolved(self, graph, relpath, fname):
        caller = next(f for f in graph.funcs
                      if f.mod.relpath == relpath and f.name == fname)
        return [callee for _call, callee in graph.direct_calls(caller)]

    def test_reexport_chain_resolves_up_to_four_hops(self, tmp_path):
        files = {"r5.py": "def f():\n    pass\n"}
        for i in range(5):
            files[f"r{i}.py"] = f"from r{i + 1} import f\n"
        files["ok.py"] = "from r1 import f\n\ndef caller():\n    f()\n"
        files["deep.py"] = "from r0 import f\n\ndef caller():\n    f()\n"
        g = self._graph(tmp_path, files)
        (ok,) = self._resolved(g, "ok.py", "caller")
        assert ok is not None and ok.mod.relpath == "r5.py"
        # One hop past the bound: unresolved, not wrong.
        (deep,) = self._resolved(g, "deep.py", "caller")
        assert deep is None

    def test_reexport_cycle_resolves_to_none(self, tmp_path):
        g = self._graph(tmp_path, {
            "a.py": "from b import g\n",
            "b.py": "from a import g\n",
            "use.py": "from a import g\n\ndef caller():\n    g()\n",
        })
        (got,) = self._resolved(g, "use.py", "caller")
        assert got is None  # bounded — and it terminated

    def test_ambiguous_duplicate_defs_resolve_to_none(self, tmp_path):
        g = self._graph(tmp_path, {"m.py": """\
            def f():
                pass

            def f():
                pass

            def caller():
                f()
        """})
        (got,) = self._resolved(g, "m.py", "caller")
        assert got is None  # precision over recall

    def test_self_method_resolves_through_imported_base(self, tmp_path):
        g = self._graph(tmp_path, {
            "base.py": """\
                class Base:
                    def ping(self):
                        return 1
            """,
            "child.py": """\
                from base import Base

                class Child(Base):
                    def caller(self):
                        return self.ping()
            """,
        })
        (got,) = self._resolved(g, "child.py", "caller")
        assert got is not None
        assert got.qualname == "Base.ping"
        assert got.mod.relpath == "base.py"

    def test_classname_method_resolves_through_local_subclass(
            self, tmp_path):
        g = self._graph(tmp_path, {"m.py": """\
            class A:
                def m(self):
                    return 1

            class B(A):
                pass

            def caller():
                return B.m()
        """})
        (got,) = self._resolved(g, "m.py", "caller")
        assert got is not None and got.qualname == "A.m"


class TestCLI:
    def test_json_format_reports_findings(self, capsys):
        rc = lint_main([os.path.join(FIXTURES, "objectref_bad.py"),
                        "--select", "objectref-leak", "--no-baseline",
                        "--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["ok"] is False
        assert out["files"] == 1
        rules = {f["rule"] for f in out["findings"]}
        assert rules == {"objectref-dropped", "objectref-leak"}
        for f in out["findings"]:
            assert set(f) == {"rule", "path", "line", "message",
                              "context"}

    def test_json_format_clean(self, capsys):
        rc = lint_main([os.path.join(FIXTURES, "objectref_clean.py"),
                        "--select", "objectref-leak", "--no-baseline",
                        "--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["ok"] is True and out["findings"] == []

    def _git(self, cwd, *args):
        return subprocess.run(["git", "-C", str(cwd), *args],
                              capture_output=True, text=True, check=True)

    def test_changed_files_diff_plus_untracked(self, tmp_path):
        self._git(tmp_path, "init", "-q")
        self._git(tmp_path, "config", "user.email", "t@t")
        self._git(tmp_path, "config", "user.name", "t")
        (tmp_path / "a.py").write_text("x = 1\n")
        (tmp_path / "keep.txt").write_text("not python\n")
        self._git(tmp_path, "add", "-A")
        self._git(tmp_path, "commit", "-qm", "seed")
        (tmp_path / "a.py").write_text("x = 2\n")      # modified
        (tmp_path / "b.py").write_text("y = 1\n")      # untracked
        got = changed_files("HEAD", str(tmp_path))
        assert got is not None
        assert {os.path.basename(p) for p in got} == {"a.py", "b.py"}

    def test_changed_files_outside_a_repo_is_none(self, tmp_path):
        assert changed_files("HEAD", str(tmp_path / "norepo")) is None

    def test_changed_only_without_git_degrades_to_full_scan(
            self, capsys, monkeypatch):
        import ray_tpu._private.lint.cli as cli_mod

        monkeypatch.setattr(cli_mod, "changed_files",
                            lambda base, root: None)
        rc = lint_main([os.path.join(FIXTURES, "objectref_clean.py"),
                        "--select", "objectref-leak", "--no-baseline",
                        "--changed-only"])
        captured = capsys.readouterr()
        assert rc == 0
        assert "falling back to a full scan" in captured.err
        assert "1 files" in captured.out  # the root was linted anyway

    def test_sarif_format_matches_golden(self, capsys):
        rc = lint_main([os.path.join(FIXTURES, "reentrancy_bad.py"),
                        "--select", "actor-reentrancy", "--no-baseline",
                        "--format", "sarif"])
        got = json.loads(capsys.readouterr().out)
        assert rc == 1
        with open(os.path.join(FIXTURES, "sarif_golden.json")) as f:
            assert got == json.load(f)

    def test_sarif_format_clean(self, capsys):
        rc = lint_main([os.path.join(FIXTURES, "reentrancy_clean.py"),
                        "--select", "actor-reentrancy", "--no-baseline",
                        "--format", "sarif"])
        got = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert got["version"] == "2.1.0"
        assert got["runs"][0]["results"] == []

    def test_prune_baseline_drops_stale_entries_only(self, tmp_path,
                                                     capsys):
        # Nothing in the repo matches the ghost entry, so a full run
        # prunes it; the write goes to the temp path, not the real
        # baseline.
        path = tmp_path / "base.json"
        path.write_text(json.dumps({"version": 1, "findings": [
            {"rule": "ghost-rule", "path": "ray_tpu/nope.py",
             "context": "x = 1", "justification": "long gone"}]}))
        rc = lint_main(["--baseline", str(path), "--prune-baseline"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "1 stale entries removed, 0 kept" in out
        assert json.loads(path.read_text())["findings"] == []

    def test_prune_baseline_refuses_partial_runs(self, capsys):
        rc = lint_main([os.path.join(FIXTURES, "objectref_clean.py"),
                        "--prune-baseline"])
        assert rc == 2
        assert "full unfiltered run" in capsys.readouterr().err


class TestLintBudget:
    def test_full_package_run_under_30s(self, package_run):
        """The budget as work, not seconds (30 s of CPU held alone, where
        the run takes 21; under six workers the same run read 39.9): a
        full run shows each module to each pass once, and costs under 5
        times what parsing the same sources once and walking them once a
        pass costs in this process under this load (3.2 times alone,
        21 s against 6.3: the bound leaves what 30 s left)."""
        run = package_run
        passes = {p for p, _ in run.visits}
        seen = {m for _, m in run.visits}
        assert passes == set(registered_passes()) and len(passes) >= 16
        assert seen == {m.relpath for m in run.modules
                        if getattr(m, "parse_error", None) is None}
        assert len(seen) > 200 and run.nodes > 300_000
        assert set(run.visits.values()) == {1}
        assert len(run.visits) == len(passes) * len(seen)
        ratio = run.lint_cpu_s / run.parse_and_walk_cpu_s
        assert ratio < 5.0, (
            f"lint took {run.lint_cpu_s:.1f}s CPU, {ratio:.1f} times a "
            f"parse and a walk a pass of its sources")


class TestCheckMetricsShim:
    """scripts/check_metrics.py stays a working thin shim."""

    def _shim(self):
        path = os.path.join(REPO, "scripts", "check_metrics.py")
        spec = importlib.util.spec_from_file_location("check_metrics",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_check_paths_flags_fixture(self):
        problems = self._shim().check_paths(FIXTURES)
        text = "\n".join(problems)
        assert "ServeRequests" in text
        assert "_seconds" in text

    def test_check_exposition_text(self):
        shim = self._shim()
        bad = "# TYPE foo_total gauge\n# TYPE bar counter\n"
        problems = shim.check_exposition_text(bad, "inline")
        assert len(problems) == 2
        assert shim.check_exposition_text(
            "# TYPE ok_total counter\n", "inline") == []
