"""Static analysis (mxlint) + dynamic engine race detector
(docs/static_analysis.md).

Lint rules are tested against small fixture snippets written to
tmp_path — one must-flag and one must-pass case per rule — plus the
pragma and baseline machinery.  The final lint test pins the real
package at zero findings, which is what lets the CI ``lint`` stage run
with an empty baseline.

The race-detector tests seed real declaration bugs (an engine op that
touches an NDArray it did not declare) and assert they are caught on
the synchronous and threaded engines, and that clean engine/bulking
runs report zero violations.
"""
import os
import subprocess
import sys
import textwrap

import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import engine, profiler
from incubator_mxnet_tpu.analysis import mxlint, race
from incubator_mxnet_tpu.error import EngineRaceError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "incubator_mxnet_tpu")


# ---------------------------------------------------------------------------
# lint helpers
# ---------------------------------------------------------------------------

def _lint_src(tmp_path, src, name="snippet.py", **kwargs):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return mxlint.lint_paths([str(p)], repo_root=str(tmp_path), **kwargs)


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# MX-TIME001 — monotonic-clock discipline
# ---------------------------------------------------------------------------

def test_time001_flags_wall_clock(tmp_path):
    fs = _lint_src(tmp_path, """
        import time
        def deadline(t):
            return time.time() + t
    """)
    assert _rules(fs) == ["MX-TIME001"]


def test_time001_passes_monotonic_and_aliased_import(tmp_path):
    assert _lint_src(tmp_path, """
        import time
        def deadline(t):
            return time.monotonic() + t
    """) == []
    # 'from time import time' must still be caught through the alias
    fs = _lint_src(tmp_path, """
        from time import time as now
        def deadline(t):
            return now() + t
    """)
    assert _rules(fs) == ["MX-TIME001"]


def test_time001_pragma_needs_reason(tmp_path):
    ok = _lint_src(tmp_path, """
        import time
        stamp = time.time()  # mxlint: allow-wall-clock(log timestamps are wall-clock by design)
    """)
    assert ok == []
    empty_reason = _lint_src(tmp_path, """
        import time
        stamp = time.time()  # mxlint: allow-wall-clock( )
    """)
    assert _rules(empty_reason) == ["MX-TIME001"]


# ---------------------------------------------------------------------------
# MX-EXC001 — broad except must not swallow typed errors
# ---------------------------------------------------------------------------

def test_exc001_flags_swallowing_handler(tmp_path):
    fs = _lint_src(tmp_path, """
        def f():
            try:
                g()
            except Exception:
                pass
    """)
    assert _rules(fs) == ["MX-EXC001"]


def test_exc001_bare_except_and_baseexception_flag(tmp_path):
    fs = _lint_src(tmp_path, """
        def f():
            try:
                g()
            except:
                pass
        def h():
            try:
                g()
            except BaseException:
                return None
    """)
    assert _rules(fs) == ["MX-EXC001", "MX-EXC001"]


def test_exc001_reraise_passes(tmp_path):
    assert _lint_src(tmp_path, """
        def f():
            try:
                g()
            except Exception as e:
                raise RuntimeError("wrapped") from e
    """) == []


def test_exc001_pragma_suppresses(tmp_path):
    assert _lint_src(tmp_path, """
        def f():
            try:
                g()
            except Exception:  # mxlint: allow-broad-except(best-effort probe)
                pass
    """) == []


def test_exc001_inner_pragma_does_not_cover_outer(tmp_path):
    # a pragma belongs to its own handler's header line: an annotated
    # handler nested in the body must not silence the outer one
    fs = _lint_src(tmp_path, """
        try:
            pass
        except Exception:
            try:
                pass
            except Exception:  # mxlint: allow-broad-except(inner justified)
                pass
    """)
    assert _rules(fs) == ["MX-EXC001"]
    assert fs[0].line == 4


def test_exc001_pragma_reason_may_contain_parens(tmp_path):
    assert _lint_src(tmp_path, """
        try:
            pass
        except Exception:  # mxlint: allow-broad-except(best-effort (see rationale above))
            pass
    """) == []


def test_exc001_raise_in_nested_def_does_not_count(tmp_path):
    # a raise inside a nested def/lambda runs later (if ever) — the
    # handler itself still swallows
    fs = _lint_src(tmp_path, """
        try:
            pass
        except Exception:
            def _cb():
                raise RuntimeError("later")
            register(_cb)
    """)
    assert _rules(fs) == ["MX-EXC001"]


def test_exc001_narrow_handler_passes(tmp_path):
    assert _lint_src(tmp_path, """
        def f():
            try:
                g()
            except (OSError, ValueError):
                pass
    """) == []


# ---------------------------------------------------------------------------
# MX-FAULT001/002 — injection-point registry wiring
# ---------------------------------------------------------------------------

def test_fault001_undeclared_point_flags(tmp_path):
    fs = _lint_src(tmp_path, """
        from incubator_mxnet_tpu import fault
        def f():
            fault.inject("kvstore.sned")   # typo'd point
    """, fault_points={"kvstore.send": 1})
    assert _rules(fs) == ["MX-FAULT001"]
    assert "kvstore.sned" in fs[0].message


def test_fault001_declared_point_passes(tmp_path):
    assert _lint_src(tmp_path, """
        from incubator_mxnet_tpu import fault
        def f():
            fault.inject("kvstore.send", detail="x")
    """, fault_points={"kvstore.send": 1}) == []


def test_inject_enforces_registry_at_runtime():
    """The static FAULT001 rule has a runtime twin: while a spec is
    active, inject() with an undeclared point raises instead of
    silently never firing."""
    from incubator_mxnet_tpu import fault
    fault.configure("engine.push:error:p=0.0:seed=1")
    try:
        with pytest.raises(ValueError, match="undeclared"):
            fault.inject("not.a.point")
        fault.inject("kvstore.send")  # declared, p=0 elsewhere: no-op
    finally:
        fault.reset()
    assert "engine.push" in fault.declared_points()


def test_fault002_dead_point_flags_whole_surface(tmp_path):
    # FAULT002 needs a directory scan plus a fault.py declaring POINTS
    (tmp_path / "fault.py").write_text(
        'POINTS = ("used.point", "dead.point")\n')
    (tmp_path / "user.py").write_text(
        'from fault import inject\n'
        'def f():\n'
        '    inject("used.point")\n')
    fs = mxlint.lint_paths([str(tmp_path)], repo_root=str(tmp_path))
    assert _rules(fs) == ["MX-FAULT002"]
    assert "dead.point" in fs[0].message


# ---------------------------------------------------------------------------
# MX-ENV001/002 — env var <-> docs sync
# ---------------------------------------------------------------------------

def _docs(tmp_path, rows):
    docs = tmp_path / "env_vars.md"
    body = "| Variable | Default | Meaning |\n|---|---|---|\n"
    body += "".join(f"| `{v}` | unset | a knob |\n" for v in rows)
    docs.write_text(body)
    return str(docs)


def test_env001_undocumented_read_flags(tmp_path):
    (tmp_path / "mod.py").write_text(
        'from incubator_mxnet_tpu.base import get_env\n'
        'FLAG = get_env("MXNET_SECRET_KNOB", 0, int)\n')
    docs = _docs(tmp_path, ["MXNET_OTHER"])
    fs = mxlint.lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                           docs_path=docs)
    assert sorted(_rules(fs)) == ["MX-ENV001", "MX-ENV002"]
    by_rule = {f.rule: f for f in fs}
    assert "MXNET_SECRET_KNOB" in by_rule["MX-ENV001"].message
    assert "MXNET_OTHER" in by_rule["MX-ENV002"].message


def test_env_rules_documented_read_passes(tmp_path):
    (tmp_path / "mod.py").write_text(
        'import os\n'
        'A = os.environ.get("MXNET_KNOB_A", "1")\n'
        'B = os.getenv("MXNET_KNOB_B")\n'
        'C = os.environ["MXNET_KNOB_C"]\n')
    docs = _docs(tmp_path, ["MXNET_KNOB_A", "MXNET_KNOB_B", "MXNET_KNOB_C"])
    assert mxlint.lint_paths([str(tmp_path)], repo_root=str(tmp_path),
                             docs_path=docs) == []


def test_env_rules_skip_single_file_scan(tmp_path):
    # whole-surface rules must not fire when only files are scanned —
    # "never read anywhere" is meaningless for one file
    (tmp_path / "mod.py").write_text(
        'import os\nA = os.getenv("MXNET_UNDOC")\n')
    docs = _docs(tmp_path, [])
    assert mxlint.lint_paths([str(tmp_path / "mod.py")],
                             repo_root=str(tmp_path), docs_path=docs) == []


# ---------------------------------------------------------------------------
# MX-BULK001 — bulkable op purity
# ---------------------------------------------------------------------------

def test_bulk001_host_effect_in_bulkable_op_flags(tmp_path):
    fs = _lint_src(tmp_path, """
        from registry import register
        @register("debug_op", bulkable=True)
        def debug_op(x):
            print("side effect")
            return x
    """)
    assert _rules(fs) == ["MX-BULK001"]
    assert "print" in fs[0].message


def test_bulk001_default_bulkable_from_jittable(tmp_path):
    # registry defaulting: bulkable defaults to jittable (default True)
    fs = _lint_src(tmp_path, """
        from registry import register
        @register("implicit")
        def implicit(x):
            return x.asnumpy()
    """)
    assert _rules(fs) == ["MX-BULK001"]


def test_bulk001_optout_passes(tmp_path):
    assert _lint_src(tmp_path, """
        from registry import register
        @register("host_op", bulkable=False)
        def host_op(x):
            print("fine: never deferred")
            return x
        @register("host_op2", jittable=False)
        def host_op2(x):
            return x.asnumpy()
    """) == []


# ---------------------------------------------------------------------------
# MX-LOCK001 — lock-order cycles
# ---------------------------------------------------------------------------

def test_lock001_opposite_order_flags(tmp_path):
    fs = _lint_src(tmp_path, """
        class T:
            def ab(self):
                with self.a_lock:
                    with self.b_lock:
                        pass
            def ba(self):
                with self.b_lock:
                    with self.a_lock:
                        pass
    """)
    assert _rules(fs) == ["MX-LOCK001"]


def test_lock001_consistent_order_passes(tmp_path):
    assert _lint_src(tmp_path, """
        class T:
            def ab(self):
                with self.a_lock:
                    with self.b_lock:
                        pass
            def ab2(self):
                with self.a_lock:
                    with self.b_lock:
                        pass
    """) == []


def test_lock001_cycle_through_call_flags(tmp_path):
    # the cycle closes through a same-module call made while holding a
    # lock — the transitive acquire-set of the callee matters
    fs = _lint_src(tmp_path, """
        class T:
            def outer(self):
                with self.a_lock:
                    self.helper()
            def helper(self):
                with self.b_lock:
                    pass
            def reversed(self):
                with self.b_lock:
                    with self.a_lock:
                        pass
    """)
    assert _rules(fs) == ["MX-LOCK001"]


def test_lock001_cycle_through_with_item_guard_flags(tmp_path):
    # the cycle closes through a guard CALL in a with-item: the call
    # runs while the outer lock is held, so its transitive acquires
    # are edges too
    fs = _lint_src(tmp_path, """
        def guard():
            with g.b_lock:
                pass
        def fwd():
            with g.a_lock:
                with guard():
                    pass
        def rev():
            with g.b_lock:
                with g.a_lock:
                    pass
    """)
    assert _rules(fs) == ["MX-LOCK001"]


def test_lock001_same_basename_modules_not_merged(tmp_path):
    # a/mod.py and b/mod.py share a basename; their lock graphs must
    # stay separate — a cross-file merge fabricates this "cycle"
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    (tmp_path / "a" / "mod.py").write_text(textwrap.dedent("""
        def f(x, y):
            with x.a_lock:
                with y.b_lock:
                    pass
    """))
    (tmp_path / "b" / "mod.py").write_text(textwrap.dedent("""
        def g(x, y):
            with x.b_lock:
                with y.a_lock:
                    pass
    """))
    fs = mxlint.lint_paths([str(tmp_path / "a" / "mod.py"),
                            str(tmp_path / "b" / "mod.py")],
                           repo_root=str(tmp_path))
    assert _rules(fs) == []


# ---------------------------------------------------------------------------
# MX-AST000, generic disable pragma, baseline
# ---------------------------------------------------------------------------

def test_ast000_syntax_error(tmp_path):
    fs = _lint_src(tmp_path, "def broken(:\n")
    assert _rules(fs) == ["MX-AST000"]


def test_generic_disable_pragma(tmp_path):
    assert _lint_src(tmp_path, """
        import time
        t = time.time()  # mxlint: disable=MX-TIME001(bench wall-clock stamp)
    """) == []


def test_baseline_split(tmp_path):
    fs = _lint_src(tmp_path, """
        import time
        a = time.time()
    """)
    assert len(fs) == 1
    base = {fs[0].key: "known since PR 3"}
    regressions, suppressed, stale = mxlint.apply_baseline(fs, base)
    assert regressions == [] and len(suppressed) == 1 and stale == []
    # a fixed finding leaves its baseline entry stale
    regressions, suppressed, stale = mxlint.apply_baseline([], base)
    assert stale == [fs[0].key]


def test_baseline_stub_reason_does_not_suppress(tmp_path):
    # baseline entries need a written reason exactly like pragmas: the
    # TODO stub --write-baseline emits must keep the finding live
    fs = _lint_src(tmp_path, """
        import time
        a = time.time()
    """)
    for stub in ("TODO: justify or fix", "", "   "):
        regressions, suppressed, _ = mxlint.apply_baseline(
            fs, {fs[0].key: stub})
        assert len(regressions) == 1 and suppressed == [], stub


# ---------------------------------------------------------------------------
# MX-DONATE001 — jit/pjit sites must decide donation
# ---------------------------------------------------------------------------

def _lint_pkg_src(tmp_path, src, name="mod.py"):
    """Write the snippet under a fake incubator_mxnet_tpu/ so the
    package-scoped MX-DONATE001 applies."""
    pkg = tmp_path / "incubator_mxnet_tpu"
    pkg.mkdir(exist_ok=True)
    p = pkg / name
    p.write_text(textwrap.dedent(src))
    return mxlint.lint_paths([str(p)], repo_root=str(tmp_path))


def test_donate001_flags_bare_jit(tmp_path):
    fs = _lint_pkg_src(tmp_path, """
        import jax
        f = jax.jit(lambda x: x + 1)
        g = pjit(lambda x: x * 2)
    """)
    # the bare pjit additionally draws MX-SHARD001: no sharding decision
    assert _rules(fs) == ["MX-DONATE001", "MX-DONATE001", "MX-SHARD001"]


def test_donate001_keyword_presence_passes(tmp_path):
    # a conditional donate_argnums value is still a decision, and
    # donate_argnames counts too
    assert _lint_pkg_src(tmp_path, """
        import jax
        f = jax.jit(lambda p, x: p, donate_argnums=(0,))
        g = jax.jit(lambda p, x: p,
                    donate_argnums=(0,) if True else ())
        h = jax.jit(lambda p, x: p, donate_argnames=("p",))
    """) == []


def test_donate001_pragma_suppresses_with_reason(tmp_path):
    assert _lint_pkg_src(tmp_path, """
        import jax
        f = jax.jit(lambda x: x + 1)  # mxlint: disable=MX-DONATE001(inputs are caller-held activations)
    """) == []
    fs = _lint_pkg_src(tmp_path, """
        import jax
        f = jax.jit(lambda x: x + 1)  # mxlint: disable=MX-DONATE001()
    """)
    assert _rules(fs) == ["MX-DONATE001"]


def test_donate001_outside_package_not_flagged(tmp_path):
    fs = _lint_src(tmp_path, """
        import jax
        f = jax.jit(lambda x: x + 1)
    """, name="bench_snippet.py")
    assert "MX-DONATE001" not in _rules(fs)


def test_donate001_method_named_jit_not_flagged(tmp_path):
    assert _lint_pkg_src(tmp_path, """
        class C:
            def jit(self, fn):
                return fn
        c = C()
        f = c.jit(lambda x: x)
    """) == []


# ---------------------------------------------------------------------------
# MX-SHARD001 — shard_map/pjit sites must decide placement
# ---------------------------------------------------------------------------

def test_shard001_flags_bare_shard_map(tmp_path):
    fs = _lint_pkg_src(tmp_path, """
        from jax import shard_map
        f = shard_map(lambda x: x)
        g = jax.pjit(lambda x: x, donate_argnums=(0,))
    """)
    assert _rules(fs) == ["MX-SHARD001", "MX-SHARD001"]


def test_shard001_explicit_sharding_passes(tmp_path):
    # keyword spelling, positional spelling, and in_shardings all count
    assert "MX-SHARD001" not in _rules(_lint_pkg_src(tmp_path, """
        from jax import shard_map
        f = shard_map(body, mesh=mesh, in_specs=specs, out_specs=out)
        g = shard_map(body, mesh, specs, out)
        h = jax.pjit(fn, in_shardings=s, out_shardings=s,
                     donate_argnums=(0,))
    """))


def test_shard001_pragma_and_scope(tmp_path):
    assert "MX-SHARD001" not in _rules(_lint_pkg_src(tmp_path, """
        f = shard_map(body)  # mxlint: disable=MX-SHARD001(ambient mesh installed by caller)
    """))
    # outside the package the rule does not apply
    fs = _lint_src(tmp_path, """
        f = shard_map(lambda x: x)
    """, name="bench_snippet.py")
    assert "MX-SHARD001" not in _rules(fs)


# ---------------------------------------------------------------------------
# --prune-stale — the baseline shrinks back by command
# ---------------------------------------------------------------------------

def test_prune_stale_baseline(tmp_path):
    import json
    base = tmp_path / "baseline.json"
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nt = time.time()\n")
    gone = tmp_path / "gone.py"          # scanned, clean: entry is stale
    gone.write_text("x = 1\n")
    bad_rel = os.path.relpath(str(bad), REPO)
    gone_rel = os.path.relpath(str(gone), REPO)
    live = {"rule": "MX-TIME001", "file": bad_rel,
            "message": "time.time() is wall-clock: an NTP step skews "
                       "timeout/deadline/duration math — use "
                       "time.monotonic() (or pragma allow-wall-clock "
                       "with a reason)",
            "reason": "seeded fixture"}
    stale = {"rule": "MX-TIME001", "file": gone_rel,
             "message": "whatever", "reason": "obsolete"}
    # NOT scanned this run: must survive the prune (a partial run must
    # not delete the rest of the tree's justified entries)
    out_of_scope = {"rule": "MX-TIME001", "file": "elsewhere/mod.py",
                    "message": "whatever", "reason": "still justified"}
    base.write_text(json.dumps({"findings": [live, stale, out_of_scope]}))
    cli = os.path.join(REPO, "tools", "mxlint.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, cli, str(bad), str(gone), "--baseline",
         str(base), "--prune-stale"],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "pruned 1 stale" in proc.stdout
    kept = json.loads(base.read_text())["findings"]
    assert sorted(e["file"] for e in kept) == \
        sorted([bad_rel, "elsewhere/mod.py"])
    # idempotent in scope: a second run prunes nothing more and the
    # live entry still suppresses
    proc2 = subprocess.run(
        [sys.executable, cli, str(bad), str(gone), "--baseline",
         str(base), "--prune-stale"],
        capture_output=True, text=True, env=env)
    assert proc2.returncode == 0
    assert "pruned 0 stale" in proc2.stdout or "pruned" not in proc2.stdout


# ---------------------------------------------------------------------------
# MX-FLIGHT001 — flight-recorder event vocabulary
# ---------------------------------------------------------------------------

_FLIGHT_VOCAB = """
    EVENTS = (
        "replica.exited",
        "scale.apply",
    )
    EVENT_PREFIXES = ("fault.",)
    HEALTH = "health"
    def record(category, name, **fields):
        pass
"""


def _lint_flight(tmp_path, consumer_src):
    (tmp_path / "flightrec.py").write_text(textwrap.dedent(_FLIGHT_VOCAB))
    (tmp_path / "consumer.py").write_text(textwrap.dedent(consumer_src))
    return mxlint.lint_paths([str(tmp_path)], repo_root=str(tmp_path))


def test_flight001_flags_unregistered_record_name(tmp_path):
    fs = _lint_flight(tmp_path, """
        from . import flightrec
        def bail():
            flightrec.record(flightrec.HEALTH, "replica.exitted")
    """)
    assert _rules(fs) == ["MX-FLIGHT001"]
    assert "replica.exitted" in fs[0].message


def test_flight001_passes_registered_and_prefix_family(tmp_path):
    assert _lint_flight(tmp_path, """
        from . import flightrec
        def bail(point):
            flightrec.record(flightrec.HEALTH, "replica.exited")
            flightrec.record(flightrec.HEALTH, f"fault.{point}")
    """) == []


def test_flight001_flags_dynamic_name_outside_prefix_families(tmp_path):
    fs = _lint_flight(tmp_path, """
        from . import flightrec
        def bail(what):
            flightrec.record(flightrec.HEALTH, f"replica.{what}")
    """)
    assert _rules(fs) == ["MX-FLIGHT001"]


def test_flight001_flags_unregistered_gate_names(tmp_path):
    # both postmortem-gate shapes: the argv pair and the gate= kwarg
    fs = _lint_flight(tmp_path, """
        def run(pm, incidents):
            import subprocess
            subprocess.run([pm, "--gate", "scale.apply,scale.aply"])
            incidents(gate="replica.exited,replica.gone")
    """)
    assert _rules(fs) == ["MX-FLIGHT001", "MX-FLIGHT001"]
    assert "scale.aply" in fs[0].message
    assert "replica.gone" in fs[1].message


def test_flight001_pragma_needs_reason(tmp_path):
    assert _lint_flight(tmp_path, """
        from . import flightrec
        def bail():
            flightrec.record(flightrec.HEALTH, "no.such.event")  # mxlint: disable=MX-FLIGHT001(fixture: asserting the gate FAILS on this name)
    """) == []
    fs = _lint_flight(tmp_path, """
        from . import flightrec
        def bail():
            flightrec.record(flightrec.HEALTH, "no.such.event")  # mxlint: disable=MX-FLIGHT001()
    """)
    assert _rules(fs) == ["MX-FLIGHT001"]


def test_flight001_real_vocabulary_covers_all_emits_and_gates():
    # the package + tests/benchmark gate surface is clean against the
    # real flightrec.EVENTS — what lets the CI locklint/lint stages
    # enforce the registry with no baseline
    from incubator_mxnet_tpu import flightrec
    assert "lock.order_violation" in flightrec.EVENTS
    assert "fault." in flightrec.EVENT_PREFIXES


# ---------------------------------------------------------------------------
# the repo itself is clean — what lets CI run with an empty baseline
# ---------------------------------------------------------------------------

def test_package_is_lint_clean():
    fs = mxlint.lint_paths([PKG], repo_root=REPO)
    assert fs == [], "\n" + mxlint.render(fs)


def test_cli_exit_codes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cli = os.path.join(REPO, "tools", "mxlint.py")
    # seeded wall-clock bug -> nonzero exit (the CI failure mode)
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nt = time.time()\n")
    proc = subprocess.run([sys.executable, cli, str(bad)],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 1 and "MX-TIME001" in proc.stdout
    # clean file -> zero
    good = tmp_path / "good.py"
    good.write_text("import time\nt = time.monotonic()\n")
    proc = subprocess.run([sys.executable, cli, str(good)],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_catches_seeded_undeclared_env_var(tmp_path):
    """Acceptance probe: an MXNET_* read with no env_vars.md row must
    fail a whole-surface scan — the same configuration the CI lint
    stage runs with."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'import os\nX = os.getenv("MXNET_TOTALLY_NEW_KNOB")\n')
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "env_vars.md").write_text("| Variable | Meaning |\n|---|---|\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         str(pkg), "--docs", str(docs / "env_vars.md")],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 1
    assert "MX-ENV001" in proc.stdout
    assert "MXNET_TOTALLY_NEW_KNOB" in proc.stdout


# ---------------------------------------------------------------------------
# dynamic race detector
# ---------------------------------------------------------------------------

@pytest.fixture
def race_on():
    prev = race.set_enabled(True)
    race.clear()
    yield
    race.clear()
    race.set_enabled(prev)


def _var(arr):
    return arr._chunk.var


def test_naive_engine_catches_undeclared_write(race_on):
    eng = engine.NaiveEngine()
    a = mx.nd.ones((2, 2))
    b = mx.nd.zeros((2, 2))
    with pytest.raises(EngineRaceError, match="mutable_vars"):
        # seeded bug: writes b but declares only a
        eng.push(lambda: b.__setitem__(slice(None), 5.0),
                 const_vars=(_var(a),), name="bad_write")
    assert race.stats()["undeclared_write"] == 1


def test_naive_engine_catches_undeclared_read(race_on):
    eng = engine.NaiveEngine()
    a = mx.nd.ones((2, 2))
    with pytest.raises(EngineRaceError, match="const_vars"):
        eng.push(lambda: a.data, name="bad_read")
    assert race.stats()["undeclared_read"] == 1


def test_naive_engine_declared_ops_clean(race_on):
    eng = engine.NaiveEngine()
    a = mx.nd.ones((2, 2))
    b = mx.nd.zeros((2, 2))
    eng.push(lambda: b.__setitem__(slice(None), a.data + 1),
             const_vars=(_var(a),), mutable_vars=(_var(b),), name="axpy")
    s = race.stats()
    assert s["ops_checked"] == 1 and s["violations"] == 0
    assert b.asnumpy()[0, 0] == 2.0


def test_op_local_arrays_exempt(race_on):
    """NDArrays created inside the closure are op-local: nothing else
    can schedule against them, so they need no declaration."""
    eng = engine.NaiveEngine()
    eng.push(lambda: mx.nd.ones((2, 2)).data, name="fresh")
    assert race.stats()["violations"] == 0


def test_threaded_engine_banks_and_rethrows_at_wait(race_on):
    eng = engine.ThreadedEngine(num_workers=2)
    a = mx.nd.ones((2, 2))
    eng.push(lambda: a.data, name="bad_read")   # undeclared
    with pytest.raises(EngineRaceError, match="bad_read"):
        eng.wait_for_all()
    # rethrow drains the pending list — the next wait is clean
    eng.wait_for_all()
    assert race.stats()["pending"] == 0


def test_threaded_engine_clean_run_zero_violations(race_on):
    eng = engine.ThreadedEngine(num_workers=4)
    arrs = [mx.nd.ones((4,)) for _ in range(8)]
    out = mx.nd.zeros((4,))
    for x in arrs:
        eng.push(lambda x=x: x.data, const_vars=(_var(x),), name="read")
    eng.push(lambda: out.__setitem__(slice(None), 1.0),
             mutable_vars=(_var(out),), name="write")
    eng.wait_for_all()
    s = race.stats()
    assert s["ops_checked"] == 9 and s["violations"] == 0


def test_undeclared_read_counts_once_despite_version_bump(race_on):
    # one missing declaration is one violation: the version-stability
    # check must not re-report an already-undeclared read
    eng = engine.NaiveEngine()
    a = mx.nd.ones((2, 2))
    var = _var(a)

    def bad():
        _ = a.data
        var._version += 1  # a concurrent writer interleaving

    with pytest.raises(EngineRaceError, match="const_vars"):
        eng.push(bad, name="bad_read_bumped")
    s = race.stats()
    assert s["undeclared_read"] == 1
    assert s["write_after_read"] == 0
    assert s["violations"] == 1


def test_naive_engine_pops_record_on_base_exception(race_on):
    # KeyboardInterrupt must not leak the op record on the TLS stack —
    # a leaked record would absorb every later access on this thread
    eng = engine.NaiveEngine()

    def boom():
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        eng.push(boom, name="interrupted")
    assert not race._stack()
    assert race.stats()["ops_checked"] == 1
    # later accesses are not attributed to the dead record
    _ = mx.nd.ones((2, 2)).asnumpy()
    assert race.stats()["violations"] == 0


def test_naive_engine_drains_banked_violation_at_wait(race_on):
    # a violation banked on the BaseException path surfaces at THIS
    # engine's next wait, not at some unrelated later engine's
    eng = engine.NaiveEngine()
    a = mx.nd.ones((2, 2))

    def rogue_then_interrupt():
        a[:] = 3.0            # undeclared write
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        eng.push(rogue_then_interrupt, name="rogue")
    assert race.stats()["pending"] == 1
    with pytest.raises(EngineRaceError, match="rogue"):
        eng.wait_for_all()
    assert race.stats()["pending"] == 0


def test_disable_clears_banked_violations(race_on):
    # a violation banked but never drained must not resurface at the
    # first wait of a later enabled epoch
    eng = engine.ThreadedEngine(num_workers=2)
    a = mx.nd.ones((2, 2))
    eng.push(lambda: a.data, name="bad_read")   # undeclared, banked
    import time as _t
    deadline = _t.monotonic() + 5
    while race.stats()["pending"] == 0 and _t.monotonic() < deadline:
        _t.sleep(0.01)
    assert race.stats()["pending"] == 1
    race.set_enabled(False)
    race.set_enabled(True)
    eng.wait_for_all()                           # clean: nothing stale
    assert race.stats()["pending"] == 0


def test_native_engine_no_false_hazard_from_queued_writer(race_on):
    # pushing a writer while a declared reader is mid-op must not make
    # the reader see a write-after-read hazard: python-side versions
    # bump at op completion (C-serialized), not at push
    from incubator_mxnet_tpu import native
    if not native.available():
        pytest.skip("native runtime unavailable")
    eng = engine.NativeEngine(num_workers=2)
    prev = engine.get_engine()
    engine.set_engine(eng)   # the array's var must be a native var
    try:
        a = mx.nd.ones((2, 2))
        var = _var(a)
        import threading
        reader_in = threading.Event()
        release = threading.Event()

        def reader():
            _ = a.data
            reader_in.set()
            release.wait(5)

        eng.push(reader, const_vars=(var,), name="reader")
        assert reader_in.wait(5)
        # queued behind the reader; under push-time bumping this alone
        # flipped var._version and framed the reader
        eng.push(lambda: a.__setitem__(slice(None), 2.0),
                 mutable_vars=(var,), name="writer")
        release.set()
        eng.wait_for_all()
        s = race.stats()
        assert s["write_after_read"] == 0 and s["violations"] == 0
        assert s["ops_checked"] >= 2
    finally:
        engine.set_engine(prev)


def test_write_after_read_hazard_detected(race_on):
    """A var an op read (without owning it) changing version before the
    op finished means a concurrent write really interleaved."""
    eng = engine.get_engine()
    var = eng.new_variable("hazard")
    rec = race.begin("reader", (var,), ())
    race.note_read(var)
    var._version += 1          # the interleaved writer
    with pytest.raises(EngineRaceError, match="version"):
        race.finish(rec, collect=False)
    assert race.stats()["write_after_read"] == 1


def test_flag_off_is_inert():
    prev = race.set_enabled(False)
    try:
        race.clear()
        eng = engine.NaiveEngine()
        a = mx.nd.ones((2, 2))
        eng.push(lambda: a.data, name="undeclared_but_unchecked")
        assert race.stats() == {"ops_checked": 0, "violations": 0,
                                "undeclared_write": 0, "undeclared_read": 0,
                                "write_after_read": 0, "pending": 0,
                                "enabled": 0}
    finally:
        race.set_enabled(prev)


def test_profiler_stats_provider_registered_while_on(race_on):
    assert "race_check" in profiler.provider_stats()
    ps = profiler.provider_stats()["race_check"]
    assert ps["enabled"] == 1
    race.set_enabled(False)
    assert "race_check" not in profiler.provider_stats()
    race.set_enabled(True)  # race_on fixture tears down


def test_bulking_stress_clean_under_race_check(race_on):
    """Eager bulked arithmetic (ops/bulking.py segments) must not trip
    the detector: segment flush materialization is not an engine op."""
    from incubator_mxnet_tpu.ops import bulking
    with bulking.bulk_scope(True):
        x = mx.nd.ones((8, 8))
        for _ in range(12):
            x = x * 1.5 + 0.25
        val = x.asnumpy()
    assert val.shape == (8, 8)
    assert race.stats()["violations"] == 0
