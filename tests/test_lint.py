"""The invariant lint suite (volcano_tpu/lint/,
docs/design/static_analysis.md): every rule proven to FIRE on a
violating fixture snippet and stay QUIET on the fixed form, pragma and
baseline mechanics (incl. stale-entry detection), and the whole-repo
run pinned at ZERO findings — from this PR on, tier-1 enforces the
clock / lock / native-fallback / randomness / jit-purity contracts."""

from __future__ import annotations

import os
import textwrap

import pytest

from volcano_tpu.lint import run_lint
from volcano_tpu.lint.rules import (ClockDisciplineRule, JitPurityRule,
                                    LockDisciplineRule,
                                    NativeFallbackParityRule,
                                    SeededRandomnessRule)
from volcano_tpu.lint.runner import main as lint_main


def write(root, relpath: str, content: str) -> None:
    path = root / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(content))


def lint(tmp_path, rules, tests_dir=None):
    """Run ``rules`` over the fixture package at tmp_path/pkg with an
    empty (absent) baseline."""
    findings, _ = run_lint(str(tmp_path / "pkg"),
                           tests_dir=str(tests_dir) if tests_dir else None,
                           rules=rules,
                           baseline_path=str(tmp_path / "no_baseline"))
    return findings


# -- clock-discipline --------------------------------------------------------


def test_clock_rule_fires_on_wall_clock_read(tmp_path):
    write(tmp_path, "pkg/sim/engine.py", """
        import time

        def tick():
            return time.time()
    """)
    fs = lint(tmp_path, [ClockDisciplineRule()])
    assert len(fs) == 1 and fs[0].rule == "clock-discipline"
    assert "time.time" in fs[0].message and fs[0].line == 5


def test_clock_rule_catches_from_import_monotonic_and_datetime(tmp_path):
    write(tmp_path, "pkg/serving/hub.py", """
        from time import monotonic
        from datetime import datetime

        def now():
            return monotonic(), datetime.now()
    """)
    fs = lint(tmp_path, [ClockDisciplineRule()])
    assert {f.line for f in fs} == {2, 6}


def test_clock_rule_quiet_on_injected_clock_and_perf_counter(tmp_path):
    write(tmp_path, "pkg/sim/engine.py", """
        import time

        def tick(clock):
            t0 = time.perf_counter()       # duration telemetry: allowed
            now = clock.now()
            return now, (time.perf_counter() - t0)
    """)
    assert lint(tmp_path, [ClockDisciplineRule()]) == []


def test_clock_rule_out_of_scope_dirs_ignored(tmp_path):
    write(tmp_path, "pkg/utils/clock.py", """
        import time

        def now():
            return time.time()
    """)
    assert lint(tmp_path, [ClockDisciplineRule()]) == []


def test_clock_rule_pragma_with_reason_suppresses(tmp_path):
    write(tmp_path, "pkg/trace/t.py", """
        import time

        def export_ts():
            return time.time()   # lint: allow(clock-discipline): export metadata only
    """)
    assert lint(tmp_path, [ClockDisciplineRule()]) == []


def test_clock_rule_pragma_without_reason_is_its_own_finding(tmp_path):
    write(tmp_path, "pkg/trace/t.py", """
        import time

        def export_ts():
            return time.time()   # lint: allow(clock-discipline)
    """)
    fs = lint(tmp_path, [ClockDisciplineRule()])
    assert {f.rule for f in fs} == {"clock-discipline",
                                    "malformed-pragma"}


# -- lock-discipline ---------------------------------------------------------

_LOCK_SCOPES = {"store.py": {"locks": {"_lock"},
                             "guarded": {"_objects"}}}


def test_lock_rule_fires_on_unlocked_locked_call_and_mutation(tmp_path):
    write(tmp_path, "pkg/store.py", """
        class Store:
            def _append_locked(self, x):
                self._objects[x] = x

            def bad_call(self, x):
                self._append_locked(x)

            def bad_mutation(self, x):
                self._objects[x] = x
                self._objects.pop(x)
    """)
    fs = lint(tmp_path, [LockDisciplineRule(scopes=_LOCK_SCOPES)])
    assert len(fs) == 3
    assert {f.line for f in fs} == {7, 10, 11}


def test_lock_rule_quiet_under_with_lock_and_locked_callee(tmp_path):
    write(tmp_path, "pkg/store.py", """
        class Store:
            def __init__(self):
                self._objects = {}       # birth: no other thread yet

            def _append_locked(self, x):
                self._objects[x] = x     # callee contract: lock held

            def good(self, x):
                with self._lock:
                    self._append_locked(x)
                    del self._objects[x]
    """)
    assert lint(tmp_path, [LockDisciplineRule(scopes=_LOCK_SCOPES)]) == []


def test_lock_rule_closure_does_not_inherit_lock_scope(tmp_path):
    # a closure body runs LATER — lexically sitting inside `with
    # self._lock:` proves nothing about the lock at call time
    write(tmp_path, "pkg/store.py", """
        class Store:
            def sneaky(self, pool):
                with self._lock:
                    def later():
                        self._objects.clear()
                    pool.submit(later)
    """)
    fs = lint(tmp_path, [LockDisciplineRule(scopes=_LOCK_SCOPES)])
    assert len(fs) == 1 and "clear" in fs[0].message


def test_lock_rule_default_scope_covers_store_and_cache():
    scopes = LockDisciplineRule().scopes
    assert "apiserver/store.py" in scopes and "cache/cache.py" in scopes


# -- native-fallback-parity --------------------------------------------------

_FASTMODEL_C = """
static PyMethodDef methods[] = {
    {"fast_op", fast_op, METH_O, "doc"},
    {NULL, NULL, 0, NULL}
};
"""


def _native_fixture(tmp_path, py_body: str, test_body: str = "",
                    c_src: str = _FASTMODEL_C):
    (tmp_path / "pkg" / "native").mkdir(parents=True, exist_ok=True)
    (tmp_path / "pkg" / "native" / "fastmodel.c").write_text(c_src)
    write(tmp_path, "pkg/user.py", py_body)
    tests = tmp_path / "tests"
    tests.mkdir(exist_ok=True)
    (tests / "test_fixture.py").write_text(textwrap.dedent(test_body))
    return lint(tmp_path, [NativeFallbackParityRule()], tests_dir=tests)


def test_native_rule_fires_on_missing_call_site(tmp_path):
    fs = _native_fixture(tmp_path, "x = 1\n", "def test_parity(fm): fm.fast_op(1)")
    assert len(fs) == 1 and "no Python call site" in fs[0].message


def test_native_rule_fires_on_unguarded_call(tmp_path):
    fs = _native_fixture(tmp_path, """
        def run(fm, x):
            return fm.fast_op(x)
    """, "def test_parity(fm): fm.fast_op(1)")
    assert len(fs) == 1 and "without a fallback guard" in fs[0].message


def test_native_rule_fires_on_missing_test(tmp_path):
    fs = _native_fixture(tmp_path, """
        def run(fm, x):
            if fm is not None:
                return fm.fast_op(x)
            return x
    """)
    assert len(fs) == 1 and "no parity test naming" in fs[0].message


def test_native_rule_quiet_on_guarded_and_tested(tmp_path):
    fs = _native_fixture(tmp_path, """
        def run(fm, x):
            try:
                return fm.fast_op(x)
            except Exception:
                return x
    """, "def test_parity(fm): fm.fast_op(1)")
    assert fs == []


def test_native_rule_closure_under_guard_counts(tmp_path):
    # the store's batch_shard idiom: the closure only EXISTS when the
    # native module does — that's the fallback guard
    fs = _native_fixture(tmp_path, """
        def build(fm):
            shard = None
            if fm is not None and hasattr(fm, "fast_op"):
                def shard(x):
                    return fm.fast_op(x)
            return shard
    """, "def test_parity(fm): fm.fast_op(1)")
    assert fs == []


def test_native_rule_c_side_pragma_waives_entry(tmp_path):
    c = """
    /* lint: allow(native-fallback-parity, fast_op): test seam only */
    static PyMethodDef methods[] = {
        {"fast_op", fast_op, METH_O, "doc"},
        {NULL, NULL, 0, NULL}
    };
    """
    fs = _native_fixture(tmp_path, "x = 1\n", "", c_src=c)
    assert fs == []


# -- seeded-randomness -------------------------------------------------------


def test_randomness_rule_fires_on_global_rng(tmp_path):
    write(tmp_path, "pkg/sim/w.py", """
        import random
        import numpy as np

        def draw(xs):
            random.shuffle(xs)
            return random.random(), np.random.rand()
    """)
    fs = lint(tmp_path, [SeededRandomnessRule()])
    assert len(fs) == 3
    assert all(f.rule == "seeded-randomness" for f in fs)


def test_randomness_rule_fires_on_from_import_and_unseeded_rng(tmp_path):
    write(tmp_path, "pkg/ops/r.py", """
        from random import shuffle
        import numpy as np

        rng = np.random.default_rng()
    """)
    fs = lint(tmp_path, [SeededRandomnessRule()])
    assert {f.line for f in fs} == {2, 5}


def test_randomness_rule_catches_numpy_random_aliases(tmp_path):
    # `import numpy.random as npr` / `from numpy import random as nr`
    # bind the module directly — the draws are the same global RNG
    write(tmp_path, "pkg/sim/a.py", """
        import numpy.random as npr
        from numpy import random as nr

        def draw(xs):
            npr.shuffle(xs)
            return nr.random(), npr.default_rng()
    """)
    fs = lint(tmp_path, [SeededRandomnessRule()])
    assert {f.line for f in fs} == {6, 7}
    assert len(fs) == 3    # shuffle + random + seedless default_rng


def test_randomness_rule_quiet_on_seeded_generators(tmp_path):
    write(tmp_path, "pkg/sim/w.py", """
        import random
        import numpy as np

        def draw(seed, xs):
            rng = random.Random(seed)
            nrng = np.random.default_rng(seed)
            rng.shuffle(xs)
            return rng.random(), nrng.random()
    """)
    assert lint(tmp_path, [SeededRandomnessRule()]) == []


# -- jit-purity --------------------------------------------------------------


def test_jit_rule_fires_on_print_metrics_and_clock(tmp_path):
    write(tmp_path, "pkg/ops/kern.py", """
        import time

        import jax
        from ..metrics import metrics as m

        @jax.jit
        def kernel(x):
            print("tracing", x)
            m.inc("kernel_runs")
            t = time.perf_counter()
            return x * 2
    """)
    fs = lint(tmp_path, [JitPurityRule()])
    assert len(fs) == 3
    assert {f.line for f in fs} == {9, 10, 11}


def test_jit_rule_covers_shard_map_bodies_and_partial_jit(tmp_path):
    write(tmp_path, "pkg/ops/shard.py", """
        from functools import partial

        import jax
        from jax import shard_map

        def build(mesh):
            def body(x):
                print(x)
                return x
            return shard_map(body, mesh=mesh)

        @partial(jax.jit, static_argnames=("n",))
        def kern(x, n):
            print(n)
            return x
    """)
    fs = lint(tmp_path, [JitPurityRule()])
    assert {f.line for f in fs} == {9, 15}


def test_jit_rule_quiet_on_pure_kernel_and_host_telemetry(tmp_path):
    write(tmp_path, "pkg/ops/kern.py", """
        import time

        import jax
        from ..metrics import metrics as m

        @jax.jit
        def kernel(x):
            return x * 2

        def host_wrapper(x):
            t0 = time.perf_counter()       # host side: fine
            y = kernel(x)
            m.observe("kernel_ms", (time.perf_counter() - t0) * 1e3)
            return y
    """)
    assert lint(tmp_path, [JitPurityRule()]) == []


# -- baseline ----------------------------------------------------------------


def test_baseline_suppresses_then_goes_stale(tmp_path):
    write(tmp_path, "pkg/sim/e.py", """
        import time

        def tick():
            return time.time()
    """)
    rule = [ClockDisciplineRule()]
    fs = lint(tmp_path, rule)
    assert len(fs) == 1
    baseline = tmp_path / "baseline.txt"
    baseline.write_text(f"{fs[0].rule} {fs[0].path} {fs[0].line_crc}"
                        f"   # fixture waiver\n")
    fs2, _ = run_lint(str(tmp_path / "pkg"), tests_dir=None, rules=rule,
                      baseline_path=str(baseline))
    assert fs2 == []
    # fix the violation: the baseline entry must now FAIL the run
    write(tmp_path, "pkg/sim/e.py", """
        def tick(clock):
            return clock.now()
    """)
    fs3, _ = run_lint(str(tmp_path / "pkg"), tests_dir=None, rules=rule,
                      baseline_path=str(baseline))
    assert len(fs3) == 1 and fs3[0].rule == "stale-baseline"


def test_baseline_entry_not_stale_while_pragmad_violation_exists(tmp_path):
    # bulk-migration overlap: a still-present violation carrying an
    # inline pragma must not flip its baseline entry to stale
    write(tmp_path, "pkg/sim/e.py", """
        import time

        def tick():
            return time.time()
    """)
    rule = [ClockDisciplineRule()]
    fs = lint(tmp_path, rule)
    assert len(fs) == 1
    baseline = tmp_path / "baseline.txt"
    baseline.write_text(f"{fs[0].rule} {fs[0].path} {fs[0].line_crc}\n")
    # the standalone-pragma form leaves the violating LINE untouched,
    # so its baseline crc still matches (a trailing same-line pragma
    # changes the line content and retires the entry naturally)
    write(tmp_path, "pkg/sim/e.py", """
        import time

        def tick():
            # lint: allow(clock-discipline): migrating to inline pragmas
            return time.time()
    """)
    fs2, _ = run_lint(str(tmp_path / "pkg"), tests_dir=None, rules=rule,
                      baseline_path=str(baseline))
    assert fs2 == [], [f.render() for f in fs2]


def test_baseline_entries_scoped_to_rules_that_ran(tmp_path):
    # a --rule subset run computes no findings for the other rules;
    # their still-valid waivers must not be reported stale
    write(tmp_path, "pkg/sim/w.py", """
        import random

        def d():
            return random.random()
    """)
    fs = lint(tmp_path, [SeededRandomnessRule()])
    assert len(fs) == 1
    baseline = tmp_path / "baseline.txt"
    baseline.write_text(f"{fs[0].rule} {fs[0].path} {fs[0].line_crc}\n")
    fs2, _ = run_lint(str(tmp_path / "pkg"), tests_dir=None,
                      rules=[ClockDisciplineRule()],
                      baseline_path=str(baseline))
    assert fs2 == [], [f.render() for f in fs2]


def test_whole_file_findings_get_distinct_baseline_keys(tmp_path):
    # two line-0 findings on the same rule+path (e.g. two unnamed
    # native entries) must not collapse onto one baseline key — one
    # entry must not waive both
    c = """
    static PyMethodDef methods[] = {
        {"op_a", op_a, METH_O, "doc"},
        {"op_b", op_b, METH_O, "doc"},
        {NULL, NULL, 0, NULL}
    };
    """
    fs = _native_fixture(tmp_path, """
        def run(fm, x):
            if fm is not None:
                return fm.op_a(x), fm.op_b(x)
            return x, x
    """, c_src=c)
    assert len(fs) == 2     # op_a and op_b each lack a named test
    assert fs[0].line_crc != fs[1].line_crc


def test_baseline_rejects_malformed_entries(tmp_path):
    write(tmp_path, "pkg/sim/e.py", "x = 1\n")
    baseline = tmp_path / "baseline.txt"
    baseline.write_text("just-two tokens\n")
    with pytest.raises(ValueError, match="malformed baseline"):
        run_lint(str(tmp_path / "pkg"), tests_dir=None,
                 rules=[ClockDisciplineRule()],
                 baseline_path=str(baseline))


# -- the shipped tree --------------------------------------------------------


def _repo_package_root():
    import volcano_tpu
    return os.path.dirname(os.path.abspath(volcano_tpu.__file__))


def test_whole_repo_zero_findings():
    """THE enforcement test: the shipped tree is clean under all five
    rules + the shipped baseline. Any new wall-clock read, unlocked
    mutation, unguarded/untested native entry, global-RNG draw or
    impure kernel body fails tier-1 from now on."""
    findings, ctx = run_lint(_repo_package_root())
    assert len(ctx.modules) > 100   # the real tree, not a fixture
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)


def test_cli_list_rules_and_clean_run():
    assert lint_main(["--list-rules"]) == 0
    assert lint_main([]) == 0
    assert lint_main(["--rule", "no-such-rule"]) == 2


def test_cli_nonzero_on_findings(tmp_path, capsys):
    write(tmp_path, "pkg/sim/e.py", """
        import time

        def tick():
            return time.time()
    """)
    rc = lint_main(["--root", str(tmp_path / "pkg"),
                    "--rule", "clock-discipline",
                    "--baseline", str(tmp_path / "none")])
    out = capsys.readouterr().out
    assert rc == 1 and "clock-discipline" in out
