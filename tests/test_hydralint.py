"""hydralint self-tests: every checker must flag the known-bad shape it
was built from (PR 4/5/9 bug classes) and pass the fixed shape; the
baseline may only shrink; inline/scoped suppressions work; the CFG
engine routes exception edges correctly; and the runtime lock/leak
sanitizers catch an A/B-B/A inversion and an unreturned claim.
Finally, the real tree must lint clean — the CI gate this PR extends."""
import ast
import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

from tools.hydralint import load_baseline, run_lint, write_baseline
from tools.hydralint import flow, leaksan, locksan

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_fixture(tmp_path, files, select):
    """Write {relpath: source} under tmp_path and lint it."""
    paths = []
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        paths.append(rel)
    return run_lint(paths, tmp_path, select={select})


# ---------------------------------------------------------------------------
# HL001 lock discipline
# ---------------------------------------------------------------------------
BAD_LOCK = """\
    import threading

    class Metrics:
        def __init__(self):
            self._lock = threading.Lock()
            self._c = {}

        def inc(self, name):
            with self._lock:
                self._c[name] = self._c.get(name, 0) + 1

        def read(self, name):
            return self._c.get(name, 0)
"""

GOOD_LOCK = BAD_LOCK.replace(
    "        def read(self, name):\n"
    "            return self._c.get(name, 0)\n",
    "        def read(self, name):\n"
    "            with self._lock:\n"
    "                return self._c.get(name, 0)\n")


def test_hl001_flags_unguarded_read_of_locked_attr(tmp_path):
    res = lint_fixture(tmp_path, {"src/m.py": BAD_LOCK}, "HL001")
    assert [f.detail for f in res.findings] == ["Metrics.read:_c"]
    assert "without it" in res.findings[0].message


def test_hl001_passes_when_all_access_is_locked(tmp_path):
    res = lint_fixture(tmp_path, {"src/m.py": GOOD_LOCK}, "HL001")
    assert res.findings == []


def test_hl001_condition_aliases_to_wrapped_lock(tmp_path):
    src = """\
        import threading

        class Q:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._items = []

            def put(self, x):
                with self._lock:
                    self._items.append(x)

            def drain(self):
                with self._cv:          # same lock: no finding
                    return list(self._items)
    """
    res = lint_fixture(tmp_path, {"src/q.py": src}, "HL001")
    assert res.findings == []


def test_hl001_rmw_in_thread_owning_class(tmp_path):
    src = """\
        import threading

        class Ticker:
            def __init__(self):
                self.ticks = 0
                self._t = threading.Thread(target=self.run)

            def run(self):
                self.ticks += 1
    """
    res = lint_fixture(tmp_path, {"src/t.py": src}, "HL001")
    assert [f.detail for f in res.findings] == ["Ticker.run:ticks:rmw"]


def test_hl001_caller_holds_lock_helper_pattern(tmp_path):
    src = """\
        import threading

        class G:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = []

            def submit(self, x):
                with self._lock:
                    self._q.append(x)
                    self._next()

            def _next(self):
                # caller holds the lock (every call site does)
                return self._q.pop(0)
    """
    res = lint_fixture(tmp_path, {"src/g.py": src}, "HL001")
    assert res.findings == []


# ---------------------------------------------------------------------------
# HL002 hot-path purity
# ---------------------------------------------------------------------------
BAD_HOTPATH = """\
    import jax.numpy as jnp
    import numpy as np

    class Gateway:
        def _worker_loop(self):
            return self._payload()

        def _payload(self):
            # the PR 5 args_for bug shape: eager device-array per request
            return jnp.full((64,), 3.0)
"""

GOOD_HOTPATH = BAD_HOTPATH.replace("jnp.full((64,), 3.0)",
                                   "np.full((64,), 3.0)")


def test_hl002_flags_eager_jnp_reachable_from_root(tmp_path):
    res = lint_fixture(tmp_path, {"src/gw.py": BAD_HOTPATH}, "HL002")
    assert len(res.findings) == 1
    f = res.findings[0]
    assert "jnp.full" in f.message
    assert "Gateway._worker_loop" in f.message    # names the root
    assert f.detail.startswith("Gateway._payload:")


def test_hl002_host_numpy_is_fine(tmp_path):
    res = lint_fixture(tmp_path, {"src/gw.py": GOOD_HOTPATH}, "HL002")
    assert res.findings == []


def test_hl002_marker_declares_extra_root(tmp_path):
    src = """\
        import time

        def claim():  # hydralint: hot-path-root
            time.sleep(0.1)
    """
    res = lint_fixture(tmp_path, {"src/a.py": src}, "HL002")
    assert [f.detail for f in res.findings] == ["claim:time.sleep:0"]


def test_hl002_scoped_disable_cuts_traversal(tmp_path):
    src = """\
        import time

        class Gateway:
            def _worker_loop(self):
                return self._register()

            # registration cost is modeled, not hot-path
            def _register(self):  # hydralint: disable=HL002
                time.sleep(0.1)
    """
    res = lint_fixture(tmp_path, {"src/gw.py": src}, "HL002")
    assert res.findings == []


# ---------------------------------------------------------------------------
# HL003 sim determinism
# ---------------------------------------------------------------------------
def test_hl003_flags_wallclock_and_unseeded_rng(tmp_path):
    src = """\
        # hydralint: sim-module
        import random
        import time

        def step(pending):
            now = time.time()
            jitter = random.random()
            for node in {1, 2, 3}:
                pass
            return now + jitter
    """
    res = lint_fixture(tmp_path, {"src/core/sim2.py": src}, "HL003")
    details = sorted(f.detail for f in res.findings)
    assert details == ["set-iter:L8", "unseeded:random.random",
                       "wallclock:time.time"]


def test_hl003_seeded_rng_and_sorted_iter_pass(tmp_path):
    src = """\
        # hydralint: sim-module
        import numpy as np

        def step(nodes, seed):
            rng = np.random.default_rng(seed)
            for node in sorted(nodes):
                pass
            return rng.random()
    """
    res = lint_fixture(tmp_path, {"src/core/sim2.py": src}, "HL003")
    assert res.findings == []


def test_hl003_ignores_non_sim_files(tmp_path):
    src = "import time\n\ndef now():\n    return time.time()\n"
    res = lint_fixture(tmp_path, {"src/other.py": src}, "HL003")
    assert res.findings == []


# ---------------------------------------------------------------------------
# HL004 metric vocabulary
# ---------------------------------------------------------------------------
EMITTER = """\
    class Node:
        def __init__(self, metrics):
            self.metrics = metrics

        def boot(self):
            self.metrics.inc("pool.miss")
"""

MAPPING_WITH = 'WIRED = {"pool.miss": "cold_runtime"}\n'
MAPPING_WITHOUT = 'WIRED = {}\n'


def test_hl004_flags_unmapped_live_metric(tmp_path):
    res = lint_fixture(tmp_path, {"src/gateway/node.py": EMITTER,
                                  "src/gateway/replay.py": MAPPING_WITHOUT},
                       "HL004")
    assert [f.detail for f in res.findings] == ["unmapped:pool.miss"]


def test_hl004_mapped_metric_passes(tmp_path):
    res = lint_fixture(tmp_path, {"src/gateway/node.py": EMITTER,
                                  "src/gateway/replay.py": MAPPING_WITH},
                       "HL004")
    assert res.findings == []


def test_hl004_flags_phantom_read(tmp_path):
    mapping = 'def pull(cm):\n    return cm.counters.get("ghost.metric", 0)\n'
    res = lint_fixture(tmp_path, {"src/gateway/node.py": EMITTER,
                                  "src/gateway/replay.py": mapping},
                       "HL004")
    assert "phantom:ghost.metric" in [f.detail for f in res.findings]


def test_hl004_counters_key_parity_across_adapters(tmp_path):
    targets = """\
        class A:
            def counters(self):
                return {"cold": 1, "warm": 2}

        class B:
            def counters(self):
                return {"cold": 1}
    """
    res = lint_fixture(tmp_path, {"src/gateway/targets.py": targets},
                       "HL004")
    assert [f.detail for f in res.findings] == ["counters-parity:B"]


# ---------------------------------------------------------------------------
# HL005 adapter conformance
# ---------------------------------------------------------------------------
def test_hl005_flags_missing_base_attr_and_unimplemented(tmp_path):
    targets = """\
        class TargetAdapter:
            def invoke(self, fid, args):
                raise NotImplementedError

        class PlatformTarget(TargetAdapter):
            pass
    """
    user = """\
        def drive(adapter):
            adapter.invoke("f", {})
            adapter.sample()
    """
    res = lint_fixture(tmp_path, {"src/gateway/targets.py": targets,
                                  "src/gateway/replay.py": user}, "HL005")
    assert sorted(f.detail for f in res.findings) == [
        "base-missing:sample", "unimplemented:PlatformTarget.invoke"]


def test_hl005_full_surface_passes(tmp_path):
    targets = """\
        class TargetAdapter:
            n_nodes = 1

            def invoke(self, fid, args):
                raise NotImplementedError

        class PlatformTarget(TargetAdapter):
            def invoke(self, fid, args):
                return {}
    """
    user = """\
        def drive(adapter):
            adapter.invoke("f", {})
            return adapter.n_nodes
    """
    res = lint_fixture(tmp_path, {"src/gateway/targets.py": targets,
                                  "src/gateway/replay.py": user}, "HL005")
    assert res.findings == []


# ---------------------------------------------------------------------------
# HL006 docs references
# ---------------------------------------------------------------------------
def test_hl006_flags_dangling_ref_and_missing_module(tmp_path):
    (tmp_path / "README.md").write_text(
        "See `missing_file.py` for details.\n\n"
        "```bash\npython -m nope.mod\n```\n")
    res = run_lint([], tmp_path, select={"HL006"})
    assert sorted(f.detail for f in res.findings) == [
        "module:nope.mod", "ref:missing_file.py"]


def test_hl006_resolved_refs_pass(tmp_path):
    (tmp_path / "real.py").write_text("x = 1\n")
    (tmp_path / "README.md").write_text(
        "See `real.py`.\n\n```bash\npython real.py\n```\n")
    res = run_lint([], tmp_path, select={"HL006"})
    assert res.findings == []


# ---------------------------------------------------------------------------
# HL007 argparse hygiene
# ---------------------------------------------------------------------------
def test_hl007_flags_missing_and_empty_help(tmp_path):
    src = """\
        import argparse

        ap = argparse.ArgumentParser()
        ap.add_argument("--good", help="does a thing")
        ap.add_argument("--bare")
        ap.add_argument("--blank", help="")
    """
    res = lint_fixture(tmp_path, {"src/cli.py": src}, "HL007")
    assert sorted(f.detail for f in res.findings) == [
        "empty-help:--blank", "no-help:--bare"]


# ---------------------------------------------------------------------------
# HL008 span discipline
# ---------------------------------------------------------------------------
# minimal registry so the checker's AST loader finds the vocabulary
TRACING_STUB = """\
    PHASES = ("admission", "queue_wait", "compute")
"""


def test_hl008_flags_bare_span_and_unknown_phase(tmp_path):
    src = """\
        def handle(ctx):
            ctx.span("compute")                 # bare: times nothing
            with ctx.span("made_up_phase"):     # not in the registry
                pass
    """
    res = lint_fixture(tmp_path, {"src/repro/core/tracing.py": TRACING_STUB,
                                  "src/gw.py": src}, "HL008")
    assert sorted(f.detail for f in res.findings) == [
        "bare-span:compute:L2", "unknown-phase:made_up_phase"]
    assert "context manager" in res.findings[0].message


def test_hl008_with_usage_and_registry_names_pass(tmp_path):
    src = """\
        def handle(ctx, t0, t1):
            with ctx.span("queue_wait"):
                pass
            with ctx.span("compute") as sp:
                sp.attrs["n"] = 1
            ctx.add_span("admission", t0, t1)
    """
    res = lint_fixture(tmp_path, {"src/repro/core/tracing.py": TRACING_STUB,
                                  "src/gw.py": src}, "HL008")
    assert res.findings == []


def test_hl008_missing_registry_skips_name_check_not_shape_check(tmp_path):
    # no tracing.py anywhere: phase-name checks are skipped rather than
    # guessed, but the context-manager rule still applies
    src = """\
        def handle(ctx):
            ctx.span("whatever")
    """
    res = lint_fixture(tmp_path, {"src/gw.py": src}, "HL008")
    assert [f.detail for f in res.findings] == ["bare-span:whatever:L2"]


def test_hl008_sim_code_must_not_trace(tmp_path):
    src = """\
        # hydralint: sim-module
        from repro.core.tracing import Tracer

        def step(ctx):
            with ctx.span("compute"):
                pass
    """
    res = lint_fixture(tmp_path, {"src/repro/core/tracing.py": TRACING_STUB,
                                  "src/core/sim2.py": src}, "HL008")
    assert sorted(f.detail for f in res.findings) == [
        "sim-import:repro.core.tracing", "sim-tracing:span:L5"]


def test_hl008_tracing_module_itself_is_exempt(tmp_path):
    impl = """\
        PHASES = ("admission", "queue_wait", "compute")

        class RequestTrace:
            def span(self, name):
                return self.span(name)      # machinery, not a call site
    """
    res = lint_fixture(tmp_path,
                       {"src/repro/core/tracing.py": impl}, "HL008")
    assert res.findings == []


def test_hl008_disable_comment_suppresses(tmp_path):
    src = """\
        def probe(ctx):
            # hydralint: disable=HL008 — identity check, not a timing
            assert ctx.span("compute") is ctx.span("compute")
    """
    res = lint_fixture(tmp_path, {"src/repro/core/tracing.py": TRACING_STUB,
                                  "src/gw.py": src}, "HL008")
    assert res.findings == []
    assert len(res.suppressed) == 2


HOST_STUB = """\
    PHASES = ("compute",)
    HOST_PHASES = ("lm.step", "lm.sync")
"""


def test_hl008_flags_unregistered_and_bare_host_phase(tmp_path):
    src = """\
        from repro.core import tracing
        from repro.core.tracing import phase

        def step():
            with phase("lm.step"):
                with tracing.phase("lm.sync", tokens=3):
                    pass
            with phase("lm.made_up"):          # not in HOST_PHASES
                pass
            phase("lm.sync")                     # bare: opens nothing
    """
    res = lint_fixture(tmp_path, {"src/repro/core/tracing.py": HOST_STUB,
                                  "src/sched.py": src}, "HL008")
    assert sorted(f.detail for f in res.findings) == [
        "bare-phase:lm.sync:L10", "unknown-host-phase:lm.made_up"]
    assert "HOST_PHASES" in next(
        f.message for f in res.findings
        if f.detail.startswith("unknown"))


def test_hl008_request_phase_is_not_a_host_phase(tmp_path):
    # the two registries stay apart: a request phase is no host phase,
    # and a host phase is no request span
    src = """\
        from repro.core.tracing import phase

        def step(ctx):
            with phase("compute"):
                pass
            with ctx.span("lm.step"):
                pass
    """
    res = lint_fixture(tmp_path, {"src/repro/core/tracing.py": HOST_STUB,
                                  "src/sched.py": src}, "HL008")
    assert sorted(f.detail for f in res.findings) == [
        "unknown-host-phase:compute", "unknown-phase:lm.step"]


def test_hl008_host_phase_in_sim_code(tmp_path):
    src = """\
        # hydralint: sim-module
        def step(phase):
            with phase("lm.step"):
                pass
    """
    res = lint_fixture(tmp_path, {"src/repro/core/tracing.py": HOST_STUB,
                                  "src/core/sim3.py": src}, "HL008")
    assert [f.detail for f in res.findings] == ["sim-tracing:phase:L3"]


# ---------------------------------------------------------------------------
# suppression mechanics
# ---------------------------------------------------------------------------
def test_inline_disable_suppresses_and_is_counted(tmp_path):
    src = BAD_LOCK.replace(
        "return self._c.get(name, 0)",
        "return self._c.get(name, 0)  # hydralint: disable=HL001 — stale ok")
    res = lint_fixture(tmp_path, {"src/m.py": src}, "HL001")
    assert res.findings == []
    assert [f.detail for f in res.suppressed] == ["Metrics.read:_c"]


def test_disable_on_comment_line_covers_next_statement(tmp_path):
    src = BAD_LOCK.replace(
        "            return self._c.get(name, 0)",
        "            # hydralint: disable=HL001 — approximate read is fine\n"
        "            return self._c.get(name, 0)")
    res = lint_fixture(tmp_path, {"src/m.py": src}, "HL001")
    assert res.findings == []


def test_scoped_disable_on_def_covers_body(tmp_path):
    src = BAD_LOCK.replace(
        "def read(self, name):",
        "def read(self, name):  # hydralint: disable=HL001")
    res = lint_fixture(tmp_path, {"src/m.py": src}, "HL001")
    assert res.findings == []


# ---------------------------------------------------------------------------
# baseline: shrink-only
# ---------------------------------------------------------------------------
def test_baseline_masks_known_findings_and_flags_stale(tmp_path):
    res = lint_fixture(tmp_path, {"src/m.py": BAD_LOCK}, "HL001")
    bl_path = tmp_path / "baseline.json"
    write_baseline(bl_path, res.findings)
    baseline = load_baseline(bl_path)
    assert res.new_against(baseline) == []

    # fixing the bug leaves the baseline entry stale -> must be removed
    fixed = lint_fixture(tmp_path, {"src/m.py": GOOD_LOCK}, "HL001")
    assert fixed.new_against(baseline) == []
    stale = fixed.stale_baseline_keys(baseline)
    assert stale and stale[0].startswith("HL001::src/m.py::")


def test_baseline_key_is_line_number_stable(tmp_path):
    res1 = lint_fixture(tmp_path, {"src/m.py": BAD_LOCK}, "HL001")
    shifted = "# a new leading comment\n" + textwrap.dedent(BAD_LOCK)
    res2 = lint_fixture(tmp_path, {"src/m.py": shifted}, "HL001")
    assert [f.key for f in res1.findings] == [f.key for f in res2.findings]
    assert res1.findings[0].line != res2.findings[0].line


def test_missing_baseline_file_is_empty():
    assert load_baseline("/nonexistent/baseline.json") == {}


# ---------------------------------------------------------------------------
# CLI: the CI gate fails on a seeded regression and on stale baseline
# ---------------------------------------------------------------------------
def _run_cli(args, cwd):
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    return subprocess.run([sys.executable, "-m", "tools.hydralint", *args],
                          cwd=cwd, env=env, capture_output=True, text=True)


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "src" / "m.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent(BAD_LOCK))

    r = _run_cli(["src", "--root", str(tmp_path), "--select", "HL001"],
                 cwd=REPO_ROOT)
    assert r.returncode == 1
    assert "HL001" in r.stdout

    bad.write_text(textwrap.dedent(GOOD_LOCK))
    r = _run_cli(["src", "--root", str(tmp_path), "--select", "HL001"],
                 cwd=REPO_ROOT)
    assert r.returncode == 0, r.stdout + r.stderr

    # stale baseline entries fail even on a clean tree (shrink-only)
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(
        {"version": 1, "findings": {"HL001::src/m.py::Gone.read:_x": "old"}}))
    r = _run_cli(["src", "--root", str(tmp_path), "--select", "HL001",
                  "--baseline", str(bl)], cwd=REPO_ROOT)
    assert r.returncode == 1
    assert "stale" in (r.stdout + r.stderr).lower()


# ---------------------------------------------------------------------------
# locksan: runtime lock-order sanitizer
# ---------------------------------------------------------------------------
def test_locksan_detects_ab_ba_inversion():
    san = locksan.LockOrderSanitizer()
    a = san.make_lock("A")
    b = san.make_lock("B")

    def t1():
        with a:
            with b:
                pass

    def t2():
        with b:
            with a:
                pass

    th1 = threading.Thread(target=t1)
    th1.start()
    th1.join()
    th2 = threading.Thread(target=t2)
    th2.start()
    th2.join()
    reports = san.check()
    assert len(reports) == 1
    assert "A" in reports[0] and "B" in reports[0]
    with pytest.raises(locksan.LockOrderViolation):
        san.assert_clean()


def test_locksan_consistent_order_is_clean():
    san = locksan.LockOrderSanitizer()
    a = san.make_lock("A")
    b = san.make_lock("B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert san.check() == []
    san.assert_clean()


def test_locksan_condition_and_handoff_locks_not_false_positives():
    with locksan.sanitized() as san:
        cv = threading.Condition()
        done = []

        def waiter():
            with cv:
                while not done:
                    cv.wait(timeout=5.0)

        t = threading.Thread(target=waiter)
        t.start()
        with cv:
            done.append(1)
            cv.notify()
        t.join(timeout=10.0)
    assert san.check() == []


def test_locksan_sanitized_raises_on_inversion():
    with pytest.raises(locksan.LockOrderViolation):
        with locksan.sanitized():
            a = threading.Lock()
            b = threading.Lock()
            with a:
                with b:
                    pass
            with b:
                with a:
                    pass


# ---------------------------------------------------------------------------
# flow: the exception-edge CFG both HL009 and HL010 run on
# ---------------------------------------------------------------------------
def _cfg_of(src):
    tree = ast.parse(textwrap.dedent(src))
    fn = next(n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef))
    return flow.build_cfg(fn)


def test_cfg_finally_runs_on_normal_and_exception_paths():
    cfg = _cfg_of("""\
        def f():
            x = acquire()
            try:
                work(x)
            finally:
                cleanup(x)
    """)
    (work,) = cfg.nodes_at(4)
    cleanups = cfg.nodes_at(6)
    # the finally body is duplicated per continuation so the normal and
    # exceptional passes through it stay distinct
    assert len(cleanups) >= 2
    assert cfg.has_path(work.idx, cfg.exit, exceptional=False)
    assert any(cfg.has_path(work.idx, c.idx, exceptional=False)
               for c in cleanups)
    # the raise continuation ALSO runs a cleanup copy, reached only via
    # the exception edge out of work(x)
    assert cfg.has_path(work.idx, cfg.raise_, exceptional=True)
    assert not cfg.has_path(work.idx, cfg.raise_, exceptional=False)


def test_cfg_with_suppression_resumes_after_the_block():
    cfg = _cfg_of("""\
        def f():
            with contextlib.suppress(KeyError):
                raise KeyError
            after()
    """)
    (rs,) = cfg.nodes_at(3, "raise-stmt")
    (after,) = cfg.nodes_at(4)
    assert cfg.has_path(rs.idx, after.idx)

    plain = _cfg_of("""\
        def f():
            with self._lock:
                raise KeyError
            after()
    """)
    (rs,) = plain.nodes_at(3, "raise-stmt")
    (after,) = plain.nodes_at(4)
    assert not plain.has_path(rs.idx, after.idx)
    assert plain.has_path(rs.idx, plain.raise_)


def test_cfg_early_return_threads_through_finally():
    cfg = _cfg_of("""\
        def f(c):
            try:
                if c:
                    return 1
                work()
            finally:
                cleanup()
    """)
    (ret,) = cfg.nodes_at(4, "return")
    (work,) = cfg.nodes_at(5)
    cleanups = cfg.nodes_at(7)
    assert any(cfg.has_path(ret.idx, c.idx, exceptional=False)
               for c in cleanups)
    assert cfg.has_path(ret.idx, cfg.exit, exceptional=False)
    assert not cfg.has_path(ret.idx, work.idx)


def test_cfg_nested_handlers_dispatch_and_catch_all():
    cfg = _cfg_of("""\
        def f():
            try:
                try:
                    risky()
                except KeyError:
                    pass
            except Exception:
                pass
            done()
    """)
    (risky,) = cfg.nodes_at(4)
    (h_inner,) = cfg.nodes_at(5, "except")
    (h_outer,) = cfg.nodes_at(7, "except")
    (done,) = cfg.nodes_at(9)
    assert cfg.has_path(risky.idx, h_inner.idx)
    assert not cfg.has_path(risky.idx, h_inner.idx, exceptional=False)
    # KeyError is not catch-all: the inner dispatch escapes to the outer
    assert cfg.has_path(risky.idx, h_outer.idx)
    assert cfg.has_path(h_inner.idx, done.idx, exceptional=False)
    assert cfg.has_path(h_outer.idx, done.idx, exceptional=False)
    (inner_disp,) = cfg.nodes_at(3, "except-dispatch")
    (outer_disp,) = cfg.nodes_at(2, "except-dispatch")
    assert any(cfg.has_path(s, outer_disp.idx) for s in inner_disp.succ)
    # except Exception IS catch-all: the outer dispatch cannot escalate
    assert cfg.raise_ not in outer_disp.succ


# ---------------------------------------------------------------------------
# HL009: resource lifecycle (acquire/release pairing on every path)
# ---------------------------------------------------------------------------
ARENA_PREAMBLE = """
class ArenaPool:
    def acquire(self, sig, factory):
        return object()

    def release(self, a):
        pass

"""

ARENA_EXC_LEAK = ARENA_PREAMBLE + """
def handler(pool, sig, factory):
    a = pool.acquire(sig, factory)
    write_args(a)
    pool.release(a)
"""

ARENA_PAIRED = ARENA_PREAMBLE + """
def handler(pool, sig, factory):
    a = pool.acquire(sig, factory)
    try:
        write_args(a)
    finally:
        pool.release(a)
"""


def test_hl009_flags_acquire_without_release_on_exception_path(tmp_path):
    res = lint_fixture(tmp_path, {"src/m.py": ARENA_EXC_LEAK}, "HL009")
    assert len(res.findings) == 1
    f = res.findings[0]
    assert "exception" in f.message
    assert f.detail.startswith("handler:arena:a")


def test_hl009_try_finally_pairing_passes(tmp_path):
    res = lint_fixture(tmp_path, {"src/m.py": ARENA_PAIRED}, "HL009")
    assert res.findings == []


def test_hl009_release_in_except_settles_the_error_path(tmp_path):
    src = ARENA_PREAMBLE + """
def handler(pool, sig, factory):
    a = pool.acquire(sig, factory)
    try:
        write_args(a)
    except Exception:
        pool.release(a)
        raise
    pool.release(a)
"""
    res = lint_fixture(tmp_path, {"src/m.py": src}, "HL009")
    assert res.findings == []


def test_hl009_flags_normal_path_leak(tmp_path):
    src = ARENA_PREAMBLE + """
def handler(pool, sig, factory):
    a = pool.acquire(sig, factory)
    if a is not None:
        pool.release(a)
"""
    res = lint_fixture(tmp_path, {"src/m.py": src}, "HL009")
    assert len(res.findings) == 1
    assert res.findings[0].detail.startswith("handler:arena:a")


def test_hl009_escape_transfers_ownership(tmp_path):
    src = ARENA_PREAMBLE + """
def claim(pool, sig, factory):
    a = pool.acquire(sig, factory)
    return a
"""
    res = lint_fixture(tmp_path, {"src/m.py": src}, "HL009")
    assert res.findings == []


def test_hl009_interprocedural_release_via_helper(tmp_path):
    src = ARENA_PREAMBLE + """
def _put_back(pool, a):
    pool.release(a)

def handler(pool, sig, factory):
    a = pool.acquire(sig, factory)
    try:
        write_args(a)
    finally:
        _put_back(pool, a)
"""
    res = lint_fixture(tmp_path, {"src/m.py": src}, "HL009")
    assert res.findings == []


def test_hl009_manual_lock_acquire_needs_try_finally(tmp_path):
    src = """
def f(self):
    self._lock.acquire()
    work()
    self._lock.release()
"""
    res = lint_fixture(tmp_path, {"src/m.py": src}, "HL009")
    assert len(res.findings) == 1
    assert "lock" in res.findings[0].detail

    good = """
def f(self):
    self._lock.acquire()
    try:
        work()
    finally:
        self._lock.release()
"""
    res = lint_fixture(tmp_path, {"src/m.py": good}, "HL009")
    assert res.findings == []


# ---------------------------------------------------------------------------
# HL010: exception safety under locks (the PR 4 _try_admit bug)
# ---------------------------------------------------------------------------
ADMIT_BUG = """
class Platform:
    def _try_admit(self, fid, rt):
        with self._lock:
            rec = self._recs[fid]
            rec.runtime = rt
            rt.register_function(fid)
            rec.placed = True
"""

ADMIT_FIXED = """
class Platform:
    def _try_admit(self, fid, rt):
        with self._lock:
            rec = self._recs[fid]
            rec.runtime = rt
            try:
                rt.register_function(fid)
            except BaseException:
                rec.runtime = None
                raise
            rec.placed = True
"""


def test_hl010_flags_partial_multi_field_update_under_lock(tmp_path):
    res = lint_fixture(tmp_path, {"src/m.py": ADMIT_BUG}, "HL010")
    assert len(res.findings) == 1
    f = res.findings[0]
    assert "runtime" in f.detail
    assert "_try_admit" in f.detail


def test_hl010_rollback_handler_protects_the_write(tmp_path):
    res = lint_fixture(tmp_path, {"src/m.py": ADMIT_FIXED}, "HL010")
    assert res.findings == []


def test_hl010_constant_resets_do_not_arm(tmp_path):
    src = """
class Platform:
    def evict(self, fid):
        with self._lock:
            rec = self._recs[fid]
            rec.runtime = None
            self._notify(fid)
            rec.placed = False
"""
    res = lint_fixture(tmp_path, {"src/m.py": src}, "HL010")
    assert res.findings == []


# ---------------------------------------------------------------------------
# HL011: sim/live accounting parity (conservation over the mapping layer)
# ---------------------------------------------------------------------------
SIM_ENGINE = """
class SimResult:
    requests = 0
    dropped = 0
"""

PARITY_TARGETS = """
class Adapter:
    def counters(self):
        return {"served": 1, "dropped": 2}
"""


def test_hl011_balanced_mapping_passes(tmp_path):
    rec = """
def finish(adapter):
    c = adapter.counters()
    return SimResult(requests=c["served"], dropped=c["dropped"])
"""
    res = lint_fixture(tmp_path, {"src/engine.py": SIM_ENGINE,
                                  "src/recorder.py": rec,
                                  "src/targets.py": PARITY_TARGETS},
                       "HL011")
    assert res.findings == []


def test_hl011_flags_unfed_simresult_field(tmp_path):
    rec = """
def finish(adapter):
    c = adapter.counters()
    return SimResult(requests=c["served"] + c["dropped"])
"""
    res = lint_fixture(tmp_path, {"src/engine.py": SIM_ENGINE,
                                  "src/recorder.py": rec,
                                  "src/targets.py": PARITY_TARGETS},
                       "HL011")
    assert [f.detail for f in res.findings] == ["unfed:dropped"]


def test_hl011_flags_dead_and_phantom_counters(tmp_path):
    rec = """
def finish(adapter):
    c = adapter.counters()
    return SimResult(requests=c["served"], dropped=c.get("cold", 0))
"""
    targets = """
class Adapter:
    def counters(self):
        return {"served": 1, "evicted": 3}
"""
    res = lint_fixture(tmp_path, {"src/engine.py": SIM_ENGINE,
                                  "src/recorder.py": rec,
                                  "src/targets.py": targets}, "HL011")
    details = sorted(f.detail for f in res.findings)
    assert len(details) == 2
    assert any(d.startswith("dead-counter:evicted:") for d in details)
    assert any(d.startswith("phantom-counter:cold:") for d in details)


# ---------------------------------------------------------------------------
# leaksan: runtime resource-leak sanitizer
# ---------------------------------------------------------------------------
def _leak_pool():
    import jax.numpy as jnp

    from repro.core.arena import ArenaPool
    pool = ArenaPool(ttl_s=60)
    factory = lambda: {"x": jnp.zeros((4,), jnp.float32)}
    return pool, factory


def test_leaksan_balanced_claims_pass_and_restore_patches():
    from repro.core.arena import ArenaPool
    with leaksan.sanitized() as san:
        pool, factory = _leak_pool()
        a = pool.acquire(("x",), factory)
        pool.release(a)
    assert (san.claims, san.releases) == (1, 1)
    # the paired APIs are restored on exit
    assert ArenaPool.acquire.__name__ == "acquire"


def test_leaksan_reports_leaked_claim_with_acquiring_site():
    with pytest.raises(leaksan.ResourceLeakError) as ei:
        with leaksan.sanitized():
            pool, factory = _leak_pool()
            pool.acquire(("x",), factory)
    msg = str(ei.value)
    assert "arena" in msg
    assert "test_hydralint.py" in msg      # the acquiring call site


def test_leaksan_trace_pairing_and_null_trace_exempt():
    # hydralint: disable=HL008 — this file only LOOKS like sim code (the
    # HL003 fixtures above carry sim-module markers); the import exercises
    # leaksan's trace pairing, nothing here simulates
    from repro.core.tracing import Tracer
    with leaksan.sanitized() as san:
        tr = Tracer(1.0)
        tr.start_request("f").finish("ok")
        Tracer(0.0).start_request("g")     # NULL_TRACE: never ledgered
    assert (san.claims, san.releases) == (1, 1)


# ---------------------------------------------------------------------------
# CLI: --format=github annotations, --explain, and the lint-speed gate
# ---------------------------------------------------------------------------
def test_cli_github_format_emits_workflow_annotations(tmp_path):
    bad = tmp_path / "src" / "m.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent(BAD_LOCK))
    r = _run_cli(["src", "--root", str(tmp_path), "--select", "HL001",
                  "--format=github"], cwd=REPO_ROOT)
    assert r.returncode == 1
    first = r.stdout.splitlines()[0]
    assert first.startswith("::error file=src/m.py,line=")
    assert "title=HL001" in first


def test_cli_explain_prints_invariant_entry():
    for code in ("HL009", "HL010", "HL011"):
        r = _run_cli(["--explain", code], cwd=REPO_ROOT)
        assert r.returncode == 0, r.stderr
        assert code in r.stdout
        assert "suppress" in r.stdout.lower()


def test_cli_budget_gate(tmp_path):
    good = tmp_path / "src" / "m.py"
    good.parent.mkdir(parents=True)
    good.write_text(textwrap.dedent(GOOD_LOCK))
    budget = tmp_path / "budget.json"

    budget.write_text(json.dumps({"lint": {"hydralint_sweep_s": 300.0}}))
    r = _run_cli(["src", "--root", str(tmp_path), "--select", "HL001",
                  "--budget", str(budget)], cwd=REPO_ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "budget — ok" in r.stdout

    budget.write_text(json.dumps({"lint": {"hydralint_sweep_s": 1e-9}}))
    r = _run_cli(["src", "--root", str(tmp_path), "--select", "HL001",
                  "--budget", str(budget)], cwd=REPO_ROOT)
    assert r.returncode == 1
    assert "OVER" in r.stdout + r.stderr


# ---------------------------------------------------------------------------
# the real tree is clean — the gate this PR turns on in CI
# ---------------------------------------------------------------------------
def test_real_tree_lints_clean():
    res = run_lint(["src", "tests"], REPO_ROOT)
    assert res.findings == [], "\n".join(f.render() for f in res.findings)
