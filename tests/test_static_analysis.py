"""raylint + runtime-sanitizer tests.

Per-rule fixtures (one minimal positive and negative snippet per RT rule),
the suppression/baseline mechanics, the chaos-point/docs drift gates, the
runtime sanitizers (lock-order, io-loop watchdog, thread affinity), the
CLI, and — marked ``lint`` so the tier-1 gate is a single test node — the
whole-package run asserting zero unsuppressed findings.
"""

import gc
import json
import textwrap
import threading
import time
import traceback

import pytest
from conftest import time_limit

from ray_tpu.analysis import lint_source


def _rules_of(result):
    return sorted({f.rule for f in result.unsuppressed})


def _lint(src: str, filename: str = "snippet.py"):
    return lint_source(textwrap.dedent(src), filename)


# ---------------------------------------------------------------- RT001
def test_rt001_blocking_in_async_def():
    res = _lint("""
        import time

        async def handler(self):
            time.sleep(1)
    """)
    assert "RT001" in _rules_of(res)


def test_rt001_io_run_reachable_from_async():
    # the PR-1 deadlock shape: an async handler calls a sync helper that
    # blocks on the io loop — caught through one-hop reachability
    res = _lint("""
        class W:
            async def handle_get(self):
                return self._fetch()

            def _fetch(self):
                return self.io.run(self._get_async())
    """)
    findings = [f for f in res.unsuppressed if f.rule == "RT001"]
    assert findings and "io.run" in findings[0].message


def test_rt001_negative_sync_and_awaited():
    res = _lint("""
        import asyncio
        import time

        def cli_loop():
            time.sleep(1)          # fine: not loop context

        async def poller(self):
            await asyncio.sleep(1)  # fine: async sleep
            return self.io.spawn(self._bg())  # fine: non-blocking spawn
    """)
    assert "RT001" not in _rules_of(res)


# ---------------------------------------------------------------- RT002
def test_rt002_lock_across_await():
    res = _lint("""
        async def update(self):
            with self._lock:
                await self._flush()
    """)
    assert "RT002" in _rules_of(res)


def test_rt002_negative():
    res = _lint("""
        async def update(self):
            with self._lock:
                self.n += 1            # released before the await
            await self._flush()
            async with self._alock:    # asyncio lock: fine
                await self._flush()

        def sync_update(self):
            with self._lock:
                self.n += 1
    """)
    assert "RT002" not in _rules_of(res)


# ---------------------------------------------------------------- RT003
def test_rt003_bare_ensure_future():
    res = _lint("""
        import asyncio

        def kick(self):
            asyncio.ensure_future(self._dispatch())
    """)
    assert "RT003" in _rules_of(res)


def test_rt003_lambda_callback():
    res = _lint("""
        import asyncio

        def retry_later(self, loop, info):
            loop.call_later(1.0, lambda: asyncio.ensure_future(self._go(info)))
    """)
    assert "RT003" in _rules_of(res)


def test_rt003_negative_held():
    res = _lint("""
        import asyncio

        def kick(self):
            t = asyncio.ensure_future(self._dispatch())
            self._held.add(t)
            t.add_done_callback(self._held.discard)
            self._hold(asyncio.create_task(self._other()))
    """)
    assert "RT003" not in _rules_of(res)


# ---------------------------------------------------------------- RT004
def test_rt004_del_blocking_kill():
    # deliberately reintroduce the PR-1 pattern: __del__ -> blocking
    # kill through the backend plane — raylint must make lint exit dirty
    res = _lint("""
        class ActorHandle:
            def __del__(self):
                _global_worker().backend.kill_actor(self._actor_id, True)
    """)
    assert "RT004" in _rules_of(res)


def test_rt004_del_io_run_and_teardown():
    res = _lint("""
        class G:
            def __del__(self):
                self.io.run(self._close_async())

        class D:
            def __del__(self):
                self.teardown(timeout=1.0)
    """)
    assert len([f for f in res.unsuppressed if f.rule == "RT004"]) == 2


def test_rt004_negative_flag_flip():
    res = _lint("""
        class Ref:
            def __del__(self):
                self._closed = True
                cb = self._on_close
                if cb is not None:
                    cb(self)
    """)
    assert "RT004" not in _rules_of(res)


# ---------------------------------------------------------------- RT005
def test_rt005_unregistered_point():
    res = _lint("""
        from ray_tpu.testing import chaos

        def send(self):
            act = chaos.fire("rpc.sned", key="x")
    """)
    findings = [f for f in res.unsuppressed if f.rule == "RT005"]
    assert findings and "rpc.sned" in findings[0].message


def test_rt005_non_literal_point():
    res = _lint("""
        from ray_tpu.testing import chaos

        def send(self, point):
            chaos.fire(point, key="x")
    """)
    assert "RT005" in _rules_of(res)


def test_rt005_negative_registered():
    res = _lint("""
        from ray_tpu.testing import chaos

        def send(self):
            act = chaos.fire("rpc.send", key="x")
    """)
    assert "RT005" not in _rules_of(res)


def test_chaos_plan_rejects_unknown_point_at_runtime():
    from ray_tpu.testing import chaos

    with pytest.raises(ValueError, match="unknown chaos point"):
        chaos.plan(1)._rule("not.a.point", "kill")
    # builders still work for every registered point
    p = (chaos.plan(2).kill_worker().kill_actor("A.b").slow_replica("d")
         .kill_cgraph_actor().kill_stream_producer().sever_channel()
         .drop_rpc("kv_put").delay_rpc("kv_get").sever_rpc("put")
         .restart_gcs())
    assert len(p.rules) == 10


# ---------------------------------------------------------------- RT006
def test_rt006_unknown_config_knob():
    res = _lint("""
        from ray_tpu.core.config import _config

        def f():
            return _config.worker_lease_timeout_msec
    """)
    assert "RT006" in _rules_of(res)


def test_rt006_unknown_metric_and_env():
    res = _lint("""
        import os
        from ray_tpu.util.metrics import Counter

        c = Counter("serve_requsets_total")
        tok = os.environ.get("RAY_TPU_BOGUS_KNOB")
    """)
    assert len([f for f in res.unsuppressed if f.rule == "RT006"]) == 2


def test_rt006_reader_drift():
    res = _lint("""
        def qps(samples, counter_rate):
            return counter_rate(samples, "serve_requests_totall")
    """)
    assert "RT006" in _rules_of(res)


def test_rt006_negative():
    res = _lint("""
        import os
        from ray_tpu.core.config import _config
        from ray_tpu.util.metrics import Counter

        c = Counter("serve_requests_total")
        t = _config.task_max_retries
        tok = os.environ.get("RAY_TPU_TOKEN")
        knob = os.environ.get("RAY_TPU_SANITIZE_LOOP_STALL_S")
    """)
    assert "RT006" not in _rules_of(res)


# ---------------------------------------------------------------- RT007
def test_rt007_mixed_clocks():
    res = _lint("""
        import time

        def elapsed():
            return time.time() - time.monotonic()
    """)
    assert "RT007" in _rules_of(res)


def test_rt007_monotonic_vs_spec_deadline():
    res = _lint("""
        import time

        def expired(spec):
            return time.monotonic() > spec.deadline
    """)
    findings = [f for f in res.unsuppressed if f.rule == "RT007"]
    assert findings and "wall-clock" in findings[0].message


def test_rt007_negative():
    res = _lint("""
        import time

        def expired(spec):
            return time.time() > spec.deadline      # correct clock domain

        def local_wait(deadline):
            return time.monotonic() > deadline      # local monotonic: fine
    """)
    assert "RT007" not in _rules_of(res)


# ------------------------------------------------- suppressions + baseline
def test_suppression_with_reason():
    res = _lint("""
        import time

        async def handler(self):
            # raylint: disable=RT001(intentional fixture)
            time.sleep(1)
    """)
    assert res.clean
    assert any(f.rule == "RT001" and f.suppressed for f in res.findings)


def test_suppression_without_reason_is_rt000():
    res = _lint("""
        import time

        async def handler(self):
            time.sleep(1)  # raylint: disable=RT001
    """)
    assert not res.clean
    assert "RT000" in _rules_of(res)


def test_unused_suppression_is_rt000():
    res = _lint("""
        def fine():
            # raylint: disable=RT002(nothing here needs this)
            return 1
    """)
    assert "RT000" in _rules_of(res)


def test_baseline_grandfathers_non_core(tmp_path):
    from ray_tpu.analysis.linter import ModuleInfo, lint_modules

    src = textwrap.dedent("""
        import time

        async def handler(self):
            time.sleep(1)
    """)
    mod = ModuleInfo("x.py", "ray_tpu/rllib/x.py", src)
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([{
        "rule": "RT001", "path": "ray_tpu/rllib/x.py",
        "line_text": "time.sleep(1)",
        "reason": "legacy sleep in rollout loop; tracked in ROADMAP",
    }]))
    res = lint_modules([mod], baseline_path=str(bl))
    assert res.clean
    assert any(f.baselined for f in res.findings)


def test_baseline_rejected_for_core_planes(tmp_path):
    from ray_tpu.analysis.linter import ModuleInfo, lint_modules

    mod = ModuleInfo("x.py", "ray_tpu/rllib/x.py", "x = 1\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([{
        "rule": "RT001", "path": "ray_tpu/core/rpc.py",
        "line_text": "time.sleep(1)", "reason": "nope",
    }]))
    res = lint_modules([mod], baseline_path=str(bl))
    assert any("core-plane" in e for e in res.errors)


def test_baseline_stale_entry_is_error(tmp_path):
    from ray_tpu.analysis.linter import ModuleInfo, lint_modules

    mod = ModuleInfo("x.py", "ray_tpu/rllib/x.py", "x = 1\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([{
        "rule": "RT003", "path": "ray_tpu/rllib/x.py",
        "line_text": "asyncio.ensure_future(f())", "reason": "gone",
    }]))
    res = lint_modules([mod], baseline_path=str(bl))
    assert any("stale" in e for e in res.errors)


# ------------------------------------------------------------- docs drift
def test_readme_chaos_table_in_sync():
    from ray_tpu.analysis import docs
    from ray_tpu.testing.chaos import REGISTERED_POINTS

    md = docs.render_chaos_points_md()
    for point in REGISTERED_POINTS:
        assert f"`{point}`" in md
    assert docs.readme_in_sync(), (
        "README chaos-point table drifted from chaos.REGISTERED_POINTS — "
        "run `python -m ray_tpu.scripts lint --update-docs`"
    )


# -------------------------------------------------------------- sanitizers
def test_lock_order_cycle_detected_single_threaded():
    from ray_tpu.analysis import sanitizers as san

    san.enable(True)
    with san.scoped(drop_prefixes=("t.",)):
        # deltas vs the pre-scope globals: an unrelated violation recorded
        # earlier in the suite (a watchdog loop-stall on a loaded box) must
        # not fail this test's own-lock assertions
        base = san.violation_counts()
        a = san.SanitizedLock("t.A")
        b = san.SanitizedLock("t.B")
        with a:
            with b:
                pass
        assert san.violation_counts() == base
        with b:
            with a:        # inversion: closes the A->B cycle
                pass
        counts = san.violation_counts()
        assert counts.get("lock_order", 0) == base.get("lock_order", 0) + 1
        v = san.violations("lock_order")[-1]
        assert len([s for s in v["stacks"] if s]) == 2  # both stacks
        # same cycle reported once
        with b:
            with a:
                pass
        assert san.violation_counts().get("lock_order", 0) == \
            base.get("lock_order", 0) + 1


def test_lock_order_no_false_positive_consistent_order():
    from ray_tpu.analysis import sanitizers as san

    san.enable(True)
    with san.scoped(drop_prefixes=("c.",)):
        base = san.violation_counts()
        a, b = san.SanitizedLock("c.A"), san.SanitizedLock("c.B")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert san.violation_counts() == base


class _Reentered:
    """Stands in for the graph's lock and runs ``finaliser`` each time the
    lock has been taken: what the collector does when it runs a
    ``__del__`` that takes a sanitized lock on a thread that is inside
    the section."""

    def __init__(self, finaliser):
        self._lock = threading.Lock()
        self._finaliser = finaliser
        self.entered = 0

    def __enter__(self):
        self._lock.acquire()
        self.entered += 1
        self._finaliser()

    def __exit__(self, *exc):
        self._lock.release()


class _TakesLockWhenCollected:
    """The real shape: garbage in a reference cycle whose ``__del__`` takes
    a sanitized lock (``ObjectRef.__del__``, ``CompiledDAGRef.__del__``)."""

    def __init__(self, lock):
        self._me = self
        self._lock = lock

    def __del__(self):
        with self._lock:
            pass


def _collect_one(lock):
    _TakesLockWhenCollected(lock)
    gc.collect()


def _once_under_format_stack(m, finaliser):
    """Run ``finaliser`` inside the next ``traceback.format_stack`` call:
    the allocation under which the parent's hang was seen."""
    real = traceback.format_stack

    def format_stack(*args, **kwargs):
        m.setattr(traceback, "format_stack", real)
        finaliser()
        return real(*args, **kwargs)

    m.setattr(traceback, "format_stack", format_stack)


def test_nested_acquire_under_format_stack_keeps_its_edge(monkeypatch):
    """A sanitized lock taken from inside ``format_stack`` (where a
    finaliser can run) while another is held returns, and the edge it
    makes is recorded."""
    from ray_tpu.analysis import sanitizers as san

    san.enable(True)
    with san.scoped(drop_prefixes=("t.",)), monkeypatch.context() as m:
        a, b, c = (san.SanitizedLock(n) for n in ("t.A", "t.B", "t.C"))
        _once_under_format_stack(m, lambda: c.acquire() and c.release())
        with time_limit(5, "nested acquire under format_stack"):
            with a:
                with b:
                    pass
        edges = san.lock_order_edges()
        assert ("t.A", "t.B") in edges and ("t.A", "t.C") in edges


def test_cycle_closed_by_a_nested_acquisition_is_reported_once(monkeypatch):
    from ray_tpu.analysis import sanitizers as san

    san.enable(True)
    with san.scoped(drop_prefixes=("t.",)), monkeypatch.context() as m:
        base = san.violation_counts().get("lock_order", 0)
        a, c = san.SanitizedLock("t.A"), san.SanitizedLock("t.C")
        with c:
            with a:
                pass

        armed = []

        def finaliser():
            # once a round: record_violation takes sanitized locks of its
            # own, and t.C under those would be other cycles
            if armed:
                armed.pop()
                with c:    # while t.A is held: closes t.C -> t.A -> t.C
                    pass

        m.setattr(san, "_graph_lock", _Reentered(finaliser))
        for _ in range(2):
            armed.append(True)
            with time_limit(5, "nested acquisition closing a cycle"):
                with a:
                    san.lock_order_edges()
            assert san.violation_counts().get("lock_order", 0) == base + 1
        v = san.violations("lock_order")[-1]
        assert v["name"] == "t.C" and "'t.A'" in v["detail"]
        assert len([s for s in v["stacks"] if s]) == 2


@pytest.mark.parametrize("where", ["format_stack", "section"])
def test_finaliser_run_by_the_collector_does_not_deadlock(where, monkeypatch):
    """``gc.collect()`` under ``format_stack`` (the parent's hang) and
    inside the section: the ``__del__`` takes its lock, returns, and its
    edges are there afterwards."""
    from ray_tpu.analysis import sanitizers as san

    san.enable(True)
    with san.scoped(drop_prefixes=("t.",)), monkeypatch.context() as m:
        a, b, c = (san.SanitizedLock(n) for n in ("t.A", "t.B", "t.C"))
        if where == "section":
            m.setattr(san, "_graph_lock",
                      _Reentered(lambda: _collect_one(c)))
        else:
            _once_under_format_stack(m, lambda: _collect_one(c))
        with time_limit(5, f"collector under {where}"):
            with a:
                with b:
                    pass
        edges = san.lock_order_edges()
        assert {("t.A", "t.B"), ("t.A", "t.C"), ("t.B", "t.C")} <= set(edges)


@pytest.mark.parametrize(
    "entry", ["acquire", "reset", "scoped", "lock_order_edges"])
def test_graph_section_reentered_by_a_finaliser(entry, monkeypatch):
    """Every way into the graph survives a finaliser that takes a
    sanitized lock inside it, and the finaliser's edge is recorded once
    the section is left — after a ``reset()`` too."""
    from ray_tpu.analysis import sanitizers as san

    san.enable(True)
    with san.scoped(drop_prefixes=("t.", "u.")), \
            monkeypatch.context() as m:
        a, b, c = (san.SanitizedLock(n) for n in ("t.A", "t.B", "t.C"))

        def finaliser():
            with c:
                pass

        lock = _Reentered(finaliser)
        m.setattr(san, "_graph_lock", lock)
        with time_limit(5, f"{entry} re-entered"):
            with a:
                if entry == "acquire":
                    with b:
                        pass
                elif entry == "reset":
                    san.reset()
                elif entry == "scoped":
                    with san.scoped(drop_prefixes=("u.",)):
                        pass
                else:
                    san.lock_order_edges()
        assert lock.entered
        m.undo()
        assert ("t.A", "t.C") in san.lock_order_edges()
        assert not a.locked() and not c.locked()


def test_sanitized_condition_wait_notify():
    from ray_tpu.analysis import sanitizers as san

    san.enable(True)
    with san.scoped(drop_prefixes=("t.",)):
        base = san.violation_counts()
        cond = san.make_condition("t.cond")
        hits = []

        def waiter():
            with cond:
                while not hits:
                    cond.wait(timeout=5)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.1)
        with cond:
            hits.append(1)
            cond.notify_all()
        t.join(timeout=5)
        assert not t.is_alive()
        assert san.violation_counts() == base


def test_loop_watchdog_catches_blocked_loop():
    from ray_tpu.analysis import sanitizers as san
    from ray_tpu.core.config import _config
    from ray_tpu.core.rpc import EventLoopThread

    san.enable(True)
    old_stall = _config.sanitize_loop_stall_s
    old_ping = _config.sanitize_loop_ping_interval_s
    _config.sanitize_loop_stall_s = 0.3
    _config.sanitize_loop_ping_interval_s = 0.1
    elt = None
    try:
        with san.scoped(drop_prefixes=("watchdog-test",)):
            base = san.violation_counts().get("loop_stall", 0)
            elt = EventLoopThread(name="watchdog-test-io")

            async def block():
                # longer than a ping interval the process's one watchdog may
                # still be sleeping out (the default, 1 s) + the stall limit:
                # at 1.2 s a run's load decided whether it saw the block
                time.sleep(2.0)  # raylint: disable=RT001(fixture: deliberately blocks the loop to trip the watchdog)

            elt.spawn(block())
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if san.violation_counts().get("loop_stall", 0) > base:
                    break
                time.sleep(0.05)
            assert san.violation_counts().get("loop_stall", 0) > base, \
                "watchdog missed a 2 s loop block"
            v = san.violations("loop_stall")[-1]
            assert "heartbeat" in v["detail"]
    finally:
        _config.sanitize_loop_stall_s = old_stall
        _config.sanitize_loop_ping_interval_s = old_ping
        if elt is not None:
            elt.stop()


def test_thread_affinity_assert():
    from ray_tpu.analysis import sanitizers as san

    san.enable(True)
    with san.scoped(drop_prefixes=("t.",)):
        base = san.violation_counts()
        san.assert_thread_affinity("t.struct", threading.get_ident())
        assert san.violation_counts() == base
        san.assert_thread_affinity("t.struct", threading.get_ident() + 1)
        assert san.violation_counts().get("affinity", 0) == \
            base.get("affinity", 0) + 1


def test_sanitizer_counts_in_summarize_metrics(ray_start_local):
    from ray_tpu.analysis import sanitizers as san
    from ray_tpu.util import state

    san.enable(True)
    with san.scoped(drop_prefixes=("test",)):
        san.record_violation("loop_stall", "test", "fixture violation")
        m = state.summarize_metrics()
        assert m["sanitizer_violations"].get("loop_stall", 0) >= 1


# --------------------------------------------------------------------- CLI
def test_cli_lint_json_and_exit_codes(tmp_path):
    from ray_tpu.scripts import main

    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import asyncio

        def kick(self):
            asyncio.ensure_future(self._dispatch())
    """))
    assert main(["lint", str(bad)]) == 1
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    assert main(["lint", str(good)]) == 0
    # --json emits machine-readable findings
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["lint", "--json", str(bad)])
    assert rc == 1
    data = json.loads(buf.getvalue())
    assert data["findings"] and data["findings"][0]["rule"] == "RT003"
    assert data["clean"] is False


# ------------------------------------------------------------- tier-1 gate
@pytest.mark.lint
def test_package_lint_clean():
    """THE gate: zero unsuppressed raylint findings over the whole
    package, no framework errors, no stale baseline entries."""
    from ray_tpu.analysis import lint_package

    res = lint_package()
    msg = "\n".join(str(f) for f in res.unsuppressed)
    assert res.unsuppressed == [], f"raylint findings:\n{msg}"
    assert res.errors == [], f"raylint errors:\n" + "\n".join(res.errors)
    assert res.files > 100  # sanity: the walk really covered the package
