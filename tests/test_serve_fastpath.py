"""Serve fast-path dispatch: compiled-channel routing for steady traffic.

Covers the PR-13 tentpole guarantees:
- warmed (deployment, replica) pairs dispatch over compiled channels while
  SLO metrics, admission accounting, deadline shedding and breaker votes
  keep firing per request (asserted, not assumed);
- a replica killed mid-fast-path degrades to the router slow path with one
  budgeted retry and no user-visible error;
- the async admission API (remote_async) queues without blocking a thread;
- the per-replica stream cap bounds open streaming responses.
"""

import asyncio
import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu import serve
from ray_tpu.core.config import _config


@pytest.fixture
def fast_warmup():
    """Drop the fast-path warmup threshold so tests engage it quickly."""
    saved = (_config.serve_fastpath_warmup_requests,
             _config.serve_fastpath_enabled)
    _config.serve_fastpath_warmup_requests = 4
    _config.serve_fastpath_enabled = True
    yield
    (_config.serve_fastpath_warmup_requests,
     _config.serve_fastpath_enabled) = saved


def _warm(handle, deployment: str, want: int = 1, timeout: float = 30.0):
    """Drive routed traffic until `want` fast-path channels are ready."""
    router = handle._router
    deadline = time.monotonic() + timeout
    i = 0
    while time.monotonic() < deadline:
        if router._fastpath.ready_deployments().get(deployment, 0) >= want:
            return
        ray_tpu.get(handle.remote(i), timeout=60)
        i += 1
        time.sleep(0.01)
    raise AssertionError(
        f"fast path never warmed: {router._fastpath.ready_deployments()}"
    )


def _metric_total(name: str, deployment: str):
    from ray_tpu.util import metrics as m

    for s in m.get_registry().collect():
        if s["name"] != name:
            continue
        want = ("deployment", deployment)
        if s["kind"] == "histogram":
            return sum(
                v[-1] for k, v in s["points"].items() if want in k
            )
        return sum(v for k, v in s["points"].items() if want in k)
    return 0


def test_fastpath_engages_and_preserves_slo_accounting(fast_warmup):
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        @serve.deployment(name="fp_echo")
        class Echo:
            def __call__(self, x):
                return x * 3

        handle = serve.run(Echo.bind())
        _warm(handle, "fp_echo")

        req_before = _metric_total("serve_requests_total", "fp_echo")
        e2e_before = _metric_total("serve_request_latency_ms", "fp_echo")
        fp_before = _metric_total("serve_fastpath_requests_total", "fp_echo")

        refs = [handle.remote(i) for i in range(20)]
        assert [ray_tpu.get(r, timeout=60) for r in refs] == \
            [3 * i for i in range(20)]

        # per-request accounting fired ON the fast path: arrival counter,
        # e2e latency histogram, and the fast-path dispatch counter
        assert _metric_total("serve_requests_total", "fp_echo") \
            == req_before + 20
        assert _metric_total("serve_request_latency_ms", "fp_echo") \
            >= e2e_before + 20
        assert _metric_total("serve_fastpath_requests_total", "fp_echo") \
            >= fp_before + 20
        # admission slots all released (inflight back to zero)
        router = handle._router
        with router._lock:
            assert sum(router._inflight.get("fp_echo", {}).values()) == 0
        # user exceptions surface typed AND count as errors, replica stays
        err_before = _metric_total("serve_request_errors_total", "fp_echo")
        with pytest.raises(TypeError):
            ray_tpu.get(handle.remote(), timeout=60)  # missing arg -> user err
        assert _metric_total("serve_request_errors_total", "fp_echo") \
            == err_before + 1
        assert router._fastpath.ready_deployments().get("fp_echo", 0) >= 1
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_fastpath_respects_admission_and_deadline(fast_warmup):
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        @serve.deployment(name="fp_adm", max_ongoing_requests=1,
                          max_queued_requests=1)
        class Echo:
            def __call__(self, x, sleep_s=0.0):
                if sleep_s:
                    time.sleep(sleep_s)
                return x

        handle = serve.run(Echo.bind())
        _warm(handle, "fp_adm")
        shed_before = _metric_total("serve_shed_total", "fp_adm")

        # saturate from concurrent callers: 1 executing + 1 queued at the
        # router; the burst overflow sheds typed even though the pair has a
        # warmed channel (admission gates the fast path too)
        sheds, oks = [], []
        lock = threading.Lock()

        def fire(i):
            try:
                ray_tpu.get(handle.remote(i, sleep_s=0.3), timeout=60)
                with lock:
                    oks.append(i)
            except exc.BackPressureError:
                with lock:
                    sheds.append(i)

        threads = [
            threading.Thread(target=fire, args=(i,)) for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert sheds, (sheds, oks)
        assert oks, (sheds, oks)
        assert _metric_total("serve_shed_total", "fp_adm") \
            >= shed_before + len(sheds)

        # expired deadline sheds typed BEFORE dispatch (fast path or not)
        dl_before = _metric_total("serve_deadline_expired_total", "fp_adm")
        with pytest.raises(exc.DeadlineExceededError):
            handle.options(timeout_s=-0.1).remote(0)
        assert _metric_total("serve_deadline_expired_total", "fp_adm") \
            == dl_before + 1
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_replica_killed_mid_fastpath_degrades_to_slow_path(fast_warmup,
                                                           tmp_path):
    """The satellite chaos scenario: kill the pinned replica with fast-path
    requests in flight; every request resolves (one budgeted retry on a
    healthy replica), the breaker/eviction plane observes the death, and
    request/latency accounting stays consistent."""
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        @serve.deployment(name="fp_kill", num_replicas=2)
        class Echo:
            def __call__(self, x, gate=None):
                # the burst waits at the gate: it is in flight when its
                # replica is killed, however fast the replica and however
                # slow the kill
                deadline = time.monotonic() + 60
                while gate and not os.path.exists(gate) \
                        and time.monotonic() < deadline:
                    time.sleep(0.01)
                return x + 7

        handle = serve.run(Echo.bind())
        router = handle._router
        _warm(handle, "fp_kill")

        with router._fastpath._lock:
            key = next(
                k for k, p in router._fastpath._pairs.items()
                if p.state == "ready"
            )
        _, rkey = key
        with router._lock:
            victim = next(
                r for r in router._replicas["fp_kill"]
                if r._actor_id.binary() == rkey
            )
        retries_before = router.retry_count
        failovers_before = _metric_total("serve_failovers_total", "fp_kill")
        req_before = _metric_total("serve_requests_total", "fp_kill")
        e2e_before = _metric_total("serve_request_latency_ms", "fp_kill")

        gate = str(tmp_path / "gate")
        refs = [handle.remote(i, gate) for i in range(10)]
        ray_tpu.kill(victim)
        open(gate, "w").close()
        # no user-visible error beyond the typed retry semantics: every
        # ref resolves with the correct value
        assert [ray_tpu.get(r, timeout=60) for r in refs] == \
            [i + 7 for i in range(10)]

        # budgeted retries happened (fastpath_failover spends a token per
        # retry — an empty bucket would have surfaced typed
        # RetryBudgetExhaustedError instead of the values above), the dead
        # replica was evicted + reported, and accounting is consistent
        assert router.retry_count > retries_before
        assert _metric_total("serve_failovers_total", "fp_kill") \
            >= failovers_before + 1
        assert _metric_total("serve_requests_total", "fp_kill") \
            == req_before + 10
        assert _metric_total("serve_request_latency_ms", "fp_kill") \
            >= e2e_before + 10
        # fallbacks recorded; in-flight slots all released
        assert _metric_total("serve_fastpath_fallbacks_total", "fp_kill") >= 1
        with router._lock:
            assert sum(router._inflight.get("fp_kill", {}).values()) == 0
        # traffic keeps flowing afterwards (slow path on survivors)
        assert ray_tpu.get(handle.remote(1), timeout=60) == 8
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_remote_async_queues_without_blocking_thread():
    ray_tpu.init(local_mode=True)
    try:
        @serve.deployment(name="fp_async", max_ongoing_requests=1,
                          max_queued_requests=100)
        class Slow:
            def __call__(self, x):
                time.sleep(0.08)
                return x

        handle = serve.run(Slow.bind())
        assert ray_tpu.get(handle.remote(0), timeout=30) == 0

        async def main():
            ticks = 0
            stop = asyncio.Event()

            async def ticker():
                nonlocal ticks
                while not stop.is_set():
                    ticks += 1
                    await asyncio.sleep(0.01)

            t = asyncio.get_running_loop().create_task(ticker())
            refs = await asyncio.gather(
                *[handle.remote_async(i) for i in range(6)]
            )
            stop.set()
            await t
            return ticks, [ray_tpu.get(r, timeout=30) for r in refs]

        ticks, out = asyncio.new_event_loop().run_until_complete(main())
        assert sorted(out) == list(range(6))
        # admission serialized ~0.5s of work; the loop must have kept
        # ticking through it (the wait parks a future, not the thread)
        assert ticks > 10, ticks

        async def shed():
            # queue bound still sheds typed on the async path: capacity 1
            # is held by a blocker, the queue admits 1, the rest of the
            # burst sheds BackPressureError
            hb = serve.run(Slow.options(
                name="fp_async2", max_ongoing_requests=1,
                max_queued_requests=1,
            ).bind())
            blocker = hb.remote("blocker")
            with pytest.raises(exc.BackPressureError):
                await asyncio.gather(
                    *[hb.remote_async(i) for i in range(8)]
                )
            ray_tpu.get(blocker, timeout=30)

        asyncio.new_event_loop().run_until_complete(shed())
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_stream_cap_bounds_open_streams():
    ray_tpu.init(local_mode=True)
    try:
        @serve.deployment(name="fp_streams", max_ongoing_streams=2,
                          max_ongoing_requests=8)
        class Streamy:
            def __init__(self):
                self.release = threading.Event()

            def __call__(self, cmd):
                if cmd == "release":
                    self.release.set()
                    return "released"

                def gen():
                    yield "header-chunk"
                    self.release.wait(timeout=30)
                    yield "tail-chunk"

                return gen()

        handle = serve.run(Streamy.bind())
        open_streams = []
        for _ in range(2):
            it = handle.stream("open")
            assert next(it) == "header-chunk"  # stream is now OPEN
            open_streams.append(it)
        # the cap: a third concurrently-open stream sheds typed
        with pytest.raises(exc.BackPressureError):
            list(handle.stream("open"))
        # unary admission is NOT starved by the open streams
        assert ray_tpu.get(handle.remote("release"), timeout=30) \
            == "released"
        for it in open_streams:
            assert list(it) == ["tail-chunk"]
        # slots freed: a new stream opens fine
        assert list(handle.stream("open")) == ["header-chunk", "tail-chunk"]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
