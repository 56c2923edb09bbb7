"""Production SLO observability (PR 8): request-latency instrumentation,
metrics time series, and crash-safe trace forensics.

Parity targets: python/ray/_private/metrics_agent.py + prometheus_exporter
(exposition correctness), the dashboard's time-series charts (bounded
retention behind the /metrics snapshot), and the reference's task-event
durability gap (a SIGKILLed worker's unflushed TaskEventBuffer) closed here
with a per-worker WAL the raylet recovers.
"""

import json
import os
import re
import time
import urllib.request

import pytest

# ---------------------------------------------------------------- unit level


def _lint_prometheus(text: str) -> None:
    """Mini exposition-format lint: every histogram's buckets must be
    cumulative and non-decreasing in file order, the +Inf bucket must equal
    _count for the same tag set, and no raw (unescaped) newline may appear
    inside a label value (a quote-parity scan per line)."""
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        assert line.count('"') % 2 == 0, f"unbalanced quotes: {line!r}"
    buckets = {}
    counts = {}
    for line in text.splitlines():
        m = re.match(r"^(\w+)_bucket\{(.*)\}\s+(\S+)$", line)
        if m:
            name, tags, val = m.groups()
            le = re.search(r'le="([^"]*)"', tags).group(1)
            rest = re.sub(r',?le="[^"]*"', "", tags)
            buckets.setdefault((name, rest), []).append((le, float(val)))
            continue
        m = re.match(r"^(\w+)_count(?:\{(.*)\})?\s+(\S+)$", line)
        if m:
            name, tags, val = m.groups()
            counts[(name, tags or "")] = float(val)
    assert buckets, "no histogram buckets in exposition"
    for (name, tags), rows in buckets.items():
        vals = [v for _, v in rows]
        assert vals == sorted(vals), f"{name}{{{tags}}} not cumulative: {rows}"
        assert rows[-1][0] == "+Inf", f"{name}{{{tags}}} missing +Inf"
        assert rows[-1][1] == counts[(name, tags)], (
            f"{name}{{{tags}}}: +Inf {rows[-1][1]} != count "
            f"{counts[(name, tags)]}"
        )


def test_prometheus_tag_value_escaping():
    """Satellite: backslash, double quote and newline in tag values must be
    escaped per the text exposition format (previously interpolated raw,
    which corrupted every line after the first embedded newline)."""
    from ray_tpu.util.metrics import render_prometheus

    text = render_prometheus([
        {"name": "esc_total", "kind": "counter", "description": 'a\\b "c"\nd',
         "boundaries": [],
         "points": {(("route", 'x\\y"z"\nw'),): 2.0}},
    ])
    assert r'route="x\\y\"z\"\nw"' in text
    assert "# HELP esc_total a\\\\b \"c\"\\nd" in text
    # the rendered body must stay line-parseable
    for line in text.splitlines():
        assert line.count('"') % 2 == 0
    _ = _lint_prometheus  # escaping lint reused by the cluster test


def test_prometheus_histogram_exposition_lint():
    from ray_tpu.util.metrics import render_prometheus

    text = render_prometheus([
        {"name": "lat_ms", "kind": "histogram", "description": "lat",
         "boundaries": [1, 10],
         "points": {
             (("deployment", "A"),): [3, 2, 1, 25.0, 6],
             (("deployment", "B"),): [0, 0, 4, 400.0, 4],
         }},
    ])
    _lint_prometheus(text)
    assert 'lat_ms_bucket{deployment="A",le="+Inf"} 6' in text


def test_timeseries_ring_bounds_and_query():
    from ray_tpu.util.metrics import MetricsTimeSeries

    ts = MetricsTimeSeries(depth=5)
    for i in range(12):
        ts.sample(
            [{"name": "c", "kind": "counter", "description": "",
              "boundaries": [], "points": {(): float(i)}},
             {"name": "other", "kind": "gauge", "description": "",
              "boundaries": [], "points": {(): 1.0}}],
            ts=float(i),
        )
    assert len(ts) == 5  # bounded: oldest evicted
    samples = ts.query()
    assert [s["ts"] for s in samples] == [7.0, 8.0, 9.0, 10.0, 11.0]
    # name filter + limit
    filtered = ts.query(names=["c"], limit=2)
    assert len(filtered) == 2
    assert all(len(s["series"]) == 1 and s["series"][0]["name"] == "c"
               for s in filtered)


def test_rate_and_percentile_helpers():
    from ray_tpu.util.metrics import (
        counter_rate,
        histogram_percentile,
        window_percentile,
    )

    mk = lambda t, v: {
        "ts": t,
        "series": [{"name": "c", "kind": "counter", "description": "",
                    "boundaries": [], "points": {(): v}}],
    }
    assert counter_rate([mk(0, 0.0), mk(10, 50.0)], "c") == 5.0
    # counter reset (process restart) clamps to 0, never negative
    assert counter_rate([mk(0, 100.0), mk(10, 20.0)], "c") == 0.0
    assert counter_rate([mk(0, 1.0)], "c") is None  # one sample: no rate

    # percentile interpolates inside the winning bucket
    assert histogram_percentile([10, 100], [10, 0, 0], 0.5) == 5.0
    assert histogram_percentile([10, 100], [0, 10, 0], 1.0) == 100.0
    assert histogram_percentile([10, 100], [0, 0, 0], 0.5) is None

    # windowed percentile uses bucket DELTAS between first and last sample
    h = lambda t, pts: {
        "ts": t,
        "series": [{"name": "h", "kind": "histogram", "description": "",
                    "boundaries": [10, 100], "points": {(): pts}}],
    }
    samples = [h(0, [100, 0, 0, 100.0, 100]),   # history: all fast
               h(10, [100, 50, 0, 3000.0, 150])]  # window: 50 slow obs
    p = window_percentile(samples, "h", 0.5)
    assert p is not None and p > 10  # the window's median is in (10, 100]

    # tag filtering sums only matching points
    tagged = [{
        "ts": 0.0,
        "series": [{"name": "c", "kind": "counter", "description": "",
                    "boundaries": [],
                    "points": {(("deployment", "A"),): 1.0,
                               (("deployment", "B"),): 100.0}}],
    }, {
        "ts": 1.0,
        "series": [{"name": "c", "kind": "counter", "description": "",
                    "boundaries": [],
                    "points": {(("deployment", "A"),): 3.0,
                               (("deployment", "B"),): 100.0}}],
    }]
    assert counter_rate(tagged, "c", {"deployment": "A"}) == 2.0


def test_aggregator_per_job_retention():
    """Satellite: a chatty job evicts its OWN oldest tasks at the per-job
    cap; another job's history survives untouched."""
    from ray_tpu.tracing import TaskEventAggregator

    agg = TaskEventAggregator(max_tasks=1000, max_tasks_per_job=5)
    for i in range(20):
        agg.ingest([{"task_id": f"noisy-{i}", "name": "spam",
                     "state": "FINISHED", "ts": float(i), "job_id": "j1"}])
    for i in range(3):
        agg.ingest([{"task_id": f"quiet-{i}", "name": "rare",
                     "state": "FINISHED", "ts": 100.0 + i, "job_id": "j2"}])
    summary = agg.summarize()
    assert summary["tasks"]["spam"]["FINISHED"] == 5      # capped per job
    assert summary["tasks"]["rare"]["FINISHED"] == 3      # untouched
    assert summary["evicted_per_job"]["j1"] == 15
    assert agg.get_task("noisy-0") is None
    assert agg.get_task("noisy-19") is not None
    assert agg.get_task("quiet-0") is not None
    # jobless events still ride only the global cap
    agg.ingest([{"task_id": "nojob", "name": "x", "state": "FINISHED",
                 "ts": 1.0}])
    assert agg.get_task("nojob") is not None


def test_aggregator_derives_task_duration_histograms():
    """Core task latency series come from the lifecycle events already
    flowing into the aggregator — no new hot-path cost."""
    from ray_tpu.tracing import TaskEventAggregator
    from ray_tpu.util.metrics import get_registry

    agg = TaskEventAggregator(max_tasks=100)
    agg.ingest([
        {"task_id": "d1", "name": "dur_fn", "state": "SUBMITTED", "ts": 1.0},
        {"task_id": "d1", "name": "dur_fn", "state": "RUNNING", "ts": 1.1},
        {"task_id": "d1", "name": "dur_fn", "state": "EXECUTED", "ts": 1.3},
        {"task_id": "d1", "name": "dur_fn", "state": "FINISHED", "ts": 1.4},
    ])
    snaps = {s["name"]: s for s in get_registry().collect()}
    key = (("name", "dur_fn"),)
    e2e = snaps["task_e2e_ms"]["points"][key]
    ex = snaps["task_exec_ms"]["points"][key]
    assert e2e[-1] == 1 and abs(e2e[-2] - 400.0) < 1      # count, sum(ms)
    assert ex[-1] == 1 and abs(ex[-2] - 200.0) < 1


def test_wal_append_read_truncate(tmp_path):
    """The WAL holds every recorded event, tolerates a torn final line, and
    truncates once a flush drained the buffer (so recovery replays only the
    genuinely-unflushed tail)."""
    from ray_tpu.tracing import TaskEventBuffer, read_wal

    wal = str(tmp_path / "w.jsonl")
    buf = TaskEventBuffer(capacity=100)
    assert buf.enable_wal(wal)
    for i in range(4):
        buf.record(task_id=f"{i:032x}", name="t", state="RUNNING")
    events = read_wal(wal)
    assert [e["task_id"] for e in events] == [f"{i:032x}" for i in range(4)]
    assert all(e["state"] == "RUNNING" for e in events)

    # torn tail (SIGKILL mid-write): parse what's intact, skip the fragment
    with open(wal, "ab") as f:
        f.write(b'{"task_id": "fff')
    assert len(read_wal(wal)) == 4

    # flush drained the buffer -> WAL truncates to empty
    drained, _ = buf.drain()
    assert len(drained) == 4
    buf.wal_flushed()
    assert read_wal(wal) == []
    # and keeps working after truncation
    buf.record(task_id="a" * 32, name="t", state="FAILED")
    assert [e["state"] for e in read_wal(wal)] == ["FAILED"]

    # busy-worker path: events recorded AFTER the drain but before the
    # flush settles stay buffered — wal_flushed rewrites the file down to
    # exactly those, so the WAL never replays already-aggregated events
    buf.drain()
    buf.wal_flushed()
    buf.record(task_id="b" * 32, name="t", state="RUNNING")
    buf.drain()
    buf.record(task_id="c" * 32, name="t", state="RUNNING")  # post-drain
    buf.wal_flushed()  # buffer non-empty: rewrite, not skip
    assert [e["task_id"] for e in read_wal(wal)] == ["c" * 32]
    # appends continue on the re-opened file
    buf.record(task_id="d" * 32, name="t", state="EXECUTED")
    assert [e["task_id"] for e in read_wal(wal)] == ["c" * 32, "d" * 32]


# --------------------------------------------------------------- local level
def test_local_timeseries_history_and_state_helpers(ray_start_local):
    """Local-backend parity: the in-process sampler gives
    get_metrics_timeseries real history, and the rate/percentile helpers
    work against it (tier-1-testable retention layer)."""
    ray = ray_start_local
    from ray_tpu.core.config import _config
    from ray_tpu.util import state
    from ray_tpu.util.metrics import Counter, Histogram

    saved = _config.metrics_report_interval_ms
    _config.metrics_report_interval_ms = 100
    try:
        c = Counter("slo_local_total", tag_keys=("deployment",))
        h = Histogram("slo_local_ms", boundaries=[1, 10, 100],
                      tag_keys=("deployment",))
        tags = {"deployment": "L"}
        c.inc(3.0, tags)
        h.observe(5.0, tags)
        time.sleep(0.35)  # let the sampler take periodic samples
        c.inc(3.0, tags)
        h.observe(50.0, tags)
        samples = state.get_metrics_timeseries(names=["slo_local_total",
                                                      "slo_local_ms"])
        assert len(samples) >= 2  # periodic history, not just one snapshot
        assert samples[-1]["ts"] >= samples[0]["ts"]
        rate = state.metric_rate("slo_local_total", tags, samples=samples)
        assert rate is not None and rate > 0
        p99 = state.metric_percentile("slo_local_ms", 0.99, tags,
                                      samples=samples)
        p50 = state.metric_percentile("slo_local_ms", 0.5, tags,
                                      samples=samples)
        assert p50 is not None and p99 is not None and p50 <= p99
    finally:
        _config.metrics_report_interval_ms = saved


# ------------------------------------------------------------- cluster level
@pytest.fixture
def cluster():
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


def test_serve_slo_pipeline_cluster(cluster):
    """Acceptance: a cluster-mode serve request populates per-deployment
    e2e/queue/exec latency histograms visible on the dashboard /metrics
    endpoint AND in get_metrics_timeseries history; the exposition passes
    the format lint; rpc_* wire counters aggregate as real counters; task
    events carry the job id."""
    ray = cluster
    from ray_tpu import serve
    from ray_tpu.api import _global_worker
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.util import state

    @serve.deployment
    class Echo:
        def __call__(self, x):
            return x * 2

    try:
        handle = serve.run(Echo.bind())
        n = 8
        assert [ray.get(handle.remote(i), timeout=60) for i in range(n)] \
            == [i * 2 for i in range(n)]

        # replica registry flush (2s) + GCS sample loop (2s): each series is
        # waited for by name and count, however long the box takes
        gcs_addr = _global_worker().backend.core.gcs_address
        dash = start_dashboard(gcs_addr, port=0)
        want = ('serve_request_latency_ms_bucket{deployment="Echo"',
                'serve_exec_latency_ms_bucket{deployment="Echo"',
                'serve_queue_wait_ms_bucket{deployment="Echo"',
                # derived core-task series + cluster-wide rpc wire counters
                "task_e2e_ms_bucket",
                "# TYPE rpc_frames_sent counter")

        def requests_served(text):
            m = re.search(
                r'serve_requests_total\{deployment="Echo"\} (\S+)', text)
            return float(m.group(1)) if m else 0.0

        deadline = time.monotonic() + 45
        while True:
            with urllib.request.urlopen(dash.url + "/metrics",
                                        timeout=10) as r:
                text = r.read().decode()
            if all(w in text for w in want) and requests_served(text) >= n:
                break
            if time.monotonic() >= deadline:
                break
            time.sleep(0.5)
        for w in want:
            assert w in text, f"missing {w!r} in /metrics:\n{text[:3000]}"
        assert requests_served(text) >= n
        _lint_prometheus(text)

        # the same series are in the retained TIME SERIES, with history: two
        # samples hold the latency histogram, and Echo's requests are in it
        tags = {"deployment": "Echo"}
        deadline = time.monotonic() + 45
        while True:
            samples = state.get_metrics_timeseries(
                names=["serve_requests_total", "serve_request_latency_ms",
                       "serve_exec_latency_ms"]
            )
            with_latency = [
                s for s in samples
                if any(x["name"] == "serve_request_latency_ms"
                       for x in s["series"])
            ]
            p50 = state.metric_percentile("serve_request_latency_ms", 0.5,
                                          tags, samples=samples)
            p99 = state.metric_percentile("serve_request_latency_ms", 0.99,
                                          tags, samples=samples)
            if (len(with_latency) >= 2 and None not in (p50, p99)) \
                    or time.monotonic() >= deadline:
                break
            time.sleep(0.5)
        assert len(with_latency) >= 2
        assert p50 is not None and p99 is not None and p50 <= p99

        # dashboard JSON timeseries + the top-like CLI rendering
        with urllib.request.urlopen(dash.url + "/api/timeseries?limit=10",
                                    timeout=10) as r:
            ts_json = json.loads(r.read())
        assert isinstance(ts_json, list) and ts_json
        assert any(x["name"] == "serve_requests_total"
                   for s in ts_json for x in s["series"])
        from ray_tpu.scripts import render_metrics_snapshot

        rendered = render_metrics_snapshot(state.get_metrics_timeseries())
        assert "Echo" in rendered and "qps" in rendered

        # `scripts metrics --dashboard`: the HTTP path renders the SAME
        # view from /api/timeseries with NO driver connection — the JSON
        # converter restores the internal tag-tuple point keys
        from ray_tpu.scripts import _fetch_timeseries_http

        http_samples = _fetch_timeseries_http(
            dash.url, limit=30
        )
        http_rendered = render_metrics_snapshot(http_samples)
        assert "Echo" in http_rendered and "qps" in http_rendered
        dash.stop()

        # per-job retention plumbing: task events carry the driver's job id
        rows = [r for r in state.list_tasks() if r["name"] == "handle_request"]
        assert rows
        t = state.get_task(rows[-1]["task_id"])
        assert any(e.get("job_id") for e in t["events"]), \
            "task events carry no job_id"
    finally:
        serve.shutdown()


@pytest.mark.chaos(timeout=180)
def test_wal_recovers_sigkilled_worker_events():
    """Acceptance (ROADMAP WAL item): a SIGKILLed worker's unflushed events
    are recovered from its WAL by the raylet and land in the aggregator —
    the killed task's timeline shows the worker-side RUNNING state and the
    previous call's profile span, and still terminates FAILED."""
    import ray_tpu
    from ray_tpu.testing import chaos
    from ray_tpu.util import state

    ray_tpu.shutdown()
    # workers flush every 60s -> every worker-side event of this test stays
    # unflushed and ONLY the WAL can deliver it. The driver keeps its normal
    # 1s flush (its _config predates the env var), so owner-side
    # SUBMITTED/FAILED still arrive on time.
    os.environ["RAY_TPU_TASK_EVENTS_FLUSH_INTERVAL_MS"] = "60000"
    try:
        with chaos.plan(seed=31).kill_actor(match="Victim.work",
                                            after_calls=2):
            ray_tpu.init(num_cpus=2, num_tpus=0)
            try:
                @ray_tpu.remote(max_restarts=0)
                class Victim:
                    def work(self):
                        from ray_tpu import tracing

                        with tracing.profile_span("last-breath"):
                            pass
                        return 1

                v = Victim.remote()
                assert ray_tpu.get(v.work.remote(), timeout=60) == 1
                dead_ref = v.work.remote()
                with pytest.raises(ray_tpu.exceptions.ActorDiedError):
                    ray_tpu.get(dead_ref, timeout=60)

                # WAL recovery is raylet-async (poll_deaths ~50ms + notify);
                # poll until the killed task's worker-side RUNNING appears
                deadline = time.monotonic() + 30
                states = []
                while time.monotonic() < deadline:
                    t = state.get_task(dead_ref.task_id.hex())
                    states = [e["state"] for e in (t or {}).get("events", [])]
                    if t and "RUNNING" in states and t["state"] == "FAILED":
                        break
                    time.sleep(0.5)
                assert t is not None and t["state"] == "FAILED", states
                assert "RUNNING" in states, (
                    f"worker-side RUNNING not recovered from WAL: {states}"
                )
                lifecycle = [s for s in states if s != "PROFILE"]
                assert lifecycle[-1] == "FAILED", lifecycle

                # call 1's span was also unflushed — recovered via the WAL
                spans = [
                    e for e in state.timeline_events()
                    if e.get("state") == "PROFILE"
                    and e.get("name") == "last-breath"
                ]
                assert spans, "profile span from the WAL never surfaced"
            finally:
                ray_tpu.shutdown()
    finally:
        os.environ.pop("RAY_TPU_TASK_EVENTS_FLUSH_INTERVAL_MS", None)


def test_samples_from_dashboard_json_roundtrip():
    """The /api/timeseries JSON shape (points as tag-dict lists) converts
    back into the internal sample shape the metrics math consumes: rates
    and histogram percentiles computed over HTTP-fetched samples match the
    driver-connection path."""
    from ray_tpu.scripts import samples_from_dashboard_json
    from ray_tpu.util.metrics import counter_rate, window_percentile

    data = [
        {
            "ts": 100.0,
            "series": [
                {"name": "serve_requests_total", "kind": "counter",
                 "boundaries": [],
                 "points": [{"tags": {"deployment": "d"}, "value": 10.0}]},
                {"name": "serve_request_latency_ms", "kind": "histogram",
                 "boundaries": [1.0, 10.0],
                 "points": [{"tags": {"deployment": "d"},
                             "value": [0.0, 0.0, 0.0, 0.0, 0.0]}]},
            ],
        },
        {
            "ts": 110.0,
            "series": [
                {"name": "serve_requests_total", "kind": "counter",
                 "boundaries": [],
                 "points": [{"tags": {"deployment": "d"}, "value": 30.0}]},
                {"name": "serve_request_latency_ms", "kind": "histogram",
                 "boundaries": [1.0, 10.0],
                 "points": [{"tags": {"deployment": "d"},
                             "value": [0.0, 20.0, 0.0, 110.0, 20.0]}]},
            ],
        },
    ]
    samples = samples_from_dashboard_json(data)
    assert samples[0]["series"][0]["points"] == {
        (("deployment", "d"),): 10.0
    }
    assert counter_rate(samples, "serve_requests_total",
                        {"deployment": "d"}) == pytest.approx(2.0)
    p50 = window_percentile(samples, "serve_request_latency_ms", 0.5,
                            {"deployment": "d"})
    assert p50 is not None and 1.0 <= p50 <= 10.0


def test_quantile_sketch_accuracy_and_merge():
    """PR-13: histograms carry a DDSketch-style quantile sketch beside the
    exposition buckets — tail percentiles come out within ~1% relative
    error instead of bucket interpolation (a p99 inside the 1000..2500ms
    bucket used to be anywhere in a 2.5x span)."""
    import random

    from ray_tpu.util import metrics as m

    h = m.Histogram("sketch_test_lat_ms", boundaries=[1, 10, 100, 1000],
                    tag_keys=("k",))
    rng = random.Random(7)
    vals = [rng.lognormvariate(3.0, 1.2) for _ in range(4000)]
    for v in vals:
        h.observe(v, {"k": "a"})
    snap = next(
        s for s in m.get_registry().collect()
        if s["name"] == "sketch_test_lat_ms"
    )
    assert "sketches" in snap
    sk = snap["sketches"][(("k", "a"),)]
    vals.sort()
    for q in (0.5, 0.9, 0.99):
        est = m.sketch_percentile(sk, q)
        true = vals[int(q * (len(vals) - 1))]
        assert abs(est - true) / true < 0.03, (q, est, true)
    # exposition buckets stay exact (the /metrics contract is unchanged):
    # bucket counts sum to the observation count
    pt = snap["points"][(("k", "a"),)]
    assert sum(pt[:-2]) == pt[-1] == len(vals)

    # merge: sketches sum bucket-wise across sources like histograms do
    import time as _t

    merged = m.merge_snapshots({
        "s1": (_t.time(), [snap]), "s2": (_t.time(), [snap]),
    })
    msnap = next(s for s in merged if s["name"] == "sketch_test_lat_ms")
    msk = msnap["sketches"][(("k", "a"),)]
    assert sum(msk["c"].values()) == 2 * sum(sk["c"].values())
    est = m.sketch_percentile(msk, 0.99)
    true = vals[int(0.99 * (len(vals) - 1))]
    assert abs(est - true) / true < 0.03  # merging two copies moves nothing


def test_window_percentile_prefers_sketch_and_falls_back():
    """window_percentile uses sketch deltas when present (accurate tails)
    and keeps the bucket-interpolation fallback for sketchless samples
    (e.g. series that crossed the dashboard's JSON boundary)."""
    from ray_tpu.util import metrics as m

    boundaries = [1, 10, 100, 1000]

    def series(count_hi, sketch):
        # one point: `count_hi` observations in the 100..1000 bucket
        s = {"name": "wp_sketch_test", "kind": "histogram",
             "boundaries": boundaries,
             "points": {(): [0, 0, 0, count_hi, 0, 0.0, count_hi]}}
        if sketch is not None:
            s["sketches"] = {(): sketch}
        return s

    def sk_of(values):
        sk = {"z": 0, "c": {}}
        for v in values:
            idx = m._sketch_index(v)
            sk["c"][idx] = sk["c"].get(idx, 0) + 1
        return sk

    first = {"ts": 100.0, "series": [series(10, sk_of([500.0] * 10))]}
    last = {"ts": 110.0, "series": [
        series(30, sk_of([500.0] * 10 + [880.0] * 20))
    ]}
    p = m.window_percentile([first, last], "wp_sketch_test", 0.5)
    # the WINDOW saw only the 880ms observations: the sketch knows that
    # within 1%, bucket interpolation could only say "100..1000"
    assert p is not None and abs(p - 880.0) / 880.0 < 0.02, p

    # sketchless fallback: same samples without sketches interpolate
    first_nb = {"ts": 100.0, "series": [series(10, None)]}
    last_nb = {"ts": 110.0, "series": [series(30, None)]}
    p2 = m.window_percentile([first_nb, last_nb], "wp_sketch_test", 0.5)
    assert p2 is not None and 100.0 <= p2 <= 1000.0
