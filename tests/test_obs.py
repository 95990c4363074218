"""Observability contract (DESIGN.md §13): span tracing, the metrics
registry, and — the part that makes tracing safe to ship on — the
observer-effect-zero guarantee: a traced server renders bit-identical
frames through identical executable-cache keys."""
import json
from collections import deque

import jax
import numpy as np
import pytest

from repro.core import engine
from repro.core.pipeline import RenderConfig
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       NULL_TRACER, Tracer, validate_chrome_trace)
from repro.scenes.synthetic import structured_scene
from repro.scenes.trajectory import dolly_trajectory
from repro.serve import SceneRegistry, ServeConfig, StreamServer


def _poses(n, dx=0.0):
    return np.asarray(dolly_trajectory(n, start=(dx, -0.3, -2.0),
                                       target=(0.0, 0.0, 6.0)))


# --- tracer unit behavior -------------------------------------------------

def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    s1 = tr.span("a")
    s2 = tr.span("b", track="other", args={"x": 1})
    assert s1 is s2                       # shared null span: no allocation
    with s1:
        pass
    tr.instant("mark")
    assert tr.events() == [] and tr.dropped == 0
    assert NULL_TRACER.span("c") is s1


def test_tracer_records_spans_and_instants():
    tr = Tracer(enabled=True)
    with tr.span("outer", track="round", args={"round": 1}):
        with tr.span("inner", track="round"):
            pass
    tr.instant("resize", track="bucket (512, 4)", args={"to": 4})
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer", "resize"]
    inner, outer, inst = evs
    # children exit (and append) before parents; nesting is by ts/dur
    assert outer["ph"] == "X" and inner["ph"] == "X" and inst["ph"] == "i"
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    assert outer["args"] == {"round": 1}
    # distinct tracks get distinct tids
    assert inner["tid"] == outer["tid"] != inst["tid"]
    chrome = tr.to_chrome()
    assert validate_chrome_trace(chrome)["spans"] == 2
    names = {ev["args"]["name"] for ev in chrome["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert names == {"round", "bucket (512, 4)"}


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs what opens."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))
        return _Ann()


def test_tracer_spans_open_profiler_annotations(monkeypatch):
    """An enabled span runs under one ``serve/<name>`` profiler
    annotation; a disabled tracer returns the shared no-op and makes no
    profiler call at all."""
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    off = Tracer(enabled=False)
    assert off.span("round") is NULL_TRACER.span("x")
    with off.span("round"):
        with off.span("fetch", track="bucket b"):
            pass
    assert ann.log == []
    on = Tracer(enabled=True)
    with on.span("round"):
        with on.span("fetch", track="bucket b"):
            pass
    assert ann.log == [("enter", "serve/round"), ("enter", "serve/fetch"),
                       ("exit", "serve/fetch"), ("exit", "serve/round")]
    assert [e["name"] for e in on.events()] == ["fetch", "round"]


def test_tracer_buffer_bounded_keeps_first():
    tr = Tracer(enabled=True, keep=8)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    evs = tr.events()
    assert len(evs) == 8 and tr.dropped == 12
    assert [e["name"] for e in evs] == [f"s{i}" for i in range(8)]
    chrome = tr.to_chrome()
    assert chrome["otherData"] == {"events": 8, "dropped": 12}
    validate_chrome_trace(chrome)         # truncation stays well-formed


def test_tracer_write_roundtrip(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("round", track="round"):
        pass
    path = tmp_path / "t.trace.json"
    assert tr.write(str(path)) == 1
    trace = json.loads(path.read_text())
    summary = validate_chrome_trace(trace)
    assert summary["spans"] == 1 and summary["names"] == ["round"]
    names = {ev["args"]["name"] for ev in trace["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert names == {"round"}


def test_validate_rejects_malformed():
    ok = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0, "dur": 1.0,
                           "pid": 1, "tid": 0}]}
    assert validate_chrome_trace(ok)["spans"] == 1
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": "nope"})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0.0, "pid": 1, "tid": 0}]})
    with pytest.raises(ValueError):       # negative dur
        validate_chrome_trace({"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0.0, "dur": -1.0, "pid": 1,
             "tid": 0}]})
    with pytest.raises(ValueError):       # overlap without nesting
        validate_chrome_trace({"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0.0, "dur": 2.0, "pid": 1,
             "tid": 0},
            {"name": "b", "ph": "X", "ts": 1.0, "dur": 2.0, "pid": 1,
             "tid": 0}]})


# --- metrics registry -----------------------------------------------------

def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("frames_total", "help text")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    assert reg.counter("frames_total") is c   # get-or-create identity
    g = reg.gauge("peak")
    g.set_max(5)
    g.set_max(3)
    assert g.value == 5
    g.set(2)
    assert g.value == 2
    with pytest.raises(TypeError):            # kind mismatch
        reg.gauge("frames_total")


def test_labeled_metrics_are_distinct():
    reg = MetricsRegistry()
    a = reg.counter("served", bucket="(256, 4)")
    b = reg.counter("served", bucket="(512, 4)")
    a.inc()
    assert b.value == 0
    assert a.key == 'served{bucket="(256, 4)"}'
    snap = reg.snapshot()
    assert snap["counters"][a.key] == 1
    assert snap["counters"][b.key] == 0


def test_histogram_empty_is_none_never_nan():
    h = MetricsRegistry().histogram("lat")
    assert h.percentile(50) is None
    st = h.stats()
    assert st == {"count": 0, "sum": 0.0, "min": None, "max": None,
                  "kept": 0, "p50": None, "p90": None, "p99": None}
    json.dumps(st)                            # and JSON-safe


def test_histogram_reservoir_bounded_lifetime_exact():
    reg = MetricsRegistry()
    h = reg.histogram("work", keep=4)
    h.observe_many(range(10))                 # 0..9
    h.observe_many([])                        # no-op, never raises
    assert h.count == 10 and h.total == 45.0
    assert (h.vmin, h.vmax) == (0.0, 9.0)     # lifetime, not reservoir
    assert h.values() == [6.0, 7.0, 8.0, 9.0]  # newest-keep window
    st = h.stats()
    assert st["kept"] == 4 and st["p50"] == 7.5


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("serve_frames_total", "frames").inc(7)
    reg.gauge("peak", bucket="(256, 4)").set(3)
    reg.histogram("lat").observe(0.5)
    reg.histogram("empty_lat")
    text = reg.to_prometheus()
    assert "# TYPE serve_frames_total counter" in text
    assert "serve_frames_total 7" in text
    assert 'peak{bucket="(256, 4)"} 3' in text
    assert 'lat{quantile="0.5"} 0.5' in text
    assert "lat_count 1" in text
    # empty histogram: no quantile rows, but count/sum still exported
    assert 'empty_lat{quantile' not in text
    assert "empty_lat_count 0" in text


# --- server integration ---------------------------------------------------

def _server(small_cam, trace: bool, **kw):
    reg = SceneRegistry((256, 512))
    entry = reg.register(structured_scene(jax.random.PRNGKey(9), 260,
                                          clutter=0.4))
    cfg = RenderConfig(window=3, capacity=128, rerender_capacity=8)
    scfg = ServeConfig(slots=2, chunk=2, r_buckets=(8,),
                       scene_buckets=(256, 512), trace=trace, **kw)
    return StreamServer(reg, small_cam, cfg, scfg), entry


def test_tracing_observer_effect_zero(small_cam):
    """Tracing ON and OFF: bit-identical frames, identical cache keys.

    The tracer only times host phases and the annotate() scopes only
    rename ops — neither may perturb numerics or the executable family.
    """
    frames, keys = {}, {}
    for trace in (False, True):
        srv, entry = _server(small_cam, trace, collect_frames=True)
        sessions = [srv.attach(_poses(5, dx=0.05 * i),
                               scene_id=entry.scene_id)
                    for i in range(2)]
        report = srv.run(max_rounds=20)
        assert report["streams_finished"] == 2
        frames[trace] = [np.concatenate(s.frames) for s in sessions]
        keys[trace] = sorted(report["cache"]["keys"])
    assert keys[False] == keys[True]
    for a, b in zip(frames[False], frames[True]):
        np.testing.assert_array_equal(a, b)


def test_traced_server_exports_valid_trace(small_cam, tmp_path):
    srv, entry = _server(small_cam, True, sim_latency=True,
                         collect_frames=True)
    srv.attach(_poses(4), scene_id=entry.scene_id)
    srv.run(max_rounds=20)
    path = tmp_path / "serve.trace.json"
    srv.tracer.write(str(path))
    summary = validate_chrome_trace(json.loads(path.read_text()))
    for name in ("round", "plan", "dispatch", "barrier", "commit",
                 "compile", "fetch", "observe"):
        assert name in summary["names"]
    compiles = [ev for ev in srv.tracer.events()
                if ev["name"] == "compile"]
    assert compiles and all("key" in ev["args"] for ev in compiles)
    # the cache's split agrees: the compiled key billed compile once and
    # dispatched cheaper thereafter
    timing = srv.cache.stats()["per_key_timing"]
    compiled = [t for t in timing.values() if t["compile_ms"] is not None]
    assert compiled and compiled[0]["dispatch_calls"] >= 1


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


@pytest.mark.parametrize("child,parent", [
    ("stack", "build"), ("upload", "build"), ("carry", "commit"),
    ("fetch", "commit"), ("observe", "commit")])
def test_traced_server_child_spans_nest_in_their_parent(small_cam, child,
                                                        parent):
    """Every host phase of a round under build and commit has its own
    span, inside its parent's span on the parent's track."""
    srv, entry = _server(small_cam, True, collect_frames=True)
    for i in range(2):
        srv.attach(_poses(4, dx=0.05 * i), scene_id=entry.scene_id)
    srv.run(max_rounds=20)
    evs = [e for e in srv.tracer.events() if e["ph"] == "X"]
    kids = [e for e in evs if e["name"] == child]
    parents = [e for e in evs if e["name"] == parent]
    assert kids and parents
    for k in kids:
        assert any(p["tid"] == k["tid"] and _inside(k, p) for p in parents)
    validate_chrome_trace(srv.tracer.to_chrome())


def test_fetch_bytes_count_every_frame_copied(small_cam):
    srv, entry = _server(small_cam, False, collect_frames=True)
    sessions = [srv.attach(_poses(5, dx=0.05 * i), scene_id=entry.scene_id)
                for i in range(2)]
    report = srv.run(max_rounds=20)
    counters = report["metrics"]["counters"]
    assert report["frames"] == 10 == counters["serve_frames_total"]
    assert counters["serve_fetch_bytes_total"] == \
        10 * small_cam.height * small_cam.width * 3 * 4
    assert counters["serve_fetch_bytes_total"] == sum(
        f.nbytes for s in sessions for f in s.frames)


class _FakeClock:
    """Advances one second a read; remembers the last value read."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        self.now += 1.0
        return self.now


def test_wait_seconds_sum_dispatch_less_enqueue(small_cam):
    """Each real frame adds its group's dispatch start (the clock read
    before the executable call) less its pose's enqueue stamp."""
    srv, entry = _server(small_cam, False)
    clock = srv.clock = _FakeClock()
    stamps = {0: 3.0, 1: 7.5}
    for i, t in stamps.items():
        srv.attach(_poses(3 + i, dx=0.05 * i), now=t,
                   scene_id=entry.scene_id)
    executable = srv._executable
    expected = []

    def watched(bucket, b):
        fn = executable(bucket, b)

        def call(*args):
            counts = np.asarray(args[2])
            expected.extend([clock.now] * int(counts.sum()))
            return fn(*args)
        return call
    srv._executable = watched
    report = srv.run(max_rounds=20)
    assert report["frames"] == 7 == len(expected)
    # Poses were stamped at attach: 3 of stream 0, 4 of stream 1.
    want = sum(expected) - 3 * stamps[0] - 4 * stamps[1]
    assert report["metrics"]["counters"]["serve_wait_seconds_total"] == \
        pytest.approx(want)


@pytest.mark.parametrize("collect", [True, False])
def test_latency_stamped_when_pixels_reach_host(small_cam, collect):
    """With frames copied to host, a frame's latency ends after its
    group's last ``fetch`` and within ``commit``; without, at the
    barrier's end."""
    srv, entry = _server(small_cam, True, collect_frames=collect)
    t_attach = srv.clock()
    sess = srv.attach(_poses(4), now=t_attach, scene_id=entry.scene_id)
    srv.run(max_rounds=20)
    origin = srv.tracer._t0_ns * 1e-9
    evs = srv.tracer.events()

    def ends(name):
        return sorted(origin + (e["ts"] + e["dur"]) * 1e-6
                      for e in evs if e["name"] == name)

    def starts(name):
        return sorted(origin + e["ts"] * 1e-6
                      for e in evs if e["name"] == name)
    lats = list(sess.latencies)
    hist = srv.metrics.histogram("serve_latency_seconds").values()
    assert lats == pytest.approx(hist) and len(lats) == 4
    delivered = [t_attach + lat for lat in lats[::2]]   # chunk 2 a round
    tol = 2e-6
    for k, t in enumerate(delivered):
        if collect:
            assert t >= ends("fetch")[k] - tol
        else:
            assert t >= ends("barrier")[k] - tol
            assert t <= starts("commit")[k] + tol
        assert t <= ends("commit")[k] + tol


def test_trace_buffer_bounded_under_serving(small_cam):
    srv, entry = _server(small_cam, True, trace_keep=8)
    srv.attach(_poses(6), scene_id=entry.scene_id)
    srv.run(max_rounds=20)
    assert len(srv.tracer.events()) == 8 and srv.tracer.dropped > 0
    validate_chrome_trace(srv.tracer.to_chrome())


def test_report_before_first_round_is_clean(small_cam):
    """Empty reservoirs must report None — never NaN, never raise —
    including per-bucket entries for buckets that never rendered."""
    srv, _ = _server(small_cam, True, sim_latency=True)
    report = srv.report()
    json.dumps(report)                        # fully serializable
    assert report["latency_p50_ms"] is None
    assert report["latency_p99_ms"] is None
    assert report["frames_per_second"] is None
    assert report["sim"] is None
    assert report["rounds_trace_dropped"] == 0
    pb = report["per_bucket"]["(512, 4)"]     # batcher exists, 0 frames
    assert pb["frames"] == 0
    assert pb["latency_p50_ms"] is None and pb["latency_p99_ms"] is None
    hists = report["metrics"]["histograms"]
    assert hists["serve_latency_seconds"]["p50"] is None


def test_rounds_trace_bound_is_counted(small_cam):
    srv, entry = _server(small_cam, False)
    srv.trace = deque(maxlen=1)               # worst-case bound
    srv.attach(_poses(6), scene_id=entry.scene_id)
    report = srv.run(max_rounds=20)
    assert len(report["rounds_trace"]) == 1
    assert report["rounds_trace_dropped"] >= 1
    assert report["rounds_trace_dropped"] == report["rounds"] - 1
    # and the counter rode the shared registry
    assert report["metrics"]["counters"][
        "serve_rounds_trace_dropped_total"] == report["rounds_trace_dropped"]


def test_frame_parity_across_chunks_with_tracing(small_cam):
    """Traced frames equal the solo engine render (the collect_frames
    parity pattern), so spans cost nothing in numerics even across
    chunk seams."""
    srv, entry = _server(small_cam, True, collect_frames=True)
    sess = srv.attach(_poses(5), scene_id=entry.scene_id)
    report = srv.run(max_rounds=20)
    got = np.concatenate(sess.frames)
    solo = engine.render_trajectory(
        entry.scene, small_cam, jax.numpy.asarray(_poses(5)),
        RenderConfig(window=3, capacity=128, rerender_capacity=8),
        phase=sess.phase)
    np.testing.assert_allclose(got, np.asarray(solo.frames), atol=1e-5)
    # The overflow counters sum the same records across chunk seams:
    # pairs past K on every frame, tiles past R on sparse frames.
    counters = report["metrics"]["counters"]
    recs = solo.records
    sparse = ~np.asarray(recs.is_full)
    assert counters["serve_overflow_pairs_total"] == int(
        np.asarray(recs.overflow_pairs).sum())
    assert counters["serve_overflow_tiles_total"] == int(
        np.asarray(recs.overflow_tiles)[sparse].sum())
    assert counters["serve_overflow_tiles_total"] > 0   # R = 8 of 16 tiles


def test_pair_budget_overflow_counter_sums_records(small_cam):
    """With a pair budget too small for the scene (T * K = 16 * 16
    slots), frames drop stage-1 pairs; the serve counter sums the
    records' counts over the frames served."""
    reg = SceneRegistry((256, 512))
    entry = reg.register(structured_scene(jax.random.PRNGKey(9), 260,
                                          clutter=0.4))
    cfg = RenderConfig(window=3, capacity=16, chunk=16, rerender_capacity=8)
    scfg = ServeConfig(slots=1, chunk=2, r_buckets=(8,),
                       scene_buckets=(256, 512))
    srv = StreamServer(reg, small_cam, cfg, scfg)
    sess = srv.attach(_poses(5), scene_id=entry.scene_id)
    report = srv.run(max_rounds=20)
    solo = engine.render_trajectory(
        entry.scene, small_cam, jax.numpy.asarray(_poses(5)), cfg,
        phase=sess.phase)
    total = int(np.asarray(solo.records.pair_budget_overflow).sum())
    assert total > 0
    assert report["metrics"]["counters"][
        "serve_pair_budget_overflow_total"] == total


def test_stage1_pairs_counter_rides_the_overflow_transfer(small_cam,
                                                          monkeypatch):
    """``serve_stage1_pairs_total`` sums the records' stage-1 pair counts
    over the frames served, and comes back in the transfer that already
    carries both pair overflows: one ``device_get`` of three arrays per
    group observed, as before the counter was added, with two."""
    srv, entry = _server(small_cam, False)
    sess = srv.attach(_poses(5), scene_id=entry.scene_id)
    real_get, observe = jax.device_get, srv._observe
    inside, gets, groups = [], [], []

    def counted(tree):
        if inside:
            gets.append(len(jax.tree_util.tree_leaves(tree)))
        return real_get(tree)

    def observed(result):
        inside.append(True)
        try:
            observe(result)
        finally:
            inside.pop()
        groups.append(result)

    monkeypatch.setattr(jax, "device_get", counted)
    srv._observe = observed
    report = srv.run(max_rounds=20)
    monkeypatch.undo()
    solo = engine.render_trajectory(
        entry.scene, small_cam, jax.numpy.asarray(_poses(5)),
        RenderConfig(window=3, capacity=128, rerender_capacity=8),
        phase=sess.phase)
    total = int(np.asarray(solo.records.stage1_pairs).sum())
    assert total > 0
    assert report["metrics"]["counters"]["serve_stage1_pairs_total"] == total
    assert groups and gets == [3] * len(groups)
