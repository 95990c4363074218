"""Serve-loop benchmark: multi-scene continuous batching under churn.

Drives ``repro.serve.StreamServer`` with synthetic traffic — Poisson
arrivals of heterogeneous dolly/orbit trajectories round-robined over K
registered scenes — and reports the serving metrics the subsystem
exists for: per-frame latency (p50/p99, enqueue -> render-complete,
wall clock), rendered frames/sec, slot utilization of the elastic
B-slot batch, the bucketed executable cache's compile/hit log (the
whole run must stay within one compilation per
``(scene_bucket, B, R)`` key — that is the recompilation bound the
bucketing buys, now across scenes AND batch sizes), and the simulated
ASIC latency of the served frames through the paper's accelerator model
(``core/streaming.py``, the paper's LDU scheduled on the host) next to
the wall-clock numbers.

Writes ``experiments/artifacts/serve_bench.json`` (full report +
per-round trace) and returns summary rows for ``benchmarks/run.py``.
``--smoke`` is the CI tier-1 configuration: tiny scene, 4 streams over
a (2, 4)-bucketed batch; CI runs it with ``--scenes 3`` so three
same-bucket scenes exercise the shared-executable path end to end.

``--replay {skewed,burst}`` switches to the traffic-replay fairness
comparison (DESIGN.md §11): the same deterministic arrival trace —
10:1 scene-bucket skew, or quiet rounds punctuated by bursts — served
twice, once under the legacy drain-before-switch planner
(``AdmissionConfig(mode="drain")``, the starvation baseline) and once
under mixed rounds with aging. The artifact
(``serve_bench_replay.json``) carries both full reports plus a
before/after comparison block; the skewed run asserts the headline
result: under drain the minority bucket's max wait grows with the
majority backlog, under mixed+aging it stays within
``max_wait_rounds``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import List, Optional

import jax

from benchmarks.common import camera, scenes
from repro.compile_cache import enable_compile_cache
from repro.core.pipeline import RenderConfig
from repro.obs.trace import validate_chrome_trace
from repro.scenes.synthetic import random_blob_scene, structured_scene
from repro.serve import (AdmissionConfig, PoissonTraffic, ReplayTraffic,
                         SceneRegistry, ServeConfig, StreamServer,
                         TrafficConfig, burst_trace, skewed_trace)

_ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "artifacts")
ARTIFACT = os.path.join(_ARTIFACTS, "serve_bench.json")
# The CI smoke run writes its own file so a local `--smoke` never
# clobbers the committed full-run artifact.
SMOKE_ARTIFACT = os.path.join(_ARTIFACTS, "serve_bench_smoke.json")
REPLAY_ARTIFACT = os.path.join(_ARTIFACTS, "serve_bench_replay.json")
REPLAY_SMOKE_ARTIFACT = os.path.join(_ARTIFACTS,
                                     "serve_bench_replay_smoke.json")

FULL = dict(
    image=64, n_gaussians=3000, window=4, warmup=True, scenes=3,
    scfg=ServeConfig(chunk=3, r_buckets=(4, 8, 16), b_buckets=(4, 8),
                     quantile=0.9, adapt_every=2, sim_latency=True),
    traffic=TrafficConfig(n_streams=12, rate=6.0, min_frames=10,
                          max_frames=16, seed=0),
)
SMOKE = dict(
    image=48, n_gaussians=3000, window=4, scenes=1,
    scfg=ServeConfig(chunk=2, r_buckets=(4, 8), b_buckets=(2, 4),
                     quantile=0.9, adapt_every=2, sim_latency=True),
    scene="indoor",
    traffic=TrafficConfig(n_streams=4, rate=8.0, min_frames=6,
                          max_frames=8, seed=0),
)

# The replay comparison serves TWO scenes in DIFFERENT buckets — a
# structured majority scene and a degree-0 blob minority scene — so the
# drain-mode baseline genuinely starves the minority (same-bucket
# scenes would share rounds regardless of planner). ``aging`` is the
# mixed-mode AdmissionConfig under test; ``max_groups_per_round=1`` is
# the worst case for fairness (one bucket per round, so only aging can
# let the minority in).
REPLAY_FULL = dict(
    image=64, n_major=1500, n_minor=400, window=4,
    scfg=ServeConfig(chunk=3, r_buckets=(4, 8, 16), b_buckets=(2, 4, 8),
                     quantile=0.9, adapt_every=2,
                     scene_buckets=(512, 1024, 2048)),
    traffic=TrafficConfig(n_streams=22, min_frames=8, max_frames=12,
                          seed=0),
    skew=10, burst_every=3, burst_size=6,
    aging=AdmissionConfig(max_wait_rounds=2, max_groups_per_round=1),
)
REPLAY_SMOKE = dict(
    image=48, n_major=260, n_minor=90, window=4,
    scfg=ServeConfig(chunk=2, r_buckets=(4, 8), b_buckets=(2, 4),
                     quantile=0.9, adapt_every=2,
                     scene_buckets=(256, 512)),
    traffic=TrafficConfig(n_streams=11, min_frames=6, max_frames=8,
                          seed=0),
    skew=10, burst_every=3, burst_size=4,
    aging=AdmissionConfig(max_wait_rounds=2, max_groups_per_round=1),
)


def _make_scenes(k: int, n: int, first: str) -> List:
    """K distinct same-bucket scenes: the named indoor/outdoor benchmark
    scenes first, then procedural clutter variants. All structured
    (SH degree 1) at one N — same (padded N, sh K) bucket — so they
    MUST share executables (the assertion below). The degree-0 blob
    scene is deliberately excluded: a different sh shape is a different
    bucket, which is bucket-isolation behavior the unit tests cover."""
    named = scenes(n)
    named.pop("synthetic")
    ordered = [named.pop(first)] + list(named.values())
    out = ordered[:k]
    key = jax.random.PRNGKey(1234)
    i = 0
    while len(out) < k:
        out.append(structured_scene(jax.random.fold_in(key, i), n,
                                    clutter=0.3 + 0.1 * (i % 4)))
        i += 1
    return out


def trace_path(name: str) -> str:
    """A bare file name lands next to the JSON artifacts; any path with
    a directory component is used as given."""
    if os.path.dirname(name):
        return name
    return os.path.join(_ARTIFACTS, name)


def _serve(setup: dict, n_scenes: int, scfg: ServeConfig):
    cam = camera(setup["image"], setup["image"])
    registry = SceneRegistry(scfg.scene_buckets)
    for scene in _make_scenes(n_scenes, setup["n_gaussians"],
                              setup.get("scene", "outdoor")):
        registry.register(scene)
    cfg = RenderConfig(window=setup["window"], capacity=256)
    server = StreamServer(registry, cam, cfg, scfg)
    if setup.get("warmup"):
        # Compile all (scene_bucket, B, R) executables up front so
        # reported latencies measure serving, not jit cold-start (the
        # smoke config skips this and eats the compiles in-round to
        # stay short).
        server.warmup()
    traffic = dataclasses.replace(setup["traffic"], scenes=n_scenes)
    return server.run(PoissonTraffic(traffic), max_rounds=200), server


def _write_trace(server: StreamServer, path: str) -> int:
    """Export + validate the run's Chrome trace; assert the observability
    contract CI relies on (DESIGN.md §13): well-formed JSON with round
    spans, and a compile-vs-dispatch split for at least one cache key."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    n_events = server.tracer.write(path)
    summary = validate_chrome_trace(server.tracer.to_chrome())
    for name in ("round", "plan", "dispatch", "barrier", "commit",
                 "compile"):
        assert name in summary["names"], \
            f"trace is missing {name!r} spans: {summary['names']}"
    compiled = [k for k, t in server.cache.stats()["per_key_timing"].items()
                if t["compile_ms"] is not None]
    assert compiled, "no cache key recorded a compile time"
    compile_spans = [ev for ev in server.tracer.events()
                     if ev["name"] == "compile"]
    assert compile_spans and all(
        "key" in ev.get("args", {}) for ev in compile_spans), \
        "compile spans must carry their cache key"
    print(f"# trace: {os.path.normpath(path)} ({n_events} events, "
          f"{summary['tracks']} tracks, {len(compiled)} compiles)")
    return n_events


def run(smoke: bool = False, n_scenes: Optional[int] = None,
        trace: Optional[str] = None) -> List[dict]:
    setup = SMOKE if smoke else FULL
    n_scenes = setup["scenes"] if n_scenes is None else int(n_scenes)
    scfg = setup["scfg"]
    if trace is not None:
        scfg = dataclasses.replace(scfg, trace=True)
    report, server = _serve(setup, n_scenes, scfg)
    if trace is not None:
        _write_trace(server, trace_path(trace))
    out = SMOKE_ARTIFACT if smoke else ARTIFACT
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)

    n_exec = report["cache"]["distinct_executables"]
    max_b = max(scfg.slot_buckets)
    want = min(max_b, setup["traffic"].n_streams)
    assert report["max_concurrent"] >= want, \
        f"expected {want} concurrent streams at peak, saw " \
        f"{report['max_concurrent']}"
    # The recompilation bound: one executable per (scene_bucket, B, R)
    # key, no matter how many scenes / rounds / churn events.
    buckets_in_use = len(report["scenes"]["buckets_in_use"])
    max_keys = len(scfg.slot_buckets) * len(scfg.r_buckets) * buckets_in_use
    assert n_exec <= max_keys, report["cache"]
    # Every stream drains and detaches: no carry was dropped by scene
    # switching or B resizes.
    assert report["streams_finished"] == setup["traffic"].n_streams
    if n_scenes > 1:
        # Same-bucket scene reuse: more distinct scenes served than
        # compiled executables can only mean scenes shared executables
        # (the hit/miss log records every reuse).
        served_scenes = set()
        for r in report["rounds_trace"]:
            served_scenes.update(r.get("scene_ids", []))
        assert len(served_scenes) >= min(n_scenes,
                                         setup["traffic"].n_streams), \
            f"only scenes {served_scenes} were served"
        assert report["cache"]["hits"] > 0, report["cache"]
    if scfg.b_buckets is not None and len(scfg.b_buckets) > 1:
        # Elastic B: the run must contain at least one resize event
        # (served without dropping carries, per the assert above).
        assert len(set(report["slots_history"])) >= 2, \
            report["slots_history"]
    assert report["sim"] is not None and report["sim"]["frames"] > 0

    return [{
        "bench": "serve", "mode": "smoke" if smoke else "full",
        "scenes": n_scenes,
        "streams_served": report["streams_served"],
        "max_concurrent": report["max_concurrent"],
        "frames": report["frames"],
        "latency_p50_ms": report["latency_p50_ms"],
        "latency_p99_ms": report["latency_p99_ms"],
        "frames_per_second": report["frames_per_second"],
        "slot_utilization": report["slot_utilization"],
        "distinct_executables": n_exec,
        "cache_hits": report["cache"]["hits"],
        "warmup_seconds": report["warmup_seconds"],
        "capacity_history": "->".join(map(str,
                                          report["capacity_history"])),
        "slots_history": "->".join(map(str, report["slots_history"])),
        "sim_cycles_per_frame": report["sim"]["cycles_per_frame"],
        "sim_latency_p50_cycles": report["sim"]["latency_p50_cycles"],
        "sim_latency_p99_cycles": report["sim"]["latency_p99_cycles"],
        "jain_service": report["fairness"]["jain_service"],
        "max_wait_rounds": report["fairness"]["max_wait_rounds"],
        "deferred": report["fairness"]["deferred"],
        "num_devices": report["num_devices"],
    }]


def _replay_serve(setup: dict, pattern: str,
                  admission: AdmissionConfig) -> dict:
    """One leg of the before/after comparison: the deterministic trace
    (scene index 0 = majority bucket, 1 = minority bucket) served under
    ``admission``. Fresh server + traffic per leg, identical seeds —
    the ONLY difference between legs is the round planner."""
    cam = camera(setup["image"], setup["image"])
    registry = SceneRegistry(setup["scfg"].scene_buckets)
    registry.register(structured_scene(jax.random.PRNGKey(21),
                                       setup["n_major"], clutter=0.4))
    registry.register(random_blob_scene(jax.random.PRNGKey(22),
                                        setup["n_minor"]))
    cfg = RenderConfig(window=setup["window"], capacity=256)
    scfg = dataclasses.replace(setup["scfg"], admission=admission)
    server = StreamServer(registry, cam, cfg, scfg)
    n = setup["traffic"].n_streams
    if pattern == "skewed":
        trace = skewed_trace(n, skew=setup["skew"])
    else:
        trace = burst_trace(n, burst_every=setup["burst_every"],
                            burst_size=setup["burst_size"], scenes=2)
    return server.run(ReplayTraffic(trace, setup["traffic"]),
                      max_rounds=400)


def run_replay(smoke: bool = False, pattern: str = "skewed") -> List[dict]:
    """The starvation before/after: drain-mode baseline vs mixed rounds
    with aging, same trace. Writes ``serve_bench_replay.json`` and
    asserts the fix's contract (see module docstring)."""
    if pattern not in ("skewed", "burst"):
        raise ValueError(f"pattern must be 'skewed' or 'burst', "
                         f"got {pattern!r}")
    setup = REPLAY_SMOKE if smoke else REPLAY_FULL
    aging = setup["aging"]
    before = _replay_serve(setup, pattern, AdmissionConfig(mode="drain"))
    after = _replay_serve(setup, pattern, aging)

    minority = str(tuple(after["scenes"]["per_scene"]["1"]["bucket"]))
    rows = []
    for leg, report in (("drain", before), ("mixed", after)):
        mb = report["per_bucket"].get(minority, {})
        rows.append({
            "bench": "serve_replay", "pattern": pattern, "planner": leg,
            "mode": "smoke" if smoke else "full",
            "streams_finished": report["streams_finished"],
            "frames": report["frames"],
            "rounds": report["rounds"],
            "jain_service": report["fairness"]["jain_service"],
            "max_wait_rounds": report["fairness"]["max_wait_rounds"],
            "deferred": report["fairness"]["deferred"],
            "minority_frames": mb.get("frames", 0),
            "minority_max_wait": mb.get("max_wait_rounds", 0),
            "minority_share": mb.get("share"),
            "minority_p99_ms": mb.get("latency_p99_ms"),
            "latency_p99_ms": report["latency_p99_ms"],
        })
    comparison = {
        "pattern": pattern, "minority_bucket": minority,
        "max_wait_bound": aging.max_wait_rounds,
        "minority_max_wait_before": rows[0]["minority_max_wait"],
        "minority_max_wait_after": rows[1]["minority_max_wait"],
        "jain_before": rows[0]["jain_service"],
        "jain_after": rows[1]["jain_service"],
        "minority_p99_ms_before": rows[0]["minority_p99_ms"],
        "minority_p99_ms_after": rows[1]["minority_p99_ms"],
    }
    out = REPLAY_SMOKE_ARTIFACT if smoke else REPLAY_ARTIFACT
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"comparison": comparison, "before": before,
                   "after": after}, f, indent=1)

    n = setup["traffic"].n_streams
    scfg = setup["scfg"]
    for report in (before, after):
        # both planners eventually serve everyone (drain starves, it
        # does not drop) and stay within the compile bound
        assert report["streams_finished"] == n, report["streams_finished"]
        buckets_in_use = len(report["scenes"]["buckets_in_use"])
        max_keys = len(scfg.slot_buckets) * len(scfg.r_buckets) \
            * buckets_in_use
        assert report["cache"]["distinct_executables"] <= max_keys
    # the headline: minority service is nonzero and its wait is bounded
    # by max_wait_rounds under mixed+aging
    assert rows[1]["minority_frames"] > 0, rows[1]
    assert rows[1]["minority_max_wait"] <= aging.max_wait_rounds, rows[1]
    if pattern == "skewed":
        # ... while the drain baseline demonstrably starved it
        assert rows[0]["minority_max_wait"] > aging.max_wait_rounds, \
            rows[0]
        assert rows[1]["jain_service"] >= rows[0]["jain_service"], \
            (rows[0], rows[1])
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI configuration: tiny scene, 4 streams, "
                         "2 buckets per axis")
    ap.add_argument("--scenes", type=int, default=None,
                    help="serve this many scenes round-robin (default: "
                         "the mode's preset; full preset is 3)")
    ap.add_argument("--replay", choices=("skewed", "burst"), default=None,
                    help="run the starvation before/after comparison on "
                         "this arrival pattern instead of Poisson traffic")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record serve-round spans and write a Chrome-"
                         "trace JSON (loads in ui.perfetto.dev); a bare "
                         "file name lands in experiments/artifacts/")
    args = ap.parse_args()
    if args.replay:
        if args.trace:
            ap.error("--trace applies to the Poisson run, not --replay")
        rows = run_replay(smoke=args.smoke, pattern=args.replay)
        out = REPLAY_SMOKE_ARTIFACT if args.smoke else REPLAY_ARTIFACT
    else:
        rows = run(smoke=args.smoke, n_scenes=args.scenes,
                   trace=args.trace)
        out = SMOKE_ARTIFACT if args.smoke else ARTIFACT
    for row in rows:
        print(",".join(f"{k}={v}" for k, v in row.items()))
    print(f"# artifact: {os.path.normpath(out)}")


if __name__ == "__main__":
    main()
