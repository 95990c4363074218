"""One benchmark cell, run once: set-up, a closed-loop window, the check.

A cell is ``<config>.<mix>`` in ``BENCHMARK.json``: the deployment in
``bench/configs/<config>.json`` under the traffic in
``bench/traffic/<mix>.json``. Everything here is general; what belongs
to one configuration, mix or per-layer metric is in those files and in
``bench/metrics/<metric>.py``.

A run:

1. makes the scenes on the device from the seed (``scenes.py``);
2. builds the program's ``StreamServer`` for the cell, one stream slot
   per client of the mix, and compiles its one (B, R) executable with
   ``warmup()``;
3. serves warm-up rounds: at least ``WARMUP_ROUNDS`` (a key frame, then
   a sparse one where the cell's window has them), then whole rounds
   until one compiles nothing;
4. measures whole rounds until ``seconds`` have passed (with ``trace``,
   a fixed number of rounds under the JAX profiler instead). The loop is
   closed: each client has one frame in flight and sends its next pose
   when its last frame is in host memory, so a frame is timed from that
   delivery to its own;
5. compares a seeded sample of the served frames with the plain
   reference (``check.py``), after the program's state is freed. The
   sample is made of chains: a key frame and the sparse frames that
   followed it in its stream, since each sparse frame is made from the
   one before.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, "bench_out")
TRACE_DIR = os.path.join(OUT, "trace")

# Rounds under the profiler in a traced run: one key-frame cycle of the
# window-5 configurations, so every phase of a staggered mesh is in it.
TRACE_ROUNDS = 5
# Warm-up rounds before the window: at least WARMUP_ROUNDS, then until
# a round compiles nothing; a program that still compiles after
# MAX_WARMUP_ROUNDS is a fault.
WARMUP_ROUNDS = 2
MAX_WARMUP_ROUNDS = 30
# Chains compared with the reference, drawn from the seed, per stream:
# where the cell serves sparse frames, one chain that holds its key
# frame and one that holds a sparse frame (often the same); otherwise
# SAMPLE_KEY_FRAMES key frames shared out over the streams.
SAMPLE_KEY_FRAMES = 6
# One frame per stream per round: a closed loop with one frame in flight.
FRAMES_PER_ROUND = 1


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def window(self) -> int:
        return int(self.config["render"]["window"])


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its config and mix."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, config, mix, int(w["chips"]), e2e, per_layer)


class CompileLog:
    """Backend compiles seen while ``active`` (jax.monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)


class ExecutableLog:
    """What the compiler said of every executable the process compiles or
    loads from the compile cache while open: its memory plan (bytes of
    arguments, outputs and temporaries) and, with ``hlo``, its optimized
    HLO text, by module name (without ``hlo``, by order of loading). A TPU profiler trace names ops by HLO
    instruction only; the text maps them to the program's name stack
    (``trace_reduce.hlo_scopes``)."""

    def __init__(self, hlo: bool):
        from jax._src import compiler
        self.texts: Dict[str, List[str]] = {}
        self.memory: Dict[str, dict] = {}
        self._compiler = compiler
        self._inner = compiler.compile_or_get_cached

        def recorded(*args, **kwargs):
            exe = self._inner(*args, **kwargs)
            try:
                stats = exe.get_compiled_memory_stats()
                # Module names and text only where a trace needs them:
                # serialising every module would lengthen the set-up.
                modules = exe.hlo_modules() if hlo else []
            except (AttributeError, RuntimeError) as e:
                # The run goes on; the metrics that need a scope go silent.
                print(f"bench: no HLO from an executable: {e}",
                      file=sys.stderr)
                return exe
            name = modules[0].name if modules else \
                f"executable {len(self.memory)}"
            self.memory[name] = {
                k: int(getattr(stats, k + "_size_in_bytes", -1))
                for k in ("argument", "output", "temp", "generated_code")}
            for module in modules:
                self.texts.setdefault(module.name, []).append(
                    module.to_string())
            return exe
        compiler.compile_or_get_cached = recorded

    def largest(self) -> tuple:
        """(module, memory plan) of the executable with most temporaries."""
        if not self.memory:
            return None, {}
        name = max(self.memory, key=lambda n: self.memory[n]["temp"])
        return name, self.memory[name]

    def close(self) -> None:
        self._compiler.compile_or_get_cached = self._inner


@dataclasses.dataclass
class Stream:
    """One closed-loop client: its session, next frame, and due time."""

    index: int
    session: object
    scene: int
    next_frame: int = 0
    due: float = 0.0
    key_frame: int = 0      # k of the stream's last key frame


@dataclasses.dataclass
class Frame:
    stream: int
    scene: int
    k: int
    key: bool
    latency: float
    key_frame: int          # k of the key frame its chain starts from
    rgb: Optional[np.ndarray] = None


@dataclasses.dataclass
class Chain:
    """Frames of one stream that follow from one key frame: the key frame
    at ``key_frame`` and the sparse frames after it that the window
    delivered (the key frame itself may precede the window). ``poses``
    runs from the key frame to the last frame held, one per frame."""

    stream: int
    scene: int
    key_frame: int
    frames: List[Frame]
    poses: List[np.ndarray] = dataclasses.field(default_factory=list)


class Reservoir:
    """A seeded uniform sample of at most ``size`` items."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen = size, rng, 0
        self.items: list = []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.size:
                self.items[j] = item


class Run:
    """The program under test, set up for one cell and seed."""

    def __init__(self, cell: Cell, seed: int, *, trace: bool,
                 clock: Callable[[], float] = time.perf_counter):
        import jax
        from poses import Traffic
        from scenes import make_scenes
        from repro.core.camera import make_camera
        from repro.core.gaussians import GaussianScene
        from repro.core.pipeline import RenderConfig
        from repro.serve import SceneRegistry, ServeConfig, StreamServer

        cfg, mix = cell.config, cell.mix
        self.cell, self.seed, self.clock = cell, int(seed), clock
        self.traffic = Traffic(mix, seed)
        self.scene_arrays = make_scenes(
            dict(cfg["scene"], num_gaussians=cfg["num_gaussians"],
                 sh_degree=cfg["sh_degree"]),
            self.traffic.scenes, seed)
        registry = SceneRegistry()
        ids = [registry.register(GaussianScene(
            *(self.scene_arrays[k][s] for k in
              ("means", "log_scales", "quats", "opacity_logits", "sh"))
        )).scene_id for s in range(self.traffic.scenes)]
        cam = make_camera(self.traffic.pose(0, 0),
                          width=cfg["image_width"],
                          height=cfg["image_height"],
                          fov_deg=cfg["camera"]["fov_deg"])
        render = cfg["render"]
        rcfg = RenderConfig(capacity=cfg["tile_capacity"],
                            chunk=render["chunk"], window=render["window"],
                            impl=render["impl"])
        serve = cfg["serve"]
        scfg = ServeConfig(slots=self.traffic.streams,
                           chunk=FRAMES_PER_ROUND,
                           r_buckets=tuple(serve["r_buckets"]),
                           collect_frames=True, trace=trace)
        self.server = StreamServer(registry, cam, rcfg, scfg)
        self.devices = jax.devices()[:cell.chips]
        self.scene_ids = ids
        self.streams: List[Stream] = []

    def attach(self) -> None:
        """One open session per client, holding its first pose."""
        for i in range(self.traffic.streams):
            s = self.traffic.scene_of(i)
            sess = self.server.attach(self.traffic.pose(i, 0)[None],
                                      scene_id=self.scene_ids[s])
            sess.closed = False          # a live client: never drains
            self.streams.append(Stream(i, sess, s, due=self.clock()))

    def is_key(self, st: Stream, k: int) -> bool:
        return k == 0 or (k + st.session.phase) % self.cell.window == 0

    def round(self) -> List[Frame]:
        """One server round; every frame it delivers, in host memory."""
        for st in self.streams:
            if not st.session.pending:
                st.session.submit(
                    self.traffic.pose(st.index, st.next_frame)[None],
                    now=st.due)
        self.server.step()
        now = self.clock()
        out = []
        for st in self.streams:
            chunks, st.session.frames = st.session.frames, []
            for chunk in chunks:
                for rgb in chunk:
                    k = st.next_frame
                    key = self.is_key(st, k)
                    if key:
                        st.key_frame = k
                    out.append(Frame(st.index, st.scene, k, key,
                                     now - st.due, st.key_frame, rgb))
                    st.next_frame += 1
                    st.due = now
        return out

    def peak_bytes(self, reserved: int) -> int:
        """The fullest chip's peak: its buffers' peak in use, plus the
        ``reserved`` bytes of temporaries the largest executable reserves
        when it is loaded, which ``peak_bytes_in_use`` does not count on
        a TPU (a round with 12 GiB held fails to reserve them)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks)) + int(reserved)

    def chain_poses(self, c: Chain) -> List[np.ndarray]:
        return [self.traffic.pose(c.stream, k) for k in
                range(c.key_frame, c.frames[-1].k + 1)]


def load_reader(name: str):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader reads (traced runs only)."""

    cell: Cell
    trace: object                 # trace_reduce.Trace
    spans: List[dict]             # program Tracer events, traced rounds
    counters: Dict[str, float]    # program counter deltas over the rounds
    frames: List[Frame]           # frames delivered in the traced rounds
    records: List[dict]           # per-frame work records (work.py)
    rounds: int
    compiles: int
    peaks: dict                   # peaks.json entry of this device kind
    log: Callable[[str], None] = print


class Sampler:
    """Per stream, a seeded sample of the chains the window delivered:
    one among those that hold their key frame and, where the cell serves
    sparse frames, one among those that hold a sparse frame, so that
    every run compares both kinds. A window-1 cell's chains are single
    key frames: ``SAMPLE_KEY_FRAMES`` of them, shared out over the
    streams."""

    def __init__(self, streams: int, window: int, rng):
        n_key = 1 if window > 1 else max(1, SAMPLE_KEY_FRAMES // streams)
        self.with_key = [Reservoir(n_key, rng) for _ in range(streams)]
        self.with_sparse = [Reservoir(1, rng) if window > 1 else None
                            for _ in range(streams)]
        self.open: List[Optional[Chain]] = [None] * streams

    def offer(self, f: Frame) -> None:
        c = self.open[f.stream]
        if c is not None and c.key_frame != f.key_frame:
            self._close(f.stream)
            c = None
        if c is None:
            c = self.open[f.stream] = Chain(f.stream, f.scene, f.key_frame,
                                            [])
        c.frames.append(f)

    def _close(self, stream: int) -> None:
        c, self.open[stream] = self.open[stream], None
        if c is None:
            return
        if any(f.key for f in c.frames):
            self.with_key[stream].offer(c)
        if self.with_sparse[stream] is not None and \
                any(not f.key for f in c.frames):
            self.with_sparse[stream].offer(c)

    def items(self) -> List[Chain]:
        for s in range(len(self.open)):
            self._close(s)
        out: Dict[tuple, Chain] = {}
        for pools in zip(self.with_key, self.with_sparse):
            for p in pools:
                for c in (p.items if p is not None else []):
                    out[(c.stream, c.key_frame)] = c
        return [out[k] for k in sorted(out)]


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_metrics(latencies: List[float], span: float,
                   setup_s: float) -> Dict[str, float]:
    """End-to-end metrics of a window of whole rounds: every frame it
    delivered over its whole span, and the 95th percentile of all their
    latencies."""
    return {"frames_per_s": len(latencies) / span,
            "frame_latency_p95_ms": 1e3 * percentile(latencies, 95),
            "setup_s": setup_s}


@dataclasses.dataclass
class Measured:
    """A run's result before the check, and what the check needs."""

    result: dict
    frames: List[Frame]       # every frame of the window, without pixels
    sample: List[Chain]       # the sampled chains, with pixels and poses
    scene_arrays: dict


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, log=print) -> dict:
    """Run ``cell`` once; the result object ``run.py`` prints last."""
    import check
    m = measure(cell, seed, seconds, trace, t_start=t_start,
                require_tpu=require_tpu, log=log)
    verdict = check.check(cell, m.scene_arrays, m.sample, log=log)
    m.result["correct"] = verdict.correct and bool(m.frames)
    m.result["failed"] = verdict.failed
    m.result["checks"] = verdict.lines
    return m.result


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float, require_tpu: bool = True,
            log=print) -> Measured:
    """Set up, serve the window and read the metrics (no check yet)."""
    import jax
    import trace_reduce
    import work

    backend = jax.default_backend()
    devices = jax.devices()
    if require_tpu and backend != "tpu":
        raise SystemExit(f"no TPU: JAX backend is {backend!r}")
    if len(devices) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips, "
                         f"JAX has {len(devices)}")
    kind = devices[0].device_kind
    peaks = None
    if trace:
        with open(os.path.join(BENCH, "peaks.json")) as f:
            table = json.load(f)["devices"]
        if require_tpu and kind not in table:
            raise SystemExit(f"no peaks for device kind {kind!r} in "
                             f"bench/peaks.json")
        peaks = table.get(kind)

    compiles = CompileLog()
    exes = ExecutableLog(hlo=trace)
    try:
        t = time.perf_counter()
        log(f"imports and backend {t - t_start:.3f} s")
        run = Run(cell, seed, trace=trace)
        log(f"scenes and server {time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        run.server.warmup()
        log(f"warmup() {time.perf_counter() - t:.3f} s, compiles "
            f"{compiles.count} ({compiles.seconds:.3f} s)")
        run.attach()
        warm = 0
        while True:
            before = compiles.count
            run.round()
            warm += 1
            if warm >= WARMUP_ROUNDS and compiles.count == before:
                break
            if warm >= MAX_WARMUP_ROUNDS:
                raise RuntimeError(f"still compiling after {warm} rounds")
        t0 = max(st.due for st in run.streams)
        setup_s = t0 - t_start
        log(f"set-up {setup_s:.3f} s: {warm} warm-up rounds, "
            f"{compiles.count} compiles ({compiles.seconds:.3f} s)")

        sampler = Sampler(len(run.streams), cell.window,
                          np.random.default_rng([int(seed), 11]))
        frames: List[Frame] = []
        records: List[dict] = []
        compiles_before = compiles.count
        counters_before = dict(run.server.metrics.snapshot()["counters"])
        if trace:
            observe = run.server._observe

            def observed(result):
                records.extend(work.frame_records(result))
                observe(result)
            run.server._observe = observed
            annotate_server_spans(run.server)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            os.makedirs(TRACE_DIR, exist_ok=True)
            jax.profiler.start_trace(TRACE_DIR)
        rounds = 0
        end = t0
        while True:
            if trace:
                with jax.profiler.TraceAnnotation("bench/round"):
                    got = run.round()
            else:
                got = run.round()
            rounds += 1
            end = max(st.due for st in run.streams)
            for f in got:
                sampler.offer(f)
                frames.append(dataclasses.replace(f, rgb=None))
            if trace and rounds >= TRACE_ROUNDS:
                break
            if not trace and end - t0 >= seconds:
                break
        span = end - t0
        if trace:
            jax.profiler.stop_trace()
        in_window = compiles.count - compiles_before
        counters = {k: v - counters_before.get(k, 0)
                    for k, v in run.server.metrics.snapshot()[
                        "counters"].items()}
        spans = run.server.tracer.events() if trace else []
        stats = dict(run.devices[0].memory_stats() or {})
        module, plan = exes.largest()
        peak = run.peak_bytes(max(plan.get("temp", 0), 0))
        sample = sampler.items()
        for c in sample:
            c.poses = run.chain_poses(c)
        scene_arrays = run.scene_arrays
        run.server = None
        del run
        gc.collect()
    finally:
        compiles.close()
        exes.close()

    lat = [f.latency for f in frames]
    log(f"window: {rounds} rounds, {len(frames)} frames in {span:.6f} s; "
        f"compiles in window {in_window}; peak {peak} bytes")
    log(f"memory: reported peak {peak}; first chip's memory_stats "
        f"{stats}; largest compiled plan {module} {plan}")
    if lat:
        log("frame latency ms: min {:.3f} p50 {:.3f} max {:.3f}; slowest "
            "frames (stream, k): {}".format(
                1e3 * min(lat), 1e3 * percentile(lat, 50), 1e3 * max(lat),
                [(f.stream, f.k) for f in sorted(
                    frames, key=lambda f: -f.latency)[:3]]))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": len(frames), "failed": 0,
              "metrics": {}, "device": device}
    if trace:
        tr = trace_reduce.Trace.load(TRACE_DIR, cell.chips, exes.texts)
        with open(os.path.join(OUT, "trace_reduced.json"), "w") as f:
            json.dump(tr.to_json(), f)
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        ctx = Context(cell, tr, last_rounds(spans, rounds), counters,
                      frames, records, rounds, in_window, peaks, log)
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = tr.breakdown()
    else:
        e2e = window_metrics(lat, span, setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    return Measured(result, frames, sample, scene_arrays)


def last_rounds(events: List[dict], n: int) -> List[dict]:
    """The program's spans inside its last ``n`` ``round`` spans."""
    rounds = sorted((e for e in events
                     if e.get("name") == "round" and e.get("ph") == "X"),
                    key=lambda e: e["ts"])[-n:]
    if not rounds:
        return []
    lo = rounds[0]["ts"]
    hi = rounds[-1]["ts"] + rounds[-1]["dur"]
    return [e for e in events if e.get("ph") == "X"
            and lo <= e["ts"] and e["ts"] + e.get("dur", 0) <= hi]


def annotate_server_spans(server) -> None:
    """Mirror the server's own spans into the profiler's host trace, so
    device idle gaps can be named by what the serve round was doing."""
    import contextlib
    import jax
    tracer = server.tracer
    span = tracer.span

    @contextlib.contextmanager
    def both(name, track="main", args=None):
        with span(name, track=track, args=args), \
                jax.profiler.TraceAnnotation("serve/" + name):
            yield

    tracer.span = both
