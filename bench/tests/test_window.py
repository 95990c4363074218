"""Window arithmetic: a whole-round rate and a tail over every frame,
with each frame timed from the delivery of its stream's previous one."""
import collections

import numpy as np
import pytest

import harness


def test_rate_is_frames_over_the_whole_span():
    lat = [0.25] * 19 + [0.5]
    m = harness.window_metrics(lat, span=1.25, setup_s=3.0)
    assert m["frames_per_s"] == pytest.approx(16.0)
    assert m["frame_latency_p95_ms"] == pytest.approx(
        1e3 * np.percentile(lat, 95))
    assert m["setup_s"] == 3.0


def test_p95_is_over_all_frames_not_per_stream():
    # One slow stream among four: its frames sit in the tail.
    lat = [0.1] * 30 + [0.9] * 10
    assert harness.window_metrics(lat, 4.0, 0.0)[
        "frame_latency_p95_ms"] == pytest.approx(900.0)


class _Session:
    def __init__(self, phase):
        self.phase, self.pending, self.frames = phase, collections.deque(), []

    def submit(self, poses, now):
        for p in poses:
            self.pending.append((p, now))


class _Server:
    """Serves one pending pose per session per round, taking ``dt``."""

    def __init__(self, sessions, clock, dt):
        self.sessions, self.clock, self.dt = sessions, clock, dt
        self.stamps = []

    def step(self):
        self.clock.t += self.dt
        for s in self.sessions:
            pose, stamp = s.pending.popleft()
            self.stamps.append(stamp)
            s.frames.append(np.zeros((1, 2, 2, 3)) + pose[0, 0])


class _Clock:
    t = 10.0

    def __call__(self):
        return self.t


class _Traffic:
    def pose(self, stream, k):
        return np.full((4, 4), float(k), np.float32)


def test_closed_loop_times_each_frame_from_the_last_delivery():
    clock = _Clock()
    run = harness.Run.__new__(harness.Run)
    run.clock, run.traffic = clock, _Traffic()
    run.cell = harness.Cell("c", {"render": {"window": 5}}, {}, 1, [], [])
    sessions = [_Session(0), _Session(2)]
    run.server = _Server(sessions, clock, dt=0.5)
    run.streams = [harness.Stream(i, s, 0, due=clock())
                   for i, s in enumerate(sessions)]
    frames = []
    for _ in range(6):
        frames += run.round()
    assert [f.k for f in frames if f.stream == 0] == list(range(6))
    assert all(f.latency == pytest.approx(0.5) for f in frames)
    # Each pose was sent when the previous frame of its stream arrived.
    assert run.server.stamps[2:4] == [10.5, 10.5]
    # Key frames follow each stream's phase: k = 0, then (k + phase) % 5.
    assert [f.k for f in frames if f.stream == 0 and f.key] == [0, 5]
    assert [f.k for f in frames if f.stream == 1 and f.key] == [0, 3]
    # Each frame carries the pixels of its own pose.
    assert all(f.rgb[0, 0, 0] == f.k for f in frames)
