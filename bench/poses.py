"""Camera poses for the traffic mixes, from the seed and the mix's file.

One general generator reads every ``bench/traffic/<mix>.json``: a mix
names its trajectory ``family`` and that family's parameters, and a new
mix is a new data file. Poses are world-to-camera (4, 4) float32
matrices in the camera convention the renderer takes: rows right, down,
forward; x right, y down, z forward.

Family ``head``: a head-mounted camera at the per-frame deltas of the
paper's 90 FPS setup (2 cm of translation and 1 degree of rotation per
frame). The eye slides back and forth along a seeded direction across
the view axis, reversing every ``reverse_every`` frames, so it stays in
the room and never restarts; the view direction circles the axis to the
target on a cone of ``cone_deg``, at exactly ``rotate_deg_per_frame``
between frames. The view stays within ``cone_deg`` of the axis, so the
work per frame is stationary over any window. The seed sets each
stream's start point, slide direction, and where on both cycles it
starts.
"""
from __future__ import annotations

import numpy as np


def look_along(eye: np.ndarray, fwd: np.ndarray,
               up=(0.0, 1.0, 0.0)) -> np.ndarray:
    """World-to-camera matrix at ``eye`` looking along ``fwd``. (4, 4)."""
    fwd = fwd / np.linalg.norm(fwd)
    right = np.cross(fwd, np.asarray(up, np.float64))
    right = right / np.linalg.norm(right)
    down = np.cross(fwd, right)
    rot = np.stack([right, down, fwd])
    w2c = np.eye(4)
    w2c[:3, :3] = rot
    w2c[:3, 3] = -rot @ eye
    return w2c


class HeadStream:
    """One ``head``-family stream; ``pose(k)`` is its k-th frame."""

    def __init__(self, params: dict, rng: np.random.Generator):
        eye = np.asarray(params["eye"], np.float64)
        target = np.asarray(params["target"], np.float64)
        jitter = float(params["eye_jitter_m"])
        self.step = float(params["translate_m_per_frame"])
        self.period = 2 * int(params["reverse_every"])
        cone = np.radians(float(params["cone_deg"]))
        delta = np.radians(float(params["rotate_deg_per_frame"]))

        self.eye0 = eye + rng.uniform(-jitter, jitter, 3)
        axis = target - self.eye0
        self.axis = axis / np.linalg.norm(axis)
        # Slide direction: a seeded unit vector across the view axis.
        a = rng.uniform(0.0, 2.0 * np.pi)
        u = np.cross(self.axis, [0.0, 1.0, 0.0])
        u /= np.linalg.norm(u)
        v = np.cross(self.axis, u)
        self.u, self.v = u, v
        self.slide = np.cos(a) * u + np.sin(a) * v
        self.start = int(rng.integers(0, self.period))
        # Azimuth step on the cone so that successive view directions
        # are exactly `delta` apart:
        # cos(delta) = cos^2(cone) + sin^2(cone) cos(dphi).
        cos_dphi = (np.cos(delta) - np.cos(cone) ** 2) / np.sin(cone) ** 2
        self.dphi = np.arccos(np.clip(cos_dphi, -1.0, 1.0)) * \
            (1.0 if rng.random() < 0.5 else -1.0)
        self.phi0 = rng.uniform(0.0, 2.0 * np.pi)
        self.cone = cone

    def pose(self, k: int) -> np.ndarray:
        m = (self.start + int(k)) % self.period
        half = self.period // 2
        tri = m if m <= half else self.period - m
        eye = self.eye0 + self.slide * self.step * (tri - half / 2.0)
        phi = self.phi0 + self.dphi * int(k)
        fwd = (np.cos(self.cone) * self.axis + np.sin(self.cone) * (
            np.cos(phi) * self.u + np.sin(phi) * self.v))
        return look_along(eye, fwd).astype(np.float32)


FAMILIES = {"head": HeadStream}


class Traffic:
    """A traffic mix: ``streams`` clients over ``scenes`` scenes."""

    def __init__(self, mix: dict, seed: int):
        if mix.get("loop") != "closed" or int(mix.get("in_flight", 1)) != 1:
            raise ValueError("only closed-loop mixes with one frame in "
                             "flight per stream are supported")
        self.streams = int(mix["streams"])
        self.scenes = int(mix["scenes"])
        traj = mix["trajectory"]
        family = FAMILIES[traj["family"]]
        self._streams = [
            family(traj, np.random.default_rng([int(seed), 7, i]))
            for i in range(self.streams)]

    def scene_of(self, stream: int) -> int:
        """Streams alternate over the mix's scenes."""
        return stream % self.scenes

    def pose(self, stream: int, k: int) -> np.ndarray:
        return self._streams[stream].pose(k)
