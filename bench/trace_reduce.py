"""From a JAX profiler trace to device busy time, per-scope time and gaps.

``Trace.load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps three things, on the profiler's one clock (nanoseconds):

- per device, the operations of its "XLA Ops" line: start, end, the
  op's kind (its HLO opcode, such as ``sort``, ``fusion`` or
  ``custom-call``) and its scope. The scope is the op's name stack
  (``jax.named_scope``), where the program labels its stages
  ``repro.frame/<stage>`` and its kernels ``repro.raster/<impl>``. A TPU
  trace names each op by its HLO instruction and carries no name stack,
  so the scope is looked up in the optimized HLO text of the op's
  module (``hlo_scopes``), which the harness records as the executables
  are compiled or loaded; the module is the "XLA Modules" event that
  holds the op;
- the host annotations the benchmark and the serve loop opened
  (``bench/round``, ``serve/<span>``);
- the window: from the first ``bench/round`` to the end of the last.

Busy time is the union of a device's operation intervals inside the
window, so nested or overlapping events count once. Time under a scope
is the union of the intervals of the operations whose scope holds it.
An idle gap is a stretch of the window in which a device runs nothing;
it is named by the host annotation that covers most of it, the
narrowest one on a tie.

``Trace.to_json``/``from_json`` keep the reduced form, which is what the
tests read (``bench/testdata``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]
DEVICE_PLANE = re.compile(r"^/device:(?:TPU|GPU|CPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench/", "serve/")
WINDOW_SPAN = "bench/round"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[list] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


# An HLO instruction in text form: its name, and the op_name metadata
# that holds its name stack.
_HLO_LINE = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*'
                       r'metadata=\{[^}]*op_name="([^"]*)"')
# A TPU op event's name is its instruction: "%name = <shape> opcode(...)",
# where a tuple shape is parenthesised and layouts hold "T(8,128)".
_TPU_OP = re.compile(r'^%?([\w.\-]+) = (?:\((?:[^()]|\([^()]*\))*\)|\S+) '
                     r'([a-z][\w\-]*)\(')


def hlo_scopes(texts: Dict[str, List[str]]) -> Dict[str, Dict[str, str]]:
    """Per module name, each instruction's name stack, from the modules'
    optimized HLO text."""
    out: Dict[str, Dict[str, str]] = {}
    for module, versions in texts.items():
        scopes = out.setdefault(module, {})
        for text in versions:
            for line in text.splitlines():
                m = _HLO_LINE.match(line)
                if m:
                    scopes.setdefault(m.group(1), m.group(2))
    return out


def op_kind(name: str) -> Tuple[str, str]:
    """An op event's (instruction name, kind). A TPU event is named by its
    HLO text and its kind is the opcode; elsewhere (the CPU) the event is
    named by the instruction, whose name less its number is the kind."""
    m = _TPU_OP.match(name)
    if m:
        return m.group(1), m.group(2)
    inst = name.lstrip("%").split(" ", 1)[0]
    return inst, re.sub(r"[._]\d+$", "", inst)


def op_label(kind: str, scope: str) -> str:
    """A stable label for an op: its innermost two ``repro.`` scopes and
    its kind."""
    scopes = re.findall(r"repro\.[a-z_]+/[A-Za-z0-9_]+", scope)
    return "/".join(scopes[-2:] + [kind]) if scopes else kind


def _module_at(modules: List[tuple], t: int) -> str:
    """The module whose event holds time ``t`` (modules sorted by start)."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return ""


class Trace:
    def __init__(self, ops: Dict[int, List[tuple]], host: List[tuple],
                 window: Interval):
        self.ops = {int(d): [tuple(o) for o in v] for d, v in ops.items()}
        self.host = [tuple(h) for h in host]
        self.window = (int(window[0]), int(window[1]))

    # -- loading -----------------------------------------------------------
    @classmethod
    def load(cls, trace_dir: str, n_devices: int,
             hlo: Dict[str, List[str]]) -> "Trace":
        """Reduce the newest trace under ``trace_dir``; ``hlo`` holds the
        optimized HLO text of the process's executables, by module."""
        from jax.profiler import ProfileData
        paths = sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        data = ProfileData.from_file(paths[-1])
        scopes = hlo_scopes(hlo)
        ops: Dict[int, List[tuple]] = defaultdict(list)
        host: List[tuple] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m and int(m.group(1)) < n_devices:
                lines = {line.name: line for line in plane.lines}
                modules = sorted(
                    (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                     ev.name.split("(", 1)[0])
                    for ev in (lines[MODULES_LINE].events
                               if MODULES_LINE in lines else ()))
                for ev in (lines[OPS_LINE].events
                           if OPS_LINE in lines else ()):
                    start = int(ev.start_ns)
                    stats = dict(ev.stats)
                    inst, kind = op_kind(ev.name)
                    inst = stats.get("hlo_op", inst)
                    module = stats.get("hlo_module") or \
                        _module_at(modules, start)
                    ops[int(m.group(1))].append(
                        (start, start + int(ev.duration_ns), kind,
                         scopes.get(module, {}).get(inst, "")))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(HOST_PREFIXES):
                            start = int(ev.start_ns)
                            host.append((start, start + int(ev.duration_ns),
                                         ev.name))
        rounds = [h for h in host if h[2] == WINDOW_SPAN]
        if not rounds:
            raise ValueError(f"no {WINDOW_SPAN} annotation in the trace")
        window = (min(h[0] for h in rounds), max(h[1] for h in rounds))
        return cls(dict(ops), host, window)

    def to_json(self) -> dict:
        return {"window": list(self.window), "host": self.host,
                "ops": {str(d): v for d, v in self.ops.items()}}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls({int(d): v for d, v in obj["ops"].items()}, obj["host"],
                   obj["window"])

    # -- device time -------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def busy(self, device: int, match=None) -> List[Interval]:
        """Union of ``device``'s op intervals in the window (optionally
        only ops whose (name, scope) satisfies ``match``)."""
        ivs = [(o[0], o[1]) for o in self.ops.get(device, [])
               if match is None or match(o[2], o[3])]
        return clip(union(ivs), *self.window)

    def busy_s(self, device: int) -> float:
        return length(self.busy(device)) * 1e-9

    def mean_busy_s(self) -> float:
        devs = self.devices
        return sum(self.busy_s(d) for d in devs) / max(len(devs), 1)

    def scope_s(self, scope: str) -> float:
        """Device seconds under a name-stack scope, summed over devices."""
        return sum(length(self.busy(d, lambda n, s: scope in s))
                   for d in self.devices) * 1e-9

    def match_s(self, match) -> float:
        return sum(length(self.busy(d, match)) for d in self.devices) * 1e-9

    # -- idle gaps ---------------------------------------------------------
    def idle_gaps(self) -> List[Tuple[int, int, int]]:
        """(device, start, end) of every stretch with nothing running."""
        lo, hi = self.window
        out = []
        for d in self.devices:
            t = lo
            for a, b in self.busy(d):
                if a > t:
                    out.append((d, t, a))
                t = max(t, b)
            if hi > t:
                out.append((d, t, hi))
        return out

    def host_name(self, lo: int, hi: int) -> str:
        """The host annotation covering most of [lo, hi)."""
        best: Optional[tuple] = None
        for a, b, name in self.host:
            ov = min(b, hi) - max(a, lo)
            if ov <= 0:
                continue
            rank = (ov, -(b - a))
            if best is None or rank > best[0]:
                best = (rank, name)
        return best[1] if best else "no host span"

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the longest gaps."""
        per: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for o in self.ops[d]:
                lo, hi = max(o[0], self.window[0]), min(o[1], self.window[1])
                if hi > lo:
                    per[op_label(o[2], o[3])] += (hi - lo) * 1e-9
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[1] - g[2])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[f"device {d}: {self.host_name(a, b)}",
                               (b - a) * 1e-9] for d, a, b in gaps]}


def describe(trace_dir: str, per_line: int = 5) -> None:
    """Print a trace's planes, lines and first events with their stats:
    what to look at by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={dict(ev.stats)}")


if __name__ == "__main__":
    import sys
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 5)
