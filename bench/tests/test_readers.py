"""The serve round's per-layer readers on a synthetic ``Context``: what
they add up, what they leave out, and that they stay silent where the
program has no such span or counter."""
import pytest

import harness


def _x(name, ts, dur, tid=0):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1,
            "tid": tid}


# Two traced rounds (times in us), with spans before and after them that
# a reader must leave out.
SPANS = [
    _x("fetch", 0.0, 900.0),                      # before the rounds
    _x("round", 1_000.0, 10_000.0),
    _x("stack", 1_100.0, 500.0, 1),
    _x("fetch", 5_000.0, 2_000.0, 1),
    _x("observe", 7_100.0, 1_000.0, 1),
    _x("round", 20_000.0, 10_000.0),
    _x("stack", 20_100.0, 700.0, 1),
    _x("fetch", 24_000.0, 3_000.0, 1),
    _x("observe", 27_100.0, 1_400.0, 1),
    {"name": "resize", "ph": "i", "ts": 20_050.0, "pid": 1, "tid": 1},
    _x("fetch", 40_000.0, 5_000.0, 1),            # after the rounds
]
COUNTERS = {"serve_fetch_bytes_total": 10e6, "serve_frames_total": 4.0,
            "serve_wait_seconds_total": 0.02}


def _ctx(spans=SPANS, counters=COUNTERS):
    return harness.Context(cell=None, trace=None, spans=spans,
                           counters=counters, frames=[], records=[],
                           rounds=2, compiles=0, peaks=None)


def _read(name, ctx):
    return harness.load_reader(name)(ctx)


@pytest.mark.parametrize("name,want", [
    ("serve.fetch_ms_per_round", 2.5),
    ("serve.observe_ms_per_round", 1.2),
    ("serve.stack_ms_per_round", 0.6),
    ("serve.fetch_gbytes_per_s", 2.0),
    ("serve.wait_ms_per_frame", 5.0),
])
def test_reader_counts_only_the_traced_rounds(name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "serve.fetch_ms_per_round", "serve.observe_ms_per_round",
    "serve.stack_ms_per_round", "serve.fetch_gbytes_per_s",
    "serve.wait_ms_per_frame"])
def test_reader_is_silent_without_its_span_or_counter(name):
    # A program that opens no such span and keeps no such counter, as
    # one from before they were added.
    spans = [e for e in SPANS
             if e["name"] not in ("stack", "fetch", "observe")]
    counters = {"serve_frames_total": 4.0}
    assert _read(name, _ctx(spans, counters)) is None
    assert _read(name, _ctx([], {})) is None
