"""The program's own spans inside the traced rounds, for the readers.

``Context.spans`` holds the serve loop's ``Tracer`` events (times in
microseconds). A reader counts only the spans that lie inside a
``round`` span, so work before or after the traced rounds is left out.
"""
from __future__ import annotations

from typing import List, Optional, Tuple


def in_rounds(spans: List[dict], name: str) -> Tuple[List[dict], int]:
    """The complete spans called ``name`` inside a ``round`` span, and
    the number of ``round`` spans."""
    done = [e for e in spans if e.get("ph") == "X"]
    rounds = [(e["ts"], e["ts"] + e["dur"]) for e in done
              if e["name"] == "round"]
    inside = [e for e in done if e["name"] == name and any(
        lo <= e["ts"] and e["ts"] + e["dur"] <= hi for lo, hi in rounds)]
    return inside, len(rounds)


def seconds(spans: List[dict], name: str) -> float:
    """Total duration of ``name`` inside the rounds, in seconds."""
    inside, _ = in_rounds(spans, name)
    return sum(e["dur"] for e in inside) * 1e-6


def ms_per_round(spans: List[dict], name: str) -> Optional[float]:
    """Total duration of ``name`` inside the rounds over their number,
    in ms; None where the program opened no such span."""
    inside, rounds = in_rounds(spans, name)
    if not inside:
        return None
    return sum(e["dur"] for e in inside) / rounds / 1e3
