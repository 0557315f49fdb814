"""Span arithmetic the per-layer readers share."""

from __future__ import annotations


def self_seconds(traces, name: str) -> float:
    """Summed self time of every span called ``name``: its duration less
    the part its direct children cover (children of one span run one
    after another on one thread, so their durations add)."""
    total = 0.0
    for tr in traces:
        spans = [s for s in tr.spans if s.t1 is not None]
        child = {}
        for s in spans:
            if s.parent_id is not None:
                child[s.parent_id] = child.get(s.parent_id, 0.0) + (s.t1 - s.t0)
        for s in spans:
            if s.name == name:
                total += max(0.0, (s.t1 - s.t0) - child.get(s.span_id, 0.0))
    return total
