"""Arithmetic the metric readers share (not a metric: no ``read``)."""

from __future__ import annotations


def self_times(spans) -> dict[str, float]:
    """Host seconds per span name, less the time of the spans nested
    directly inside it (``spans``: ``(name, start, end, depth)``)."""
    out: dict[str, float] = {}
    order = sorted(spans, key=lambda s: (s[1], s[3]))
    for i, (name, t0, t1, depth) in enumerate(order):
        child = 0.0
        for name2, u0, u1, d2 in order[i + 1:]:
            if u0 >= t1:
                break
            if d2 == depth + 1 and u1 <= t1:
                child += u1 - u0
        out[name] = out.get(name, 0.0) + (t1 - t0) - child
    return out


def span_total(spans, prefix: str) -> float:
    return sum(t1 - t0 for name, t0, t1, _ in spans if name.startswith(prefix))
