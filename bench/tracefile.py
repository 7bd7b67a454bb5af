"""Reduce a profiler trace of the window to the device's numbers.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load` keeps what the
benchmark reads from it, as plain lists of ``(name, start_s, dur_s)``:

  * per device plane (``/device:TPU:<n>``), the events of its ``XLA Ops``
    line (what ran on the chip) and of its ``XLA Modules`` line (which
    compiled program it belonged to);
  * on the host, the window (``bench.window``) and the benchmark's own
    spans (:data:`SPAN_PREFIXES`), which are ``TraceAnnotation``s.

The reduction (:func:`reduce`) is plain arithmetic on those lists, so
the tests can feed it a small synthetic trace.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIXES = ("bench.", "front.", "control.", "drain.", "nms.",
                 "camera.")
WINDOW = "bench.window"


def load(log_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: dict = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [(e.name, e.start_ns * 1e-9,
                                         e.duration_ns * 1e-9)
                                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9))
    return {"devices": devices, "spans": spans}


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` of ``(name, start, dur)`` clipped to
    ``[lo, hi]``."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in intervals
                if s + d > lo and s < hi)
    out: list[list[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def module_name(name: str) -> str:
    """An XLA module's name without its per-compile id suffix."""
    return re.sub(r"(\(\d+\)|\.\d+)$", "", name)


def _innermost(spans, t: float) -> str:
    best = None
    for name, s, d in spans:
        if s <= t < s + d and name != WINDOW and (best is None
                                                  or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def reduce(trace: dict, top: int = 10) -> dict:
    """``busy_s`` (union of device op time, averaged over the devices),
    ``window_s``, the top ``device_ops`` (device time per XLA module)
    and the top ``idle_gaps`` (idle device time by the innermost
    benchmark span open on the host at the gap's middle)."""
    spans = trace["spans"]
    win = [(s, s + d) for name, s, d in spans if name == WINDOW]
    devices = {k: v for k, v in trace["devices"].items() if v.get("XLA Ops")}
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": []}
    if win:
        lo, hi = win[0]
    else:
        evs = [e for v in devices.values() for e in v["XLA Ops"]]
        lo = min(s for _, s, _ in evs)
        hi = max(s + d for _, s, d in evs)
    busy, modules, gaps = 0.0, {}, {}
    for lines in devices.values():
        merged = union(lines["XLA Ops"], lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, s, d in lines.get("XLA Modules", ()):
            t = sum(e - b for b, e in union([(name, s, d)], lo, hi))
            modules[module_name(name)] = modules.get(module_name(name),
                                                     0.0) + t
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                who = _innermost(spans, (a + b) / 2)
                gaps[who] = gaps.get(who, 0.0) + (b - a)
    n = len(devices)
    rank = lambda d: sorted(([k, v / n] for k, v in d.items()),  # noqa: E731
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy / n, "window_s": hi - lo,
            "device_ops": rank(modules), "idle_gaps": rank(gaps)}
