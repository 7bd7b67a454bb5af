#!/usr/bin/env python3
"""The program's own spans and counters over one traced window.

    python bench/program_spans.py --workload det2-overload --seed 7 --seconds 51 --spans 1

The serving program times the steps beneath ``drain.dispatch`` itself
(``drain.stage``, ``drain.project``, ``drain.forward``,
``drain.fetch``, ``drain.backproject``) and counts ``upload_bytes`` and
``staged_rows``, on a ``repro.serving.telemetry.SpanSink``.  ``run.py``
gives the pod none.  This script serves one window as ``run.py --trace
1`` does, with such a sink in front of the benchmark's, recording the
program's spans while the window is driven (``--spans 1``) or not
(``--spans 0``, the baseline of their cost).  The trace's
``breakdown.idle_gaps`` then puts each idle gap of the device down to
the innermost program span as well as the benchmark's wrappers.

It prints one JSON line last: the run's result line, and ``program``
with what the per-layer metrics below read, every span's total host
ms and count per finished frame, the counters, and real crops per
frame.  The functions named for the metrics are their arithmetic,
for readers under ``bench/metrics/`` once ``run.py`` records what they
read (see PERF.md, Open questions).
"""

from __future__ import annotations

import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as run_mod  # noqa: E402
from bench import tracefile, window  # noqa: E402


# --------------------------------------------------------------------------
# the metrics' arithmetic
# --------------------------------------------------------------------------

def span_ms_per_frame(spans, name: str, frames: int) -> float | None:
    """Host ms in the program spans ``name`` per finished frame
    (``spans``: the sink's ``(name, t0_ns, t1_ns, parent, attrs)``)."""
    if not spans or not frames:
        return None
    return sum(t1 - t0 for n, t0, t1, _, _ in spans if n == name) \
        * 1e-6 / frames


def stage_ms_per_frame(spans, frames):
    return span_ms_per_frame(spans, "drain.stage", frames)


def fetch_ms_per_frame(spans, frames):
    return span_ms_per_frame(spans, "drain.fetch", frames)


def backproject_ms_per_frame(spans, frames):
    return span_ms_per_frame(spans, "drain.backproject", frames)


def upload_mb_per_frame(counters, frames: int) -> float | None:
    """Host bytes turned into device arrays, MB per finished frame."""
    if not counters or "upload_bytes" not in counters or not frames:
        return None
    return counters["upload_bytes"] * 1e-6 / frames


def launches_per_frame(modules: float | None, frames: int,
                       traced_s: float, window_s: float) -> float | None:
    """Device programs (``XLA Modules`` events) in the traced slice per
    frame, the frames being the window's scaled by the share of it that
    was traced: a count of the frames finished inside the slice would
    jump at the rounds' boundaries."""
    if modules is None or not frames or traced_s <= 0 or window_s <= 0:
        return None
    return modules / (frames * traced_s / window_s)


def module_events(raw: dict) -> float | None:
    """``XLA Modules`` events inside the ``tracefile.WINDOW`` span of a
    trace from ``tracefile.load``, averaged over the devices."""
    win = [(s, s + d) for n, s, d in raw["spans"] if n == tracefile.WINDOW]
    devices = [v["XLA Modules"] for v in raw["devices"].values()
               if v.get("XLA Modules")]
    if not win or not devices:
        return None
    lo, hi = win[0]
    return sum(sum(lo <= s < hi for _, s, _ in ev)
               for ev in devices) / len(devices)


# --------------------------------------------------------------------------
# one traced window with the program's spans
# --------------------------------------------------------------------------

def attach(pod):
    """Put a span-recording sink in front of the pod's telemetry sink,
    in the server and its backend; events go on to the old sink.  It
    records nothing until switched on."""
    from repro.serving.telemetry import SpanSink

    class ProgramSpans(SpanSink):
        enabled = True
        spans_on = False

        def __init__(self, sink):
            super().__init__()
            self.sink = sink

        def emit(self, event: str, **fields) -> None:
            self.sink.emit(event, **fields)

    rec = ProgramSpans(pod.server.telemetry)
    pod.server.telemetry = pod.backend.telemetry = rec
    return rec


@contextlib.contextmanager
def _recording(rec, spans_on: bool, seen: dict):
    """Record the program's spans while ``window.drive`` runs, and keep
    the window's bounds and the trace's module count in ``seen``."""
    drive, reduce = window.drive, tracefile.reduce

    def drive_w(*a, **k):
        rec.clear_spans()
        rec.spans_on = spans_on
        try:
            wr = seen["window"] = drive(*a, **k)
        finally:
            rec.spans_on = False
        return wr

    def reduce_w(raw, *a, **k):
        seen["modules"] = module_events(raw)
        return reduce(raw, *a, **k)

    window.drive, tracefile.reduce = drive_w, reduce_w
    try:
        yield
    finally:
        window.drive, tracefile.reduce = drive, reduce


def serve(sess: run_mod.Session, seed: int, seconds: float,
          spans_on: bool, *, free: bool = True) -> dict:
    """One traced window of ``sess`` (a ``run.Session`` made with
    ``trace=True``): its result line plus ``program``."""
    rec, sink, seen = attach(sess.pod), sess.sink, {}
    with _recording(rec, spans_on, seen):
        out = sess.window(seed, seconds, free=free)
    wr = seen["window"]
    frames = sum(wr.t0 <= t <= wr.t_end for t in sink.finished.values())
    window_s = wr.t_end - wr.t0
    spans = list(rec.spans)
    crops = sum(b for t, _, b, _ in sink.dispatches
                if wr.t0 <= t <= wr.t_end)
    totals: dict[str, list] = {}
    for name, t0, t1, _, _ in spans:
        tot = totals.setdefault(name, [0.0, 0])
        tot[0] += (t1 - t0) * 1e-6
        tot[1] += 1
    dev = out["device"]
    out["program"] = {
        "spans_on": spans_on, "frames": frames, "window_s": window_s,
        "frames_per_s": frames / window_s if window_s > 0 else None,
        "stage_ms_per_frame": stage_ms_per_frame(spans, frames),
        "fetch_ms_per_frame": fetch_ms_per_frame(spans, frames),
        "backproject_ms_per_frame": backproject_ms_per_frame(spans,
                                                             frames),
        "upload_mb_per_frame": upload_mb_per_frame(rec.counters, frames),
        "launches_per_frame": launches_per_frame(
            seen.get("modules"), frames, dev.get("window_s", 0.0),
            window_s),
        "modules_traced": seen.get("modules"),
        "crops_per_frame": crops / frames if frames else None,
        "counters": dict(rec.counters),
        "span_ms_per_frame": {k: v[0] / frames for k, v in totals.items()}
        if frames else {},
        "span_calls_per_frame": {k: v[1] / frames for k, v in totals.items()}
        if frames else {},
    }
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    cell = run_mod.load_cell(args.workload)

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise run_mod.NoChip(f"JAX found no TPU (platform "
                             f"{device.platform!r}); nothing was run")
    sess = run_mod.Session(cell, args.seed, True, device=device)
    out = serve(sess, args.seed, args.seconds, bool(args.spans))
    print(f"correct: {out['correct']}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except run_mod.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
