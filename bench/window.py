"""Drive the pod's open loop on the host clock and record what it does.

The program's three open-loop phases (``PodServer.open_loop_begin``,
``serve_open_batch``, ``open_loop_end``) are driven here: the driver
sleeps until a frame is due, hands every frame due by now to one
``serve_open_batch`` call in due order, and repeats until the window's
seconds are up.  Completions are
stamped by :func:`make_sink`'s telemetry sink whose ``frame_finish``
record comes after NMS, when the detections are host lists.

Everything else the benchmark reads is recorded by wrappers bound to
the live objects of one run (nothing in the program is edited):

  * :class:`Spans` — host spans around the calls into each layer, as
    ``jax.profiler.TraceAnnotation`` too, so the device trace sees them;
  * :class:`Capture` — what the timed path served, for the reference
    comparison: a seeded sample of batched dispatches (crops, raw heads,
    decoded rows, back-projected detections) and every frame's NMS
    input and keep-mask.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench.schedule import Due


def make_sink():
    """A telemetry sink that stamps the host clock on the records the
    benchmark reads: ``frame_finish`` (completion), ``admission``
    (verdicts) and ``dispatch_launch`` (batch fill).  It subclasses the
    program's ``TelemetrySink``, so the server emits through its normal
    hook."""
    from repro.serving.telemetry import TelemetrySink

    class _Sink(TelemetrySink):
        enabled = True

        def __init__(self):
            self.finished: dict[tuple[int, int], float] = {}
            self.verdicts: dict[tuple[int, int], str] = {}
            self.dispatches: list[tuple[float, str, int, int]] = []

        def emit(self, event: str, **f) -> None:
            if event == "frame_finish":
                self.finished[(f["stream"], f["frame_idx"])] = \
                    time.perf_counter()
            elif event == "admission":
                self.verdicts[(f["stream"], f["frame_idx"])] = f["verdict"]
            elif event == "dispatch_launch":
                self.dispatches.append((time.perf_counter(), f["variant"],
                                        f["b"], f["padded"]))

    return _Sink()


# --------------------------------------------------------------------------
# host spans
# --------------------------------------------------------------------------

class Spans:
    """Named host spans around calls on live objects.

    ``wrap(obj, attr, name)`` replaces the bound method on the instance
    with one that records ``(name, start, end, depth)`` on the host
    clock, inside a ``jax.profiler.TraceAnnotation`` of the same name.
    Recording starts at :meth:`open` (the window), so set-up calls
    leave nothing behind."""

    def __init__(self):
        self.records: list[tuple[str, float, float, int]] = []
        self.on = False
        self._depth = 0

    def open(self) -> None:
        self.records.clear()
        self.on = True

    def close(self) -> None:
        self.on = False

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return
        import jax

        self._depth += 1
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self._depth -= 1
            self.records.append((name, t0, time.perf_counter(), self._depth))

    def wrap(self, obj, attr: str, name: str) -> None:
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with self.span(name):
                return fn(*a, **k)

        setattr(obj, attr, wrapped)


def attach_spans(pod, spans: Spans) -> None:
    """Spans at the layer boundaries of one pod (see PERF.md, Layers)."""
    server = pod.server
    spans.wrap(server, "_admit_arrival", "control.admit")
    spans.wrap(server.policy, "plan_drain", "control.plan_drain")
    spans.wrap(server, "_ingest", "control.ingest")
    spans.wrap(server.queues, "drain_ops", "drain.dispatch")
    spans.wrap(server, "_suppress_tick", "nms.suppress")
    spans.wrap(pod.backend, "infer_erp", "drain.discovery")
    spans.wrap(server, "frame_source", "camera.frame")


# --------------------------------------------------------------------------
# what the timed path served
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """One sampled batched dispatch, as served."""

    variant: int
    size: int
    b: int
    items: list = dataclasses.field(default_factory=list)  # (frame, center, fov)
    pis: object = None       # (b, S, S, 3) crops, device
    geoms: list | None = None
    out: tuple | None = None  # (boxes, scores, classes, heads), device
    dets: list | None = None  # per row: [(sph box, category, score)]


class Capture:
    """Seeded sample of served dispatches plus every frame's NMS.

    Per variant the ``keep`` largest dispatches are kept, ties broken
    by a draw from the seed, so the sample holds the fullest batches.
    Also counts the rows of every forward (for the FLOP count) and
    flags malformed detections."""

    def __init__(self, seed: int, keep: int = 2):
        self.keep = keep
        self.on = False
        self._cur: Served | None = None
        self._rows: int | None = None
        self.reset(seed)

    def reset(self, seed: int) -> None:
        """Forget what was recorded; the next window draws from ``seed``."""
        self.rng = np.random.default_rng((seed, 0xC4))
        self.sample: dict[int, list[tuple[tuple, Served]]] = {}
        self.nms: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self.forwards: list[tuple[float, int, int]] = []  # (t, variant, rows)
        self.malformed = 0

    def attach(self, pod, n_classes: int) -> None:
        backend = pod.backend
        launch = backend.launch_srois_batched
        project = backend._project_chunk
        batched = backend._batched_fn

        def launch_w(items, variant, group=None):
            idx = variant.index - 1
            rec = self._admit(idx, len(items)) if self.on else None
            if rec is not None:
                rec.items = [(f, tuple(map(float, r.center)),
                              tuple(map(float, r.fov))) for f, r in items]
            self._cur, self._rows = rec, len(items)
            try:
                resolve = launch(items, variant, group)
            finally:
                self._cur, self._rows = None, None
            if rec is None:
                return resolve

            def resolve_w():
                out = resolve()
                rec.dets = [[(np.array(d.box, np.float64), d.category,
                              d.score) for d in row] for row in out]
                return out

            return resolve_w

        def project_w(chunk, size):
            pis, geoms = project(chunk, size)
            if self._cur is not None:
                self._cur.pis, self._cur.geoms = pis, list(geoms)
                self._cur.size = size
            return pis, geoms

        def batched_w(idx, b_pad, group=None):
            fn = batched(idx, b_pad, group)
            rows = self._rows if self._rows is not None else 1
            rec = self._cur

            def call(params, imgs, valid):
                out = fn(params, imgs, valid)
                if self.on:
                    self.forwards.append((time.perf_counter(), idx, rows))
                if rec is not None:
                    rec.out = out
                return out

            return call

        backend.launch_srois_batched = launch_w
        backend._project_chunk = project_w
        backend._batched_fn = batched_w
        for loop in pod.loops:
            self._wrap_finalize(loop, n_classes)

    def _wrap_finalize(self, loop, n_classes: int) -> None:
        finalize = loop.finalize_detections

        def finalize_w(result, keep):
            if self.on and result.detections:
                dets = result.detections
                boxes = np.stack([np.asarray(d.box, np.float64)
                                  for d in dets])
                scores = np.array([d.score for d in dets], np.float64)
                cats = np.array([d.category for d in dets])
                if (boxes.shape[1:] != (4,) or not np.isfinite(boxes).all()
                        or not ((scores > 0) & (scores <= 1)).all()
                        or not ((cats >= 0) & (cats < n_classes)).all()):
                    self.malformed += 1
                if keep is not None:
                    self.nms.append((boxes, scores,
                                     np.asarray(keep, bool).copy()))
            return finalize(result, keep)

        loop.finalize_detections = finalize_w

    def _admit(self, idx: int, b: int) -> Served | None:
        key = (b, float(self.rng.random()))
        held = self.sample.setdefault(idx, [])
        if len(held) >= self.keep:
            low = min(range(len(held)), key=lambda i: held[i][0])
            if held[low][0] >= key:
                return None
            held.pop(low)
        rec = Served(variant=idx, size=0, b=b)
        held.append((key, rec))
        return rec

    def served(self) -> list[Served]:
        return [rec for idx in sorted(self.sample)
                for _, rec in sorted(self.sample[idx], key=lambda kr: kr[0])
                if rec.out is not None and rec.dets is not None]


# --------------------------------------------------------------------------
# the open loop on the host clock
# --------------------------------------------------------------------------

@dataclasses.dataclass
class WindowResult:
    t0: float
    seconds: float
    t_end: float
    due: list
    handover: dict            # (stream, frame) -> host time handed over
    failed: set               # (stream, frame) of arrivals that raised
    errors: list


def sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05) if left > 0.002 else 0)


def drive(server, due: list[Due], seconds: float, *, frame_base: int,
          t_base: float, spans: Spans | None = None,
          at=None) -> WindowResult:
    """Hand ``due`` frames to ``server`` at their due times (window
    seconds after now); stream ``s``'s k-th frame is served as frame
    ``frame_base + k`` at event time ``t_base + due``.  Once ``seconds``
    are up nothing more is handed over: the call in flight finishes, and
    frames still waiting are never handed over (a camera's depth-1
    buffer would have replaced them).  ``at = (t_s, fn)`` calls ``fn()``
    once, before the first handover ``t_s`` or more into the window."""
    from repro.serving.traffic import Arrival

    span = spans.span if spans is not None else (
        lambda name: contextlib.nullcontext())
    handover: dict = {}
    failed: set = set()
    errors: list = []
    n, i = len(due), 0
    t0 = time.perf_counter()
    while i < n:
        if t0 + due[i].t_s > time.perf_counter():
            with span("front.wait"):
                sleep_until(t0 + due[i].t_s)
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if at is not None and now >= at[0]:
            at[1]()
            at = None
        j = i
        while j < n and due[j].t_s <= now:
            j += 1
        batch = due[i:j]
        th = time.perf_counter()
        keys = [(d.stream, frame_base + d.frame_idx) for d in batch]
        for k in keys:
            handover[k] = th
        try:
            server.serve_open_batch([
                Arrival(t_s=t_base + d.t_s, stream=d.stream,
                        frame_idx=frame_base + d.frame_idx) for d in batch])
        except Exception as e:  # a failed round is counted, not fatal
            import traceback

            failed.update(keys)
            errors.append(traceback.format_exc())
            del e
        i = j
    t_end = time.perf_counter()
    return WindowResult(t0, seconds, t_end, due, handover, failed, errors)
