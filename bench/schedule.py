"""Open-loop arrival schedules, generated from a traffic mix and a seed.

The per-stream ingredients are those of ``repro.serving.traffic``
(``StreamClock``): a frame every ``1/(fps * scale(t))`` seconds, a
seeded multiplicative lognormal jitter ``exp(normal(0, jitter))`` drawn
from ``np.random.default_rng((seed, stream))``, and ``rate_trace`` steps
``(t_start_s, scale)``.  They are kept here so that the yardstick does
not move when the program's generator does.

Every seed offers the same work: a stream makes exactly
``floor(fps * integral of scale over the window)`` frames.  They are laid
out in the stream's own time (where the rate is ``fps`` throughout): a
seeded phase under one gap, then gaps whose jitter multipliers are
scaled to mean 1, so the last frame is due before the window ends.  The
own time is then mapped onto the window through the rate trace, so a
step of scale 3 packs three times the frames into its seconds.  The seed
changes when frames arrive, not how many.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Due:
    """One camera frame due at the pod's front door.

    ``t_s`` is seconds after the window opens; ``frame_idx`` is the
    stream's own frame counter."""

    t_s: float
    stream: int
    frame_idx: int


def _steps(rate_trace, seconds: float) -> list[tuple[float, float, float]]:
    """``(start, end, scale)`` segments covering ``[0, seconds)``."""
    trace = sorted((float(t), float(s)) for t, s in rate_trace)
    if any(s <= 0 for _, s in trace):
        raise ValueError(f"rate_trace scales must be > 0: {trace}")
    bounds = [(0.0, 1.0)] + [(t, s) for t, s in trace if 0.0 < t < seconds]
    for t, s in trace:  # a step at or before 0 sets the opening scale
        if t <= 0.0:
            bounds[0] = (0.0, s)
    ends = [t for t, _ in bounds[1:]] + [seconds]
    return [(t, e, s) for (t, s), e in zip(bounds, ends)]


def stream_times(stream: int, fps: float, seconds: float, *, seed: int,
                 jitter: float = 0.0, rate_trace=()) -> list[float]:
    """Due times of one stream in ``[0, seconds)``."""
    if fps <= 0 or jitter < 0 or seconds <= 0:
        raise ValueError(f"need fps > 0, jitter >= 0, seconds > 0; got "
                         f"{fps}, {jitter}, {seconds}")
    steps = _steps(rate_trace, seconds)
    own_total = sum((e - t) * s for t, e, s in steps)  # own seconds
    k = int(math.floor(fps * own_total + 1e-9))
    if k == 0:
        return []
    rng = np.random.default_rng((seed, stream))
    phase = float(rng.uniform())
    mult = np.exp(rng.normal(0.0, jitter, size=k - 1)) if jitter > 0 \
        else np.ones(k - 1)
    if k > 1:
        mult /= mult.mean()
    own = (phase + np.concatenate([[0.0], np.cumsum(mult)])) / fps
    out, seg, base = [], 0, 0.0  # base: own seconds before segment seg
    for u in own:
        while seg + 1 < len(steps) and \
                base + (steps[seg][1] - steps[seg][0]) * steps[seg][2] <= u:
            base += (steps[seg][1] - steps[seg][0]) * steps[seg][2]
            seg += 1
        t0, _, s = steps[seg]
        out.append(t0 + (float(u) - base) / s)
    return out


def schedule(mix: dict, seconds: float, seed: int) -> list[Due]:
    """Every frame due in a window of ``seconds``, sorted by due time
    (ties by stream).  ``mix``: the traffic file's parameters."""
    n = int(mix["streams"])
    fps = mix["fps"]
    fps = [float(f) for f in fps] if isinstance(fps, list) \
        else [float(fps)] * n
    if len(fps) != n:
        raise ValueError(f"{len(fps)} fps values for {n} streams")
    out = []
    for s in range(n):
        times = stream_times(s, fps[s], seconds, seed=seed,
                             jitter=float(mix.get("jitter", 0.0)),
                             rate_trace=mix.get("rate_trace", ()))
        out.extend(Due(t, s, k) for k, t in enumerate(times))
    out.sort(key=lambda d: (d.t_s, d.stream))
    return out
