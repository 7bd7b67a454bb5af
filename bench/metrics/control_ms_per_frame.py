"""Host self time of the control plane per finished frame (ms): the
spans ``control.admit`` (``PodServer._admit_arrival``: SRoI prediction,
allocation, admission, emission), ``control.plan_drain`` and
``control.ingest`` (frame bookkeeping, telemetry), less the camera's
frame copy, the drain and the NMS nested inside them."""

from bench.metrics._common import self_times


def read(run):
    if not run.spans or not run.frames:
        return None
    t = self_times(run.spans)
    return sum(v for k, v in t.items() if k.startswith("control.")) \
        * 1e3 / run.frames
