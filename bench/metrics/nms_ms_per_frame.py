"""Host time of ``PodServer._suppress_tick`` (batched spherical NMS,
host or device) per finished frame (ms)."""

from bench.metrics._common import span_total


def read(run):
    if not run.spans or not run.frames:
        return None
    return span_total(run.spans, "nms.") * 1e3 / run.frames
