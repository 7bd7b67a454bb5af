"""Host time of the blocking backend calls per finished frame (ms):
``drain.dispatch`` (``VariantQueues.drain_ops``: projection upload,
batched forward, decode, row-wise back-projection) and
``drain.discovery`` (the full-ERP discovery forward)."""

from bench.metrics._common import span_total


def read(run):
    if not run.spans or not run.frames:
        return None
    return span_total(run.spans, "drain.") * 1e3 / run.frames
