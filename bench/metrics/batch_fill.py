"""Real over padded rows of the window's batched dispatches (%), from
the program's ``dispatch_launch`` telemetry (``b`` / ``padded``)."""


def read(run):
    padded = sum(p for _, _, _, p in run.dispatches)
    if not padded:
        return None
    return 100.0 * sum(b for _, _, b, _ in run.dispatches) / padded
