"""Share of the traced window with no operation on the device (%):
100 * (1 - union of the device's op intervals / window)."""


def read(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
