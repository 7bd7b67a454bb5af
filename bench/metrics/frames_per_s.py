"""Frames finished per second of the window (frames/s): every frame
whose ``frame_finish`` came before the last ``serve_open_batch`` call
returned, over the seconds from the window's opening to that return.
Frames are handed over until the window's seconds are up; the round in
flight then finishes, so the count and the time hold whole rounds."""


def read(run):
    span = run.t_end - run.t0
    if not run.frames or span <= 0:
        return None
    return run.frames / span
