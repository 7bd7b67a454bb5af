"""Useful detector FLOPs finished in the traced part of the window over
the chip's bf16 peak for that part (%): every batched and discovery
forward's real rows (padding rows left out) times ``bench/flops.py``'s
count of its rung."""


def read(run):
    if run.trace is None or run.peak is None or not run.trace["window_s"]:
        return None
    flops = sum(rows * run.flops[idx] for t, idx, rows in run.forwards
                if run.trace_t0 <= t <= run.t_end)
    return 100.0 * flops / (run.trace["window_s"] * run.peak["bf16_flops"])
