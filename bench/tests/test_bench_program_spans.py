"""The program's spans in a traced window (``bench/program_spans.py``).

The arithmetic of the metrics that read them on hand-built inputs, the
trace reduction putting a gap down to a drain step nested in
``drain.dispatch``, and one small traced window on the CPU, in which
the benchmark's wrapper spans stay apart from the program's.
"""

import pytest

from bench import program_spans as ps
from bench import run as run_mod
from bench import tracefile
from bench.tests.test_bench_run import SMALL

MS = 1_000_000  # ns


def test_a_gap_inside_a_nested_drain_step_goes_to_that_step():
    trace = {
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("fusion", 10.0, 1.0), ("copy", 12.5, 0.5),
                        ("conv", 16.0, 1.0)]}},
        "spans": [
            ("bench.window", 10.0, 8.0),
            ("drain.dispatch", 10.0, 8.0),    # its own gap [17, 18]
            ("drain.dispatch", 10.2, 7.6),    # the wrapper inside it
            ("drain.stage", 11.0, 2.0),       # gap [11, 12.5]
            ("drain.fetch", 13.0, 3.0),       # gap [13, 16]
        ],
    }
    gaps = dict(map(tuple, tracefile.reduce(trace)["idle_gaps"]))
    assert gaps == pytest.approx({"drain.stage": 1.5, "drain.fetch": 3.0,
                                  "drain.dispatch": 1.0})


SPANS = [("drain.dispatch", 0, 10 * MS, None, {}),
         ("drain.stage", 1 * MS, 3 * MS, 0, {"b": 4}),
         ("drain.fetch", 4 * MS, 5 * MS, 0, {}),
         ("drain.fetch", 5 * MS, 7 * MS, 0, {}),
         ("drain.backproject", 7 * MS, 8 * MS, 0, {})]


@pytest.mark.parametrize("fn, want", [
    (ps.stage_ms_per_frame, 1.0), (ps.fetch_ms_per_frame, 1.5),
    (ps.backproject_ms_per_frame, 0.5)])
def test_span_metrics_per_frame(fn, want):
    assert fn(SPANS, 2) == pytest.approx(want)
    assert fn([], 2) is None and fn(SPANS, 0) is None


def test_upload_mb_per_frame():
    assert ps.upload_mb_per_frame({"upload_bytes": 44_200_000}, 2) \
        == pytest.approx(22.1)
    assert ps.upload_mb_per_frame({}, 2) is None
    assert ps.upload_mb_per_frame({"staged_rows": 2}, 2) is None
    assert ps.upload_mb_per_frame({"upload_bytes": 1}, 0) is None


def test_launches_per_frame_scales_the_frames_by_the_traced_share():
    # 60 frames in a 50 s window, 12.5 s of it traced: 15 frames' worth
    assert ps.launches_per_frame(3000, 60, 12.5, 50.0) == pytest.approx(200)
    assert ps.launches_per_frame(None, 60, 12.5, 50.0) is None
    assert ps.launches_per_frame(3000, 0, 12.5, 50.0) is None
    assert ps.launches_per_frame(3000, 60, 0.0, 50.0) is None


def test_module_events_counts_inside_the_window():
    raw = {"devices": {
        "/device:TPU:0": {"XLA Modules": [("jit_a(1)", 1.0, 0.1),
                                          ("jit_b(2)", 5.0, 0.1),
                                          ("jit_a(1)", 9.0, 0.1)]},
        "/device:TPU:1": {"XLA Modules": [("jit_a(1)", 6.0, 0.1)]}},
        "spans": [("bench.window", 4.0, 6.0)]}
    assert ps.module_events(raw) == pytest.approx(1.5)
    assert ps.module_events(dict(raw, spans=[])) is None
    assert ps.module_events({"devices": {}, "spans": raw["spans"]}) is None


@pytest.fixture(scope="module")
def traced():
    cell = run_mod.load_cell("det2-overload")
    cell["mix"] = dict(cell["mix"], streams=2, fps=0.5)
    overrides = dict(SMALL, detectors=[
        dict(d, input_size=s, width_mult=w, base_depth=n)
        for d, s, w, n in zip(cell["config"]["detectors"], (64, 96),
                              (0.25, 0.5), (1, 2))])
    sess = run_mod.Session(cell, 2 ** 31 + 5, True, cache=False,
                           config_overrides=overrides, log=lambda *_: None)
    return sess, ps.serve(sess, 2 ** 31 + 5, 4.0, True, free=False)


def test_a_traced_window_keeps_wrapper_and_program_spans_apart(traced):
    sess, out = traced
    assert out["correct"] is True
    wrappers = {"front.wait", "control.admit", "control.plan_drain",
                "control.ingest", "drain.dispatch", "nms.suppress",
                "drain.discovery", "camera.frame"}
    assert {r[0] for r in sess.spans.records} <= wrappers
    prog = out["program"]
    assert prog["frames"] > 0
    assert {"drain.stage", "drain.project", "drain.forward", "drain.fetch",
            "drain.backproject"} <= set(prog["span_ms_per_frame"])
    for name in ("stage_ms_per_frame", "fetch_ms_per_frame",
                 "backproject_ms_per_frame", "upload_mb_per_frame"):
        assert prog[name] > 0, name
    erp_mb = 64 * 128 * 3 * 4 * 1e-6
    assert prog["upload_mb_per_frame"] >= erp_mb * prog["crops_per_frame"]
    rec = sess.pod.server.telemetry
    assert rec.sink is sess.sink and sess.pod.backend.telemetry is rec
    assert not rec.spans_on
