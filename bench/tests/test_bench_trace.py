"""The trace reduction on a small synthetic trace with known answers."""

import pytest

from bench import tracefile


def _trace():
    # window [10, 20] s on the host; one device busy in [11, 13] and
    # [12, 14] (overlapping ops, union 3 s) and [18, 19] (1 s)
    return {
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("fusion.1", 11.0, 2.0), ("conv.2", 12.0, 2.0),
                        ("fusion.3", 18.0, 1.0), ("early", 5.0, 1.0)],
            "XLA Modules": [("jit_traced(12)", 11.0, 3.0),
                            ("jit__sph_nms_batch_device(3)", 18.0, 1.0)],
        }},
        "spans": [
            ("bench.window", 10.0, 10.0),
            ("front.wait", 10.0, 1.0),           # gap [10, 11]
            ("control.admit", 14.0, 4.0),       # gap [14, 18] ...
            ("camera.frame", 14.5, 0.5),        # ... nested, shorter
            ("nms.suppress", 19.0, 1.0),        # gap [19, 20]
        ],
    }


def test_busy_idle_and_window():
    r = tracefile.reduce(_trace())
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(4.0)


def test_device_time_per_module():
    ops = dict(map(tuple, tracefile.reduce(_trace())["device_ops"]))
    assert ops == pytest.approx({"jit_traced": 3.0,
                                 "jit__sph_nms_batch_device": 1.0})


def test_idle_gaps_by_innermost_host_span():
    gaps = dict(map(tuple, tracefile.reduce(_trace())["idle_gaps"]))
    # the gap [14, 18] has its middle (16) in control.admit only
    assert gaps == pytest.approx({"front.wait": 1.0, "control.admit": 4.0,
                                  "nms.suppress": 1.0})


def test_two_devices_average():
    t = _trace()
    t["devices"]["/device:TPU:1"] = {"XLA Ops": [("x", 10.0, 10.0)]}
    assert tracefile.reduce(t)["busy_s"] == pytest.approx(7.0)


def test_no_device_events_reads_nothing():
    r = tracefile.reduce({"devices": {}, "spans": []})
    assert r["busy_s"] == 0.0 and r["device_ops"] == []


def test_union_merges_and_clips():
    ev = [("a", 0.0, 2.0), ("b", 1.0, 2.0), ("c", 5.0, 1.0)]
    assert tracefile.union(ev, 0.5, 5.5) == [(0.5, 3.0), (5.0, 5.5)]


def test_module_name_drops_the_compile_id():
    assert tracefile.module_name("jit_traced(123)") == "jit_traced"
    assert tracefile.module_name("jit_forward.7") == "jit_forward"
