"""A cell, configuration, traffic mix and metric are found by name from
files, so a later change adds one by adding files and entries only."""

import json
import re
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import run as run_mod
from bench.metrics import _common

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_has_the_contract_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert "workloads" not in moved or cell in moved["workloads"]


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_loads_from_its_files(cell):
    c = run_mod.load_cell(cell)
    assert c["config"]["name"] == c["workload"]["config"]
    assert c["mix"]["streams"] == c["config"]["streams"]
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(run_mod.reader(m["name"]))


def test_config_files_name_their_cut():
    for entry in BM["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert entry["reduced"] == cfg["reduced"]
        assert set(cfg["limits"]) == {"proj_err", "heads_err",
                                      "decode_err", "backproj_err",
                                      "nms_flips"}


def test_a_new_cell_and_metric_are_files_and_entries(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(run_mod.BENCH / d, bench / d)
    (bench / "traffic" / "burst-det2.json").write_text(json.dumps(
        {"streams": 8, "fps": 1.0, "jitter": 0.2,
         "rate_trace": [[10.0, 3.0]]}))
    (bench / "metrics" / "late_frames.py").write_text(
        "def read(run):\n    return sum(x > 1e3 for x in run.latencies_ms)\n")
    bm = json.loads(json.dumps(BM))
    bm["workloads"].append({"name": "det2-burst", "config": "det2",
                            "traffic": "burst-det2", "chips": 1,
                            "why": "bursts"})
    bm["per_layer"].append({"name": "late_frames", "unit": "frames",
                            "better": "lower", "source": "host_clock",
                            "layer": "front door", "moves": "frames_per_s",
                            "workloads": ["det2-burst"]})
    cell = run_mod.load_cell("det2-burst", bm, bench)
    assert cell["mix"]["rate_trace"] == [[10.0, 3.0]]
    assert [m["name"] for m in cell["per_layer"]] == ["late_frames"]
    read = run_mod.reader("late_frames", bench)
    assert read(run_mod.Run(latencies_ms=[5.0, 2e3, np.inf])) == 2
    # a suffixed name reads with its stem's reader
    assert run_mod.reader("late_frames.overload", bench)(
        run_mod.Run(latencies_ms=[2e3])) == 1
    with pytest.raises(FileNotFoundError):
        run_mod.reader("no_such_metric", bench)


def _run(**kw):
    base = dict(seconds=10.0, frames=20, t0=0.0, t_end=10.0, trace_t0=0.0,
                spans=[],
                dispatches=[], forwards=[], flops=[1e9, 2e9], trace=None,
                peak={"bf16_flops": 1e12}, setup_s=42.0, arrivals=[],
                latencies_ms=[])
    base.update(kw)
    return run_mod.Run(**base)


def test_readers_on_a_known_run():
    spans = [("control.admit", 0.0, 1.0, 1), ("camera.frame", 0.2, 0.4, 2),
             ("drain.dispatch", 1.0, 3.0, 1), ("nms.suppress", 3.0, 3.5, 1),
             ("control.ingest", 3.0, 4.0, 1)]
    spans[3] = ("nms.suppress", 3.0, 3.5, 2)
    run = _run(spans=spans,
               dispatches=[(1.0, "v", 3, 8), (2.0, "v", 8, 8)],
               forwards=[(1.0, 0, 3), (2.0, 1, 8), (11.0, 1, 8)],
               trace={"busy_s": 1.5, "window_s": 10.0})
    r = lambda name: run_mod.reader(name)(run)  # noqa: E731
    assert r("frames_per_s") == pytest.approx(2.0)  # 20 frames in 10 s
    assert r("setup_s") == 42.0
    # admit 1.0 - camera 0.2, ingest 1.0 - nms 0.5: 1.3 s over 20 frames
    assert r("control_ms_per_frame") == pytest.approx(65.0)
    assert r("drain_ms_per_frame") == pytest.approx(100.0)
    assert r("nms_ms_per_frame") == pytest.approx(25.0)
    assert r("batch_fill") == pytest.approx(100 * 11 / 16)
    assert r("device_idle_share") == pytest.approx(85.0)
    # 3 rows x 1 GF + 8 rows x 2 GF inside the window, over 10 s x 1 TF
    assert r("mfu") == pytest.approx(100 * 19e9 / 1e13)


def test_readers_find_nothing_without_a_trace():
    run = _run()
    for name in ("device_idle_share", "mfu", "control_ms_per_frame",
                 "batch_fill"):
        assert run_mod.reader(name)(run) is None
    assert run_mod.reader("frames_per_s")(_run(frames=0)) is None


def test_self_times_leave_out_nested_spans():
    spans = [("control.admit", 0.0, 1.0, 1), ("camera.frame", 0.2, 0.4, 2),
             ("drain.dispatch", 0.5, 0.9, 2), ("control.admit", 2.0, 2.5, 1)]
    t = _common.self_times(spans)
    assert t["control.admit"] == pytest.approx(0.4 + 0.5)
    assert t["camera.frame"] == pytest.approx(0.2)
    assert _common.span_total(spans, "control.") == pytest.approx(1.5)


@pytest.mark.parametrize("entry", BM["configs"], ids=lambda e: e["name"])
def test_detectors_run_at_their_published_stage_widths(entry):
    """Each rung's channels at strides 8, 16 and 32 are the published
    ones the file states; only the block count may be cut."""
    from bench import reference

    cfg = json.loads((ROOT / entry["file"]).read_text())
    for d in cfg["detectors"]:
        pub = cfg["published"][d["name"]]
        widths = [reference._width(d, 2 ** (i + 1))
                  for i in range(len(reference.strides(d)))]
        assert widths == pub["stage_widths"]
        assert reference._depth(d) <= min(pub["blocks_per_stage"])


class _SlowServer:
    """A stand-in for the pod: each call takes ``round_s`` seconds."""

    def __init__(self, round_s):
        self.round_s, self.calls = round_s, []

    def serve_open_batch(self, batch):
        import time

        self.calls.append(len(batch))
        time.sleep(self.round_s)


def test_the_driver_stops_handing_over_at_the_close():
    from bench import window
    from bench.schedule import schedule

    due = schedule({"streams": 2, "fps": 20.0, "jitter": 0.2}, 0.5, 3)
    server = _SlowServer(0.2)
    began = []
    wr = window.drive(server, due, 0.5, frame_base=0, t_base=0.0,
                      at=(0.1, lambda: began.append(len(server.calls))))
    # the call in flight at the close finishes, and none follows it
    assert wr.t_end - wr.t0 >= 0.5
    assert len(server.calls) in (2, 3)
    assert sum(server.calls) == len(wr.handover) < len(due)
    assert max(wr.handover.values()) - wr.t0 < 0.5
    # the trace begins once, before the first handover past 0.1 s
    assert began == [1]


def test_det2_pod_is_the_pod_build_jax_pod_builds(monkeypatch):
    """At 64/96 px and a 64x128 ERP (the sizes are patched on both sides,
    as tests/test_serve_jax.py does), the benchmark's det2 pod has the
    builder's weights bit for bit for the same detectors, ladder, loops,
    buckets, policy and frames.  Two things differ on purpose: the
    detectors run at their published stage widths (the builder's are
    narrower), and the crop cache is off (``crop_cache_size`` 0),
    because it can serve another frame's crop (PERF.md, Open
    questions)."""
    import jax

    from bench import pod as pod_mod
    from repro.launch import serve
    from repro.serving.batching import ShapeBuckets
    from repro.serving.runtime import make_policy

    config = run_mod.load_cell("det2-overload")["config"]
    config = dict(config, erp_hw=[64, 128], detectors=[
        dict(d, input_size=s) for d, s in zip(config["detectors"], (64, 96))])
    cfgs = pod_mod.detector_configs(config)
    assert [c.name for c in cfgs] == [c.name for c in serve.JAX_POD_DETECTORS]
    assert [c.width(8) for c in cfgs] != \
        [c.width(8) for c in serve.JAX_POD_DETECTORS]
    monkeypatch.setattr(serve, "JAX_POD_DETECTORS", tuple(cfgs))
    monkeypatch.setattr(serve, "JAX_POD_ERP_HW", tuple(config["erp_hw"]))
    buckets = ShapeBuckets(tuple(config["batch_sizes"]), resolutions=(64, 96),
                           nms_sizes=tuple(config["nms_sizes"]))
    want_server, want = serve.build_jax_pod(
        config["streams"], config["frame_pool"], buckets=buckets,
        policy=make_policy("sync"))
    pool = pod_mod.render_pool(config, pod_mod.make_videos(config))
    got = pod_mod.build_pod(config, pod_mod.init_weights(config),
                            pod_mod.PooledFrames(pool))

    b, w = got.backend, want
    assert b.cfgs == w.cfgs
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree.leaves(b.params), jax.tree.leaves(w.params)))
    assert jax.tree.structure(b.params) == jax.tree.structure(w.params)
    for attr in ("conf", "use_kernel", "max_det", "fused", "buckets"):
        assert getattr(b, attr) == getattr(w, attr), attr
    assert (b.crop_cache_size, w.crop_cache_size) == (0, 256)
    for mine, theirs in zip(got.loops, want_server.loops):
        assert [v.name for v in mine.variants] == \
            [v.name for v in theirs.variants]
        for attr in ("budget_s", "explore_costs", "nms_threshold",
                     "explore_every", "delta", "gamma", "f"):
            assert getattr(mine, attr) == getattr(theirs, attr), attr
        assert mine.latency_model.network.bandwidth_mbps == \
            theirs.latency_model.network.bandwidth_mbps
    s, t = got.server, want_server
    assert (s.max_batch, s.buckets, s.policy.describe(), s.placement) == \
        (t.max_batch, t.buckets, t.policy.describe(), t.placement)
    assert len(s.loops) == len(t.loops) == config["streams"]
    for stream in range(config["streams"]):
        for frame in range(config["frame_pool"]):
            assert np.array_equal(got.frames(stream, frame),
                                  t.frame_source(stream, frame))
