"""Spans and counters on the telemetry hook.

  * on the base sink (and the event sinks built on it) ``span`` and
    ``count`` do nothing: ``span`` returns one shared null context;
  * ``SpanSink`` records nested spans with their parent's index, and
    counters, only while ``spans_on``;
  * on a small CPU detector pod the backend's spans nest under the
    server's ``drain.dispatch``, take no more time than it, and the
    uploaded bytes are the staged rows' and discovery's frames;
  * an event log recorded through a span-recording sink is the same,
    byte for byte, as one recorded without it;
  * each batched forward is a program named for its (variant, bucket);
  * back-projection and the pull are one span each per chunk, and
    ``backproject_rows`` counts the chunks' padded rows.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sroi import SRoI
from repro.launch import serve
from repro.models import detector as det_mod
from repro.serving import profiles
from repro.serving.batching import ShapeBuckets
from repro.serving.fleet import _PodSink
from repro.serving.replay import CorpusSpec, record
from repro.serving.telemetry import (JsonlSink, MemorySink, SpanSink,
                                     TelemetrySink)

DRAIN_STEPS = ("drain.stage", "drain.project", "drain.forward",
               "drain.fetch", "drain.backproject")


class SpanJsonlSink(SpanSink, JsonlSink):
    pass


@pytest.fixture
def tiny_pod(monkeypatch, tmp_path):
    monkeypatch.setattr(serve, "JAX_POD_DETECTORS", tuple(
        dataclasses.replace(c, input_size=s)
        for c, s in zip(det_mod.PAPER_LADDER[:2], (64, 96))))
    monkeypatch.setattr(serve, "JAX_POD_ERP_HW", (64, 128))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_base_sinks_span_and_count_do_nothing(tmp_path):
    sinks = [TelemetrySink(), MemorySink(), JsonlSink(tmp_path / "a.jsonl")]
    null = sinks[0].span("drain.stage")
    for sink in sinks:
        assert not sink.spans_on
        assert sink.span("drain.fetch", variant=1, b=3) is null
        with sink.span("drain.dispatch"):
            sink.count("upload_bytes", 10)
        assert not hasattr(sink, "spans") and not hasattr(sink, "counters")
        sink.close()


def test_span_sink_records_nesting_attrs_and_counters():
    sink = SpanSink()
    with sink.span("drain.dispatch"):
        with sink.span("drain.stage", variant=0, b=3, padded=4):
            sink.count("upload_bytes", 5)
            sink.count("upload_bytes", 7)
        with sink.span("drain.fetch"):
            pass
    with sink.span("nms.suppress"):
        pass
    names = [s[0] for s in sink.spans]
    assert names == ["drain.dispatch", "drain.stage", "drain.fetch",
                     "nms.suppress"]
    assert [s[3] for s in sink.spans] == [None, 0, 0, None]
    assert sink.spans[1][4] == {"variant": 0, "b": 3, "padded": 4}
    for name, t0, t1, _, _ in sink.spans:
        assert 0 < t0 <= t1, name
    outer, stage, fetch = sink.spans[:3]
    assert outer[1] <= stage[1] and fetch[2] <= outer[2]
    assert sink.counters == {"upload_bytes": 12}

    sink.clear_spans()
    assert sink.spans == [] and sink.counters == {}


def test_span_sink_records_nothing_while_off():
    sink = SpanSink()
    sink.spans_on = False
    assert sink.span("drain.stage") is TelemetrySink().span("x")
    with sink.span("drain.stage"):
        sink.count("staged_rows", 8)
    assert sink.spans == [] and sink.counters == {}


def test_a_fleet_pod_sink_forwards_spans_and_counts():
    base = SpanSink()
    pod = _PodSink(base, 3)
    with pod.span("control.admit", b=1):
        pod.count("staged_rows", 2)
    assert [s[0] for s in base.spans] == ["control.admit"]
    assert base.counters == {"staged_rows": 2}


@pytest.mark.parametrize("spec", [
    CorpusSpec(mode="closed", n_streams=3, frames=6, policy="async",
               devices=4, budget_s=3.0),
    CorpusSpec(mode="open", n_streams=3, frames=4, budget_s=0.9, devices=4,
               admission="slo", slo_s=2.0, fps=0.8, jitter=0.2,
               horizon_s=8.0, churn=((2.0, 1, False), (5.0, 1, True)))],
    ids=["closed", "open"])
def test_event_log_is_byte_identical_with_spans_recording(tmp_path, spec):
    plain, spanned = tmp_path / "plain.jsonl", tmp_path / "spanned.jsonl"
    with JsonlSink(plain) as sink:
        record(spec, sink)
    with SpanJsonlSink(spanned) as sink:
        record(spec, sink)
    assert plain.read_bytes() == spanned.read_bytes()
    names = {s[0] for s in sink.spans}
    assert {"control.plan_drain", "drain.dispatch", "control.ingest",
            "nms.suppress"} <= names
    assert ("control.admit" in names) == (spec.mode == "open")


def test_pod_spans_nest_under_the_drain_and_count_uploads(tiny_pod):
    sink = SpanSink()
    server, backend = serve.build_jax_pod(2, 4, telemetry=sink)
    assert backend.telemetry is sink
    server.run(range(3))

    spans = sink.spans
    names = [s[0] for s in spans]
    assert set(DRAIN_STEPS) <= set(names)
    assert all(t1 >= t0 > 0 for _, t0, t1, _, _ in spans)
    children: dict[int, float] = {}
    for name, t0, t1, parent, _ in spans:
        if name in DRAIN_STEPS:
            assert parent is not None and spans[parent][0] == "drain.dispatch"
            children[parent] = children.get(parent, 0) + (t1 - t0)
    for i, total in children.items():
        assert total <= spans[i][2] - spans[i][1]
    for name, _, _, parent, _ in spans:
        if name == "drain.discovery":
            assert spans[parent][0] == "control.ingest"

    projected = [a for name, _, _, _, a in spans if name == "drain.project"]
    staged = sink.counters["staged_rows"]
    assert staged == sum(a["padded"] for a in projected)
    assert staged >= sum(a["b"] for a in projected) > 0
    erp_bytes = server.frame_source(0, 0).nbytes
    discovery = names.count("drain.discovery")
    assert sink.counters["upload_bytes"] == (staged + discovery) * erp_bytes


def test_forward_program_is_named_per_variant_and_bucket(tiny_pod):
    _, backend = serve.build_jax_pod(1, 1)
    for idx, b_pad in ((0, 1), (0, 4), (1, 4)):
        cfg = backend.cfgs[idx]
        imgs = jnp.zeros((b_pad, cfg.input_size, cfg.input_size, 3))
        lowered = backend._batched_fn(idx, b_pad).lower(
            backend.params[idx], imgs, jnp.arange(b_pad) < 1)
        head = lowered.as_text().splitlines()[0]
        assert f'@"jit_forward_{cfg.name}_b{b_pad}"' in head, head
    assert backend.trace_count == 3


@pytest.mark.parametrize("n", [3, 7])
def test_backproject_and_fetch_are_one_span_per_chunk(tiny_pod, n):
    """A dispatch of n crops records one ``drain.backproject`` and one
    ``drain.fetch`` span per chunk, not per row, and counts every row
    through the back-projection program, padding rows included."""
    _, backend = serve.build_jax_pod(
        1, 1, buckets=ShapeBuckets((1, 2, 4), resolutions=(64, 96)))
    backend.telemetry = sink = SpanSink()
    frame = np.random.default_rng(0).random((64, 128, 3), np.float32)
    items = [(frame, SRoI(center=(0.3 * k - 1.0, 0.1), fov=(0.9, 0.7)))
             for k in range(n)]
    variant = profiles.make_ladder()[0]
    out = backend.launch_srois_batched(items, variant)()
    assert len(out) == n

    chunks = backend.buckets.split(n)
    padded = [backend.buckets.pad_batch(b) for b in chunks]
    attrs = {name: [a for s, _, _, _, a in sink.spans if s == name]
             for name in ("drain.backproject", "drain.fetch")}
    assert attrs["drain.backproject"] == [
        {"b": b, "padded": p} for b, p in zip(chunks, padded)]
    assert attrs["drain.fetch"] == [{"b": b} for b in chunks]
    assert sink.counters["backproject_rows"] == sum(padded)
