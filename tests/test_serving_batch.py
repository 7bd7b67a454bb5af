"""Variant-batched inference in the pod serving loop.

Pins the PR-2 serving refactor:

  * shape buckets bound the dispatch shape space (pad/split/resolution);
  * the batched latency path (per-batch fixed + per-item marginal)
    reduces to the per-request term at b=1 and preserves the
    scheduler's utility ordering (pinned allocator plans);
  * a PodServer tick equals the inline per-request path detection-for-
    detection on the oracle backend, and issues exactly one batched
    forward per distinct variant;
  * the Jax backend's bucketed-padded batched forward matches its
    per-request path and compiles at most ``len(buckets)`` programs per
    variant under mixed-size ticks;
  * ``decode``'s validity mask silences padded batch rows;
  * the CubeMap baseline through the queue machinery is unchanged.
"""

import dataclasses
import math

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import sroi as sroi_mod
from repro.core.omnisense import OmniSenseLoop
from repro.core.sphere import pad_detection_rows, sph_nms_batch
from repro.data.synthetic import make_video
from repro.models import detector as det_mod
from repro.serving import baselines, profiles
from repro.serving.batching import (DEFAULT_BATCH_BUCKETS, ShapeBuckets,
                                    VariantQueues)
from repro.serving.network import NetworkModel
from repro.serving.scheduler import (JaxDetectorBackend, OmniSenseLatencyModel,
                                     OracleBackend)
from repro.serving.server import PodServer


class TestShapeBuckets:
    def test_pad_batch_smallest_bucket(self):
        b = ShapeBuckets((1, 2, 4, 8))
        assert [b.pad_batch(i) for i in range(1, 9)] == [1, 2, 4, 4, 8, 8, 8, 8]
        with pytest.raises(ValueError):
            b.pad_batch(9)
        with pytest.raises(ValueError):
            b.pad_batch(0)

    def test_split_chunks_to_buckets(self):
        b = ShapeBuckets((1, 2, 4))
        assert b.split(11) == [4, 4, 3]
        assert b.split(4) == [4]
        assert b.split(1) == [1]
        assert b.split(0) == []

    def test_resolution_bucket_membership(self):
        b = ShapeBuckets((1, 2), resolutions=(64, 96))
        assert b.bucket_resolution(64) == 64
        with pytest.raises(ValueError):
            b.bucket_resolution(80)
        assert ShapeBuckets((1,)).bucket_resolution(80) == 80  # unrestricted

    def test_for_max_batch_tops_out_exactly(self):
        assert ShapeBuckets.for_max_batch(8).batch_sizes == (1, 2, 4, 8)
        assert ShapeBuckets.for_max_batch(4).batch_sizes == (1, 2, 4)
        assert ShapeBuckets.for_max_batch(6).batch_sizes == (1, 2, 4, 6)
        assert ShapeBuckets.for_max_batch(1).batch_sizes == (1,)

    def test_invalid_buckets_rejected(self):
        with pytest.raises(ValueError):
            ShapeBuckets((2, 1))
        with pytest.raises(ValueError):
            ShapeBuckets(())
        with pytest.raises(ValueError):
            ShapeBuckets((0, 2))

    @given(st.integers(1, 500), st.integers(0, 200))
    @settings(max_examples=50, deadline=None)
    def test_split_pad_invariants_property(self, seed, count):
        self._check_split_pad(seed, count)

    def test_split_pad_invariants_fixed(self):
        for seed, count in ((0, 0), (1, 1), (2, 7), (3, 64), (4, 133)):
            self._check_split_pad(seed, count)

    @staticmethod
    def _check_split_pad(seed, count):
        """Chunks conserve the request count, never exceed the top
        bucket, and every chunk pads to a member bucket >= its size."""
        rng = np.random.default_rng(seed)
        sizes = tuple(sorted(rng.choice(
            np.arange(1, 33), size=int(rng.integers(1, 5)), replace=False)))
        b = ShapeBuckets(tuple(int(s) for s in sizes))
        chunks = b.split(count)
        assert sum(chunks) == count
        assert all(0 < c <= b.max_batch for c in chunks)
        for c in chunks:
            padded = b.pad_batch(c)
            assert padded in b.batch_sizes and padded >= c


class TestBatchedLatencyModel:
    def _lat(self):
        return OmniSenseLatencyModel(profiles.paper_profile(), NetworkModel())

    def test_b1_reduces_to_per_request(self):
        lat = self._lat()
        for v in profiles.make_ladder(seed=0):
            assert lat.batched_inference_delay(v, 1) == lat._inf(v)

    def test_sublinear_and_monotone(self):
        lat = self._lat()
        v = profiles.make_ladder(seed=0)[3]
        costs = [lat.batched_inference_delay(v, b) for b in (1, 2, 4, 8)]
        assert all(a < b for a, b in zip(costs, costs[1:]))  # more work
        # ... but each batch of b costs less than b separate forwards
        for b, c in zip((2, 4, 8), costs[1:]):
            assert c < b * costs[0]
        amort = [lat.amortized_inference_delay(v, b) for b in (1, 2, 4, 8)]
        assert all(a > b for a, b in zip(amort, amort[1:]))

    def test_variant_cost_ordering_preserved(self):
        """Batching rescales every variant by the same curve, so the
        allocator's cost ordering across variants cannot flip."""
        lat = self._lat()
        variants = profiles.make_ladder(seed=0)
        for b in (1, 2, 8):
            batched = [lat.batched_inference_delay(v, b) for v in variants]
            single = [lat._inf(v) for v in variants]
            assert np.argsort(batched).tolist() == np.argsort(single).tolist()

    def test_allocator_plans_pinned(self):
        """Regression pin: the per-stream allocator (which prices
        requests individually) must produce the same plans before and
        after the batched-cost path was added."""
        video = make_video(n_frames=16, n_objects=30, seed=7)
        variants = profiles.make_ladder(seed=0)
        lat = self._lat()
        backend = OracleBackend(video)
        loop = OmniSenseLoop(variants, lat, backend, budget_s=2.0)
        plans = []
        for f in range(8):
            backend.set_frame(f)
            r = loop.process_frame(None)
            plans.append(None if r.plan is None else r.plan.models)
        assert plans == [None, (5, 3, 3), (5, 3, 3), (5, 4), (5, 4),
                         (5, 4), (5, 3, 3), (5, 3, 3)]


def _oracle_pod(n_streams, seed0=40, budget=2.0, max_batch=4):
    variants = profiles.make_ladder(seed=0)
    loops, backends = [], []
    for s in range(n_streams):
        video = make_video(n_frames=16, n_objects=30, seed=seed0 + s)
        lat = OmniSenseLatencyModel(profiles.paper_profile(), NetworkModel())
        b = OracleBackend(video)
        backends.append(b)
        loops.append(OmniSenseLoop(variants, lat, b, budget_s=budget))
    return loops, backends


class TestPodServerBatchedTick:
    def test_batched_tick_matches_per_request_inline(self):
        """The tentpole equivalence: a PodServer tick — request
        emission, variant-queue drain into batched forwards, scatter,
        batched NMS — keeps exactly the detections the inline
        per-request path produces, stream by stream, frame by frame."""
        n_streams, n_frames = 4, 8
        inline, backends_a = _oracle_pod(n_streams)
        batched, backends_b = _oracle_pod(n_streams)
        server = PodServer(batched, backends_b, max_batch=4)
        saw = 0
        for f in range(n_frames):
            expect = []
            for loop, b in zip(inline, backends_a):
                b.set_frame(f)
                expect.append(loop.process_frame(None).detections)
            server.step(f)
            for s, loop in enumerate(batched):
                got = loop._history[-1]
                assert len(got) == len(expect[s]), (f, s)
                for da, db in zip(expect[s], got):
                    np.testing.assert_array_equal(da.box, db.box)
                    assert da.category == db.category
                    assert da.score == db.score
                saw += len(got)
        assert saw > 0

    def test_one_dispatch_per_variant_per_tick(self):
        """S streams choosing V distinct variants => exactly V batched
        forwards in the tick (queues fit one bucket each here)."""
        n_frames = 6
        inline, backends_a = _oracle_pod(3, seed0=60, max_batch=8)
        batched, backends_b = _oracle_pod(3, seed0=60, max_batch=8)
        server = PodServer(batched, backends_b, max_batch=8)
        for f in range(n_frames):
            expect_variants = set()
            for loop, b in zip(inline, backends_a):
                b.set_frame(f)
                res = loop.process_frame(None)
                if res.plan is not None:
                    expect_variants |= {m for m in res.plan.models if m > 0}
            before = server.stats.dispatches
            server.step(f)
            assert server.stats.dispatches - before == len(expect_variants), f

    def test_queue_machinery_respects_max_batch(self):
        loops, backends = _oracle_pod(6, seed0=80, max_batch=2)
        server = PodServer(loops, backends, max_batch=2)
        stats = server.run(range(6))
        assert stats.batch_sizes and max(stats.batch_sizes) <= 2
        assert stats.dispatches == len(stats.batch_sizes)

    def test_batched_cost_charged_not_per_request_sums(self):
        loops, backends = _oracle_pod(6, seed0=90, max_batch=8)
        server = PodServer(loops, backends, max_batch=8)
        stats = server.run(range(8))
        assert stats.dispatches > 0
        # some tick batched >1 requests, so the pod pays strictly less
        # than the per-request sum, but never less than amortization-free
        assert stats.sum_batched_inf_s < stats.sum_per_request_inf_s
        assert stats.batching_gain > 1.0
        mb = max(stats.batch_sizes)
        assert stats.batching_gain <= mb / (1 + (mb - 1) * 0.15) + 1e-9

    def test_mismatched_buckets_rejected(self):
        loops, backends = _oracle_pod(2)
        with pytest.raises(ValueError):
            PodServer(loops, backends, max_batch=8,
                      buckets=ShapeBuckets((1, 2, 4)))

    def test_backend_buckets_smaller_than_server_rejected(self):
        """A backend whose bucket ladder tops out below the server's
        would silently split drained chunks, so the priced tick
        schedule would diverge from the executed one."""
        loops, backends = _oracle_pod(2)
        for b in backends:
            b.buckets = ShapeBuckets((1, 2, 4))  # tops out below 8
        with pytest.raises(ValueError):
            PodServer(loops, backends, max_batch=8)

    def test_marginal_batch_cost_override_is_honored(self):
        """An explicit marginal_batch_cost must override the latency
        model's curve in every priced dispatch."""
        stats = {}
        for marginal in (None, 0.0):
            loops, backends = _oracle_pod(6, seed0=90, max_batch=8)
            server = PodServer(loops, backends, max_batch=8,
                               marginal_batch_cost=marginal)
            stats[marginal] = server.run(range(4))
        # identical schedules (same seeds) and per-request sums, but
        # marginal=0 prices every dispatch at the single-forward cost —
        # strictly cheaper than the model's 0.15 curve once any b > 1
        assert stats[0.0].batch_sizes == stats[None].batch_sizes
        assert max(stats[0.0].batch_sizes) > 1
        assert stats[0.0].sum_per_request_inf_s == pytest.approx(
            stats[None].sum_per_request_inf_s)
        assert stats[0.0].sum_batched_inf_s < stats[None].sum_batched_inf_s
        assert stats[0.0].batching_gain > stats[None].batching_gain


# ---------------------------------------------------------------------------
# Real Jax detector path: bucketed-padded batched forward
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def jax_backend():
    cfg = dataclasses.replace(det_mod.PAPER_LADDER[0], input_size=64,
                              n_classes=8)
    params = det_mod.init_params(jax.random.PRNGKey(0), cfg)
    return JaxDetectorBackend(
        [cfg], [params], conf=0.01, use_kernel=False, max_det=4,
        buckets=ShapeBuckets((1, 2, 4), resolutions=(64,)))


def _regions(rng, n):
    fov = (math.radians(60), math.radians(60))
    return [sroi_mod.SRoI(center=(float(rng.uniform(-2.5, 2.5)),
                                  float(rng.uniform(-0.9, 0.9))), fov=fov)
            for _ in range(n)]


class TestJaxBatchedBackend:
    def test_batched_matches_per_request(self, jax_backend):
        """Acceptance: batched-padded inference produces the same
        detections as the per-request path on the Jax backend (crop,
        forward, decode, back-project all shared; only the batch shape
        differs, so results agree to float tolerance)."""
        rng = np.random.default_rng(0)
        frame = rng.random((64, 128, 3)).astype(np.float32)
        variant = profiles.make_ladder(seed=0)[0]
        regions = _regions(rng, 3)
        per_request = [jax_backend.infer_sroi(frame, r, variant)
                       for r in regions]
        batched = jax_backend.infer_srois_batched(
            [(frame, r) for r in regions], variant)  # one chunk, padded to 4
        assert sum(len(d) for d in per_request) > 0
        assert len(batched) == len(per_request)
        for dets_a, dets_b in zip(per_request, batched):
            assert len(dets_a) == len(dets_b)
            for da, db in zip(dets_a, dets_b):
                assert da.category == db.category
                np.testing.assert_allclose(da.box, db.box,
                                           rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(da.score, db.score,
                                           rtol=1e-4, atol=1e-5)

    def test_mixed_shapes_compile_at_most_len_buckets(self, jax_backend):
        """A tick of mixed-size request groups triggers at most
        ``len(buckets)`` distinct jit compilations per variant — the
        shape-bucketing guarantee (trace_count increments only when
        jax.jit actually retraces)."""
        rng = np.random.default_rng(1)
        frame = rng.random((64, 128, 3)).astype(np.float32)
        variant = profiles.make_ladder(seed=0)[0]
        start = jax_backend.trace_count
        for count in (1, 2, 3, 1, 2):  # mixed-shape "ticks"
            jax_backend.infer_srois_batched(
                [(frame, r) for r in _regions(rng, count)], variant)
        n_buckets = len(jax_backend.buckets.batch_sizes)
        assert jax_backend.trace_count - start <= n_buckets
        assert len(jax_backend._jit_cache) <= n_buckets * len(jax_backend.cfgs)
        for idx, b_pad in jax_backend._jit_cache:
            assert b_pad in jax_backend.buckets.batch_sizes

    def test_batched_program_returns_the_heads_it_decoded(self, jax_backend):
        """The served program also returns its raw heads (what the chip
        smoke compares with a reference): they are the forward's heads,
        and the decoded rows are exactly their decode."""
        rng = np.random.default_rng(3)
        idx, b = 0, jax_backend.buckets.batch_sizes[-1]
        cfg = jax_backend.cfgs[idx]
        s = cfg.input_size
        imgs = rng.random((b, s, s, 3)).astype(np.float32)
        valid = np.arange(b) < b - 1
        *decoded, heads = jax_backend._batched_fn(idx, b)(
            jax_backend.params[idx], imgs, valid)
        want = det_mod.apply(jax_backend.params[idx], imgs, cfg)
        for h, w in zip(heads, want, strict=True):
            np.testing.assert_allclose(np.asarray(h), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)
        again = jax.jit(lambda o: det_mod.decode(
            o, cfg, jax_backend.conf, max_det=jax_backend.max_det,
            valid=valid))(heads)
        for d, a in zip(decoded, again, strict=True):
            np.testing.assert_array_equal(np.asarray(d), np.asarray(a))

    def test_decode_valid_mask_silences_padded_rows(self):
        cfg = dataclasses.replace(det_mod.PAPER_LADDER[0], input_size=64,
                                  n_classes=8)
        params = det_mod.init_params(jax.random.PRNGKey(1), cfg)
        rng = np.random.default_rng(2)
        imgs = rng.random((2, 64, 64, 3)).astype(np.float32)
        outs = det_mod.apply(params, imgs, cfg)
        valid = np.array([True, False])
        boxes, scores, classes = det_mod.decode(outs, cfg, 0.01, max_det=8,
                                                valid=valid)
        assert (np.asarray(scores)[1] == 0).all()  # padded row silenced
        b_ref, s_ref, c_ref = det_mod.decode(outs, cfg, 0.01, max_det=8)
        for r in (0,):  # valid rows decode exactly as without a mask
            np.testing.assert_array_equal(np.asarray(scores)[r],
                                          np.asarray(s_ref)[r])
            np.testing.assert_array_equal(np.asarray(boxes)[r],
                                          np.asarray(b_ref)[r])


@pytest.mark.slow
class TestPodServerJaxBackend:
    def test_pod_tick_on_real_detector_matches_inline(self):
        """End-to-end pod tick on the REAL detector path: streams share
        one JaxDetectorBackend, frames come from ``frame_source``, and
        the batched tick's post-NMS histories match per-stream inline
        processing to float tolerance."""
        rng = np.random.default_rng(5)
        n_streams, n_frames = 3, 2
        cfgs = [dataclasses.replace(det_mod.PAPER_LADDER[i], input_size=64,
                                    n_classes=8) for i in range(2)]
        params = [det_mod.init_params(jax.random.PRNGKey(i), c)
                  for i, c in enumerate(cfgs)]
        variants = profiles.make_ladder(n_categories=8, seed=0)[:2]
        frames = {(s, f): rng.random((64, 128, 3)).astype(np.float32)
                  for s in range(n_streams) for f in range(n_frames)}
        seeds = [[sroi_mod.Detection(
                      box=np.array([rng.uniform(-2, 2), rng.uniform(-0.8, 0.8),
                                    0.5, 0.5]), category=int(rng.integers(8)),
                      score=0.9) for _ in range(2)]
                 for _ in range(n_streams)]

        def build():
            backend = JaxDetectorBackend(
                cfgs, params, conf=0.01, use_kernel=False, max_det=4,
                buckets=ShapeBuckets((1, 2, 4, 8), resolutions=(64,)))
            lat = OmniSenseLatencyModel(profiles.paper_profile(),
                                        NetworkModel())
            loops = []
            for s in range(n_streams):
                loop = OmniSenseLoop(variants, lat, backend, budget_s=4.0,
                                     n_categories=8, explore_every=0)
                loop.seed_history(list(seeds[s]))
                loops.append(loop)
            return loops, backend

        inline_loops, _ = build()
        pod_loops, backend = build()
        server = PodServer(pod_loops, [backend] * n_streams, max_batch=8,
                           frame_source=lambda s, f: frames[(s, f)])
        saw = 0
        for f in range(n_frames):
            expect = []
            for s, loop in enumerate(inline_loops):
                expect.append(loop.process_frame(frames[(s, f)]).detections)
            server.step(f)
            for s, loop in enumerate(pod_loops):
                got = loop._history[-1]
                assert len(got) == len(expect[s]), (f, s)
                for da, db in zip(expect[s], got):
                    assert da.category == db.category
                    np.testing.assert_allclose(da.box, db.box,
                                               rtol=1e-4, atol=1e-4)
                saw += len(got)
        assert saw > 0  # the real detector must actually emit detections


class TestCubeMapThroughQueues:
    def test_results_match_per_request_path(self):
        """CubeMap routed through the variant-queue machinery must keep
        the exact predictions and calibrated E2E of the per-face
        implementation it replaced."""
        video = make_video(n_frames=8, n_objects=30, seed=3)
        variants = profiles.make_ladder(seed=0)
        lat = OmniSenseLatencyModel(profiles.paper_profile(), NetworkModel())
        backend = OracleBackend(video)
        frames = range(0, 6)
        preds, e2e = baselines.run_cubemap_baseline(
            video, backend, lat, variants[3], frames)

        # the pre-refactor implementation, inlined
        lat_ref = OmniSenseLatencyModel(profiles.paper_profile(),
                                        NetworkModel())
        backend_ref = OracleBackend(make_video(n_frames=8, n_objects=30,
                                               seed=3))
        fov = (math.pi / 2, math.pi / 2)
        per_frame = []
        for f in frames:
            backend_ref.set_frame(f)
            dets = []
            for ct, cp in baselines.CUBE_CENTERS:
                region = sroi_mod.SRoI(center=(ct, cp), fov=fov)
                dets.extend(backend_ref.infer_sroi(None, region, variants[3]))
            per_frame.append((f, dets))
        expect = []
        rows = [(f, dets) for f, dets in per_frame if dets]
        boxes, scores, mask = pad_detection_rows([d for _, d in rows])
        keep = sph_nms_batch(boxes, scores, mask, iou_threshold=0.6)
        for r, (f, dets) in enumerate(rows):
            expect.extend((f, d) for d, k in zip(dets, keep[r]) if k)

        assert len(preds) == len(expect) and len(preds) > 0
        for (fa, da), (fb, db) in zip(preds, expect):
            assert fa == fb and da.category == db.category
            np.testing.assert_array_equal(da.box, db.box)

    def test_face_batching_cheaper_than_pipelined(self):
        video = make_video(n_frames=4, n_objects=20, seed=4)
        variants = profiles.make_ladder(seed=0)
        frames = range(0, 3)
        e2es = {}
        for fb in (1, 6):
            lat = OmniSenseLatencyModel(profiles.paper_profile(),
                                        NetworkModel())
            backend = OracleBackend(make_video(n_frames=4, n_objects=20,
                                               seed=4))
            preds, e2e = baselines.run_cubemap_baseline(
                video, backend, lat, variants[3], frames, face_batch=fb)
            e2es[fb] = e2e
        assert e2es[6] < e2es[1]


class TestNmsShapeBuckets:
    """PR-3 satellite (ROADMAP open item): per-tick batched-NMS rows
    pad to the ShapeBuckets N-ladder so the device path's (B, N)
    compile shapes are bounded, pinned by the sphere-level trace
    counter exactly like ``infer_srois_batched``'s."""

    def test_pad_nms_rows_snaps_to_ladder(self):
        b = ShapeBuckets((1, 2), nms_sizes=(8, 16, 32))
        assert [b.pad_nms_rows(n) for n in (0, 1, 8, 9, 16, 30)] == \
            [8, 8, 8, 16, 16, 32]
        # beyond the top rung: top-rung multiples, never an error
        assert b.pad_nms_rows(33) == 64 and b.pad_nms_rows(65) == 96

    def test_invalid_nms_buckets_rejected(self):
        with pytest.raises(ValueError):
            ShapeBuckets((1, 2), nms_sizes=(16, 8))
        with pytest.raises(ValueError):
            ShapeBuckets((1, 2), nms_sizes=())

    def _rows(self, rng, n_rows, max_det=12):
        rows = []
        for _ in range(n_rows):
            k = int(rng.integers(0, max_det))
            rows.append([sroi_mod.Detection(
                box=np.array([rng.uniform(-2, 2), rng.uniform(-0.8, 0.8),
                              rng.uniform(0.2, 0.6), rng.uniform(0.2, 0.6)]),
                category=0, score=float(rng.uniform(0.1, 1.0)))
                for _ in range(k)])
        return rows

    def test_bucketed_padding_keeps_identical_masks(self):
        """Masked padding (N to the ladder, B to the stream count) can
        never change which real detections survive."""
        rng = np.random.default_rng(0)
        buckets = ShapeBuckets((1, 2, 4), nms_sizes=(8, 16, 32))
        for trial in range(5):
            rows = self._rows(rng, n_rows=int(rng.integers(1, 6)))
            boxes_a, scores_a, mask_a = pad_detection_rows(rows)
            keep_a = sph_nms_batch(boxes_a, scores_a, mask_a,
                                   iou_threshold=0.6)
            boxes_b, scores_b, mask_b = pad_detection_rows(
                rows, pad_n=buckets.pad_nms_rows, total_rows=8)
            assert boxes_b.shape[0] == 8
            assert boxes_b.shape[1] in (8, 16, 32)
            keep_b = sph_nms_batch(boxes_b, scores_b, mask_b,
                                   iou_threshold=0.6)
            for r, dets in enumerate(rows):
                np.testing.assert_array_equal(keep_a[r, :len(dets)],
                                              keep_b[r, :len(dets)])
            assert not keep_b[len(rows):].any()  # padded rows keep nothing

    def test_device_path_traces_bounded_by_ladder(self):
        """Trace-counter pin: ladder-padded ticks retrace the jitted
        device NMS once per rung, not once per detection count."""
        from repro.core.sphere import nms_device_trace_count

        rng = np.random.default_rng(1)
        buckets = ShapeBuckets((1, 2, 4), nms_sizes=(8, 16))
        n_streams = 4
        start = nms_device_trace_count()
        for tick in range(6):
            rows = self._rows(rng, n_rows=int(rng.integers(1, n_streams + 1)))
            boxes, scores, mask = pad_detection_rows(
                rows, pad_n=buckets.pad_nms_rows, total_rows=n_streams)
            sph_nms_batch(boxes, scores, mask, iou_threshold=0.6,
                          backend="jit")
        assert nms_device_trace_count() - start <= len(buckets.nms_sizes)

    def test_pod_server_suppression_unchanged_by_bucketing(self):
        """The served histories with bucketed NMS padding equal the
        unpadded per-stream suppression (the pre-PR-3 behaviour)."""
        inline, backends_a = _oracle_pod(3, seed0=70)
        batched, backends_b = _oracle_pod(3, seed0=70)
        server = PodServer(batched, backends_b, max_batch=4)
        for f in range(6):
            for loop, b in zip(inline, backends_a):
                b.set_frame(f)
                loop.process_frame(None)
            server.step(f)
        for la, lb in zip(inline, batched):
            assert len(la._history[-1]) == len(lb._history[-1])
            for a, b in zip(la._history[-1], lb._history[-1]):
                np.testing.assert_array_equal(a.box, b.box)


class TestVariantQueuesUnit:
    class _CountingBackend:
        def __init__(self):
            self.calls = []

        def infer_srois_batched(self, items, variant):
            self.calls.append((variant.name, len(items)))
            return [[] for _ in items]

    def test_drain_order_and_chunking(self):
        from repro.core.omnisense import InferenceRequest
        from repro.serving.batching import QueuedRequest

        variants = profiles.make_ladder(seed=0)
        backend = self._CountingBackend()
        q = VariantQueues(ShapeBuckets((1, 2)))
        fov = (1.0, 1.0)
        for slot, v in enumerate([variants[1]] * 3 + [variants[0]]):
            q.put(QueuedRequest(
                request=InferenceRequest(
                    region=sroi_mod.SRoI(center=(0.0, 0.0), fov=fov),
                    variant=v, slot=slot, special=False),
                owner=None, backend=backend))
        results, dispatches = q.drain()
        assert len(results) == 4 and len(q) == 0
        # sorted variant-name drain order; chunks of <= max bucket
        assert backend.calls == [(variants[1].name, 2), (variants[1].name, 1),
                                 (variants[0].name, 1)]
        assert [(d["variant"], d["b"], d["padded"]) for d in dispatches] == [
            (variants[1].name, 2, 2), (variants[1].name, 1, 1),
            (variants[0].name, 1, 1)]

    def test_default_buckets_exported(self):
        assert DEFAULT_BATCH_BUCKETS == (1, 2, 4, 8)

    def test_real_backend_groups_priced_individually(self):
        """A same-variant chunk spanning DISTINCT real backends executes
        one forward per backend group — pricing must follow the group
        sizes, never the chunk, or stats would report batching that
        never ran.  Per-stream oracle instances (``semantic_batch``)
        keep chunk-level pricing: they simulate one shared accelerator."""
        from repro.core.omnisense import InferenceRequest
        from repro.serving.batching import QueuedRequest

        class _RealBackend:  # no semantic_batch attribute
            def infer_srois_batched(self, items, variant):
                return [[] for _ in items]

        variants = profiles.make_ladder(seed=0)
        v = variants[1]
        lat = OmniSenseLatencyModel(profiles.paper_profile(), NetworkModel())
        b1, b2 = _RealBackend(), _RealBackend()
        q = VariantQueues(ShapeBuckets((1, 2, 4)))
        for slot, be in enumerate([b1, b1, b1, b2]):
            q.put(QueuedRequest(
                request=InferenceRequest(
                    region=sroi_mod.SRoI(center=(0.0, 0.0), fov=(1.0, 1.0)),
                    variant=v, slot=slot, special=False),
                owner=None, backend=be, latency_model=lat))
        _, dispatches = q.drain()
        assert len(dispatches) == 1
        d = dispatches[0]
        assert d["semantic"] is False
        assert sorted(d["group_sizes"]) == [1, 3] and d["forwards"] == 2

        loops, backends = _oracle_pod(1)
        server = PodServer(loops, backends)
        batched, per_req = server._dispatch_cost(d)
        assert batched == pytest.approx(lat.batched_inference_delay(v, 3)
                                        + lat.batched_inference_delay(v, 1))
        assert per_req == pytest.approx(4 * lat._inf(v))

        # oracle chunks (semantic simulation) stay chunk-priced
        o_loops, o_backends = _oracle_pod(2)
        q2 = VariantQueues(ShapeBuckets((1, 2, 4)))
        for slot, be in enumerate(o_backends):
            q2.put(QueuedRequest(
                request=InferenceRequest(
                    region=sroi_mod.SRoI(center=(0.0, 0.0), fov=(1.0, 1.0)),
                    variant=v, slot=slot, special=False),
                owner=None, backend=be, latency_model=lat))
        _, o_dispatches = q2.drain()
        assert o_dispatches[0]["semantic"] is True
        o_batched, _ = server._dispatch_cost(o_dispatches[0])
        assert o_batched == pytest.approx(lat.batched_inference_delay(v, 2))
