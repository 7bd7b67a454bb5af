"""Correctness envelope of the device-resident fused tick (PR 9).

The fused path replaces per-crop projection dispatches and per-detection
back-projection dispatches with batched device programs, plus two
cross-tick reuse levers and a reduced-precision IoU variant.  Each lever
has an exactness (or bounded-error) contract pinned here:

  * batched gnomonic projection: rows are bit-identical across batch
    sizes, and the fused backend's detections are bit-identical to the
    staged per-crop path's (f32 mode);
  * crop cache: a sub-pixel region drift reuses the anchor's PI *and
    geometry*, so the drifted tick's detections are bit-identical to
    re-serving the anchor;
  * incremental NMS: recomputing only churned rows equals a full
    recompute exactly (row independence);
  * batched back-projection: one jitted ``pi_box_to_sphbb`` program
    and one pull per chunk, within a stated tolerance of a float64
    back-projection, the same at any batch size, through a cache hit's
    anchor geometry;
  * bf16 SphIoU: keep-mask flips stay under the measured bound and only
    ever touch rows with an IoU near the 0.6 threshold.

Property tests follow the repo convention: a hypothesis ``@given`` form
plus a fixed-seed twin that runs without hypothesis installed.
"""

from __future__ import annotations

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _hypothesis_compat import given, settings, st  # noqa: E402

from repro.core import sphere  # noqa: E402
from repro.core import sroi as sroi_mod  # noqa: E402
from repro.core.sphere import IncrementalNms, pad_detection_rows  # noqa: E402
from repro.kernels.gnomonic.ops import project_srois_batched  # noqa: E402
from repro.models import detector as det_mod  # noqa: E402
from repro.serving import profiles  # noqa: E402
from repro.serving.batching import ShapeBuckets  # noqa: E402
from repro.serving.scheduler import JaxDetectorBackend  # noqa: E402

THR = 0.6
FOV = (math.radians(60), math.radians(60))


def _random_boxes(rng, n):
    return np.stack([rng.uniform(-3, 3, n), rng.uniform(-1.2, 1.2, n),
                     rng.uniform(0.3, 1.2, n), rng.uniform(0.3, 1.2, n)], -1)


def _dets_equal(a, b) -> bool:
    """Bitwise equality of two per-item detection-list sequences."""
    if len(a) != len(b):
        return False
    for row_a, row_b in zip(a, b):
        if len(row_a) != len(row_b):
            return False
        for da, db in zip(row_a, row_b):
            if (da.category != db.category or da.score != db.score
                    or not np.array_equal(np.asarray(da.box),
                                          np.asarray(db.box))):
                return False
    return True


@pytest.fixture(scope="module")
def detector():
    cfg = dataclasses.replace(det_mod.PAPER_LADDER[0], input_size=64,
                              n_classes=8)
    params = det_mod.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _backend(detector, **kw):
    cfg, params = detector
    kw.setdefault("buckets", ShapeBuckets((1, 2, 4, 8), resolutions=(64,)))
    return JaxDetectorBackend([cfg], [params], conf=0.01, use_kernel=False,
                              max_det=4, **kw)


def _regions(rng, n, fov=FOV):
    return [sroi_mod.SRoI(center=(float(rng.uniform(-2.5, 2.5)),
                                  float(rng.uniform(-0.9, 0.9))), fov=fov)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# Batched projection + fused-vs-staged bit-identity
# ---------------------------------------------------------------------------


class TestFusedProjection:
    def test_rows_bit_identical_across_batch_sizes(self):
        """The batched projector at B=8 produces the exact rows the
        same program produces one crop at a time — the invariant that
        lets cached (anchor-batch) PIs mix freely with fresh ones."""
        rng = np.random.default_rng(0)
        frame = rng.random((64, 128, 3)).astype(np.float32)
        centers = np.stack([rng.uniform(-2.5, 2.5, 8),
                            rng.uniform(-0.9, 0.9, 8)], -1)
        fovs = np.full((8, 2), FOV[0])
        full = np.asarray(project_srois_batched(
            [frame] * 8, centers, fovs, (32, 32)))
        ones = np.stack([np.asarray(project_srois_batched(
            [frame], centers[i:i + 1], fovs[i:i + 1], (32, 32)))[0]
            for i in range(8)])
        assert np.array_equal(full, ones)

    def test_fused_backend_matches_staged_bitwise(self, detector):
        """f32 acceptance: the fused tick (batched projection + crop
        cache + vectorised back-projection) produces bit-identical
        detections to the staged per-crop path at B=8."""
        rng = np.random.default_rng(1)
        frame = rng.random((64, 128, 3)).astype(np.float32)
        variant = profiles.make_ladder(seed=0)[0]
        items = [(frame, r) for r in _regions(rng, 8)]
        fused = _backend(detector, fused=True)
        staged = _backend(detector, fused=False)
        out_fused = fused.infer_srois_batched(items, variant)
        out_staged = staged.infer_srois_batched(items, variant)
        assert sum(len(d) for d in out_fused) > 0
        assert _dets_equal(out_fused, out_staged)
        assert fused.crop_cache_misses == 8  # first tick: all cold


# ---------------------------------------------------------------------------
# Crop-cache reuse under sub-pixel drift
# ---------------------------------------------------------------------------


class TestCropCache:
    @given(st.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def test_subpixel_drift_reuses_bit_identical_property(self, seed,
                                                          detector):
        self._check_drift(seed, detector)

    def test_subpixel_drift_reuses_bit_identical_fixed(self, detector):
        for seed in (0, 1, 2):
            self._check_drift(seed, detector)

    @staticmethod
    def _check_drift(seed, detector):
        """A tick whose regions drifted less than half the pixel pitch
        hits the crop cache for every crop, and its detections are
        bit-identical to re-serving the anchor regions (the cache
        returns the anchor's PI and back-projects through the anchor's
        geometry)."""
        rng = np.random.default_rng(seed)
        frame = rng.random((64, 128, 3)).astype(np.float32)
        variant = profiles.make_ladder(seed=0)[0]
        size = 64
        px, py = FOV[0] / size, FOV[1] / size
        # anchors on pitch-quantisation bucket centres so any drift
        # under pitch/2 provably lands in the anchor's bucket
        anchors = [sroi_mod.SRoI(
            center=(round(float(rng.uniform(-2.5, 2.5)) / px) * px,
                    round(float(rng.uniform(-0.9, 0.9)) / py) * py),
            fov=FOV) for _ in range(4)]
        drifted = [sroi_mod.SRoI(
            center=(r.center[0] + float(rng.uniform(-0.45, 0.45)) * px,
                    r.center[1] + float(rng.uniform(-0.45, 0.45)) * py),
            fov=FOV) for r in anchors]
        backend = _backend(detector, fused=True)
        out_anchor = backend.infer_srois_batched(
            [(frame, r) for r in anchors], variant)
        hits0 = backend.crop_cache_hits
        out_drift = backend.infer_srois_batched(
            [(frame, r) for r in drifted], variant)
        assert backend.crop_cache_hits - hits0 == len(anchors)
        assert _dets_equal(out_anchor, out_drift)

    def test_different_frame_never_reuses(self, detector):
        """Same geometry on a DIFFERENT frame must miss: the content
        guard keeps id() reuse from aliasing across frames."""
        rng = np.random.default_rng(3)
        variant = profiles.make_ladder(seed=0)[0]
        regions = _regions(rng, 2)
        backend = _backend(detector, fused=True)
        frame_a = rng.random((64, 128, 3)).astype(np.float32)
        frame_b = rng.random((64, 128, 3)).astype(np.float32)
        backend.infer_srois_batched([(frame_a, r) for r in regions], variant)
        hits0 = backend.crop_cache_hits
        backend.infer_srois_batched([(frame_b, r) for r in regions], variant)
        assert backend.crop_cache_hits == hits0

    def test_cache_disabled_when_staged(self, detector):
        backend = _backend(detector, fused=False)
        assert backend.crop_cache_size == 0


# ---------------------------------------------------------------------------
# Incremental cross-tick NMS == full recompute
# ---------------------------------------------------------------------------


class _Det:
    def __init__(self, box, score):
        self.box = box
        self.score = score


def _random_rows(rng, b, base=None, churn=1.0):
    rows = []
    for r in range(b):
        if base is not None and rng.random() > churn:
            rows.append(base[r])
            continue
        n = int(rng.integers(0, 12))
        boxes = _random_boxes(rng, n)
        rows.append([_Det(boxes[i], float(rng.uniform(0.1, 1)))
                     for i in range(n)])
    return rows


class TestIncrementalNms:
    @given(st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_equals_full_recompute_property(self, seed):
        self._check_churn(seed)

    def test_equals_full_recompute_fixed(self):
        for seed in (0, 1, 2, 3, 4):
            self._check_churn(seed)

    @staticmethod
    def _check_churn(seed):
        """Across ticks that churn a random subset of rows (and change
        the padded N), the incremental keep-mask equals a from-scratch
        ``sph_nms_batch`` exactly, and unchurned rows hit the cache."""
        rng = np.random.default_rng(seed)
        b = int(rng.integers(2, 8))
        inc = IncrementalNms(THR, backend="host")
        keys = list(range(b))
        rows = None
        for _ in range(4):
            rows = _random_rows(rng, b, rows,
                                churn=float(rng.uniform(0.0, 0.7)))
            boxes, scores, mask = pad_detection_rows(rows)
            if not boxes.size:
                continue
            keep_inc = inc.suppress(keys, boxes, scores, mask)
            keep_full = sphere.sph_nms_batch(boxes, scores, mask,
                                             iou_threshold=THR,
                                             backend="host")
            assert np.array_equal(keep_inc, keep_full)
        assert inc.hits > 0 or inc.misses > 0

    def test_reuse_survives_padded_n_changes(self):
        """A row kept byte-identical must HIT even when other rows grow
        the padded N between ticks (padding is not part of the row's
        canonical form)."""
        rng = np.random.default_rng(7)
        inc = IncrementalNms(THR, backend="host")
        stable = _random_rows(rng, 1)[0]
        tick1 = [stable, _random_rows(rng, 1)[0]]
        tick2 = [stable, [_Det(b, 0.5) for b in _random_boxes(rng, 20)]]
        inc.suppress([0, 1], *pad_detection_rows(tick1))
        hits0 = inc.hits
        keep = inc.suppress([0, 1], *pad_detection_rows(tick2))
        assert inc.hits == hits0 + 1
        full = sphere.sph_nms_batch(*pad_detection_rows(tick2),
                                    iou_threshold=THR, backend="host")
        assert np.array_equal(keep, full)


# ---------------------------------------------------------------------------
# bf16 SphIoU keep-mask flip bound
# ---------------------------------------------------------------------------

# acceptance bound, mirrored by the nightly gate (check_regression.py):
# measured flip rate is ~0.1% on random box sets; 1% is the envelope.
BF16_FLIP_BOUND = 0.01
# rows with no IoU pair this close to the threshold must never flip
BF16_NEAR_MARGIN = 0.05


class TestBf16SphIoU:
    @given(st.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def test_flip_bound_property(self, seed):
        self._check_flips(seed)

    def test_flip_bound_fixed(self):
        flips = total = 0
        for seed in (0, 1, 2, 3):
            f, t = self._check_flips(seed)
            flips += f
            total += t
        assert flips / total <= BF16_FLIP_BOUND

    @staticmethod
    def _check_flips(seed):
        """bf16 IoU may flip keep decisions only on rows holding a
        near-threshold pair, and at a rate under the gated bound."""
        rng = np.random.default_rng(seed)
        b, n = 8, 24
        boxes = _random_boxes(rng, b * n).reshape(b, n, 4)
        scores = rng.uniform(0.1, 1, (b, n))
        k32 = sphere.sph_nms_batch(boxes, scores, None, THR, backend="jit")
        k16 = sphere.sph_nms_batch(boxes, scores, None, THR, backend="jit",
                                   iou_dtype=jnp.bfloat16)
        diff = k32 != k16
        iou = np.stack([sphere.sph_iou_matrix_np(boxes[i].astype(np.float64),
                                                 boxes[i].astype(np.float64))
                        for i in range(b)])
        near = np.abs(iou - THR) <= BF16_NEAR_MARGIN
        np.einsum("bii->bi", near)[:] = False  # self-IoU is always 1
        far_rows = ~near.any(axis=(1, 2))
        assert not (diff.any(axis=1) & far_rows).any(), \
            "bf16 flipped a row with no near-threshold IoU pair"
        return int(diff.sum()), int(diff.size)

    def test_host_backend_rejects_iou_dtype(self):
        rng = np.random.default_rng(0)
        boxes = _random_boxes(rng, 8)[None]
        scores = rng.uniform(0.1, 1, (1, 8))
        with pytest.raises(ValueError, match="iou_dtype"):
            sphere.sph_nms_batch(boxes, scores, None, THR, backend="host",
                                 iou_dtype=jnp.bfloat16)

    @pytest.mark.parametrize("backend", ["device", "jit"])
    def test_tpu_rejects_iou_dtype(self, monkeypatch, backend):
        """On a TPU the bf16 SphIoU cannot lower (v5e has no bf16
        transcendentals): both compiled backends refuse it up front."""
        rng = np.random.default_rng(0)
        boxes = _random_boxes(rng, 8)[None]
        scores = rng.uniform(0.1, 1, (1, 8))
        monkeypatch.setattr(sphere.jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="TPU"):
            sphere.sph_nms_batch(boxes, scores, None, THR, backend=backend,
                                 iou_dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# Batched back-projection: one jitted program and one pull per chunk
# ---------------------------------------------------------------------------

# float32 inputs and arithmetic through about twenty dependent ops (tan,
# normalise, two rotations, arctan2/arcsin) on O(1) angles: a few float32
# ulps of 1 rad each, well under 1e-5 rad; a wrong geometry, a dropped
# corner or a swapped axis is off by 1e-3 rad or more.
BACKPROJ_TOL = 1e-5


def _frame64(t, p):
    """Rows e1 (the direction (t, p)), east and north: the rotation
    taking direction (t, p) to +x, in float64."""
    ct, st, cp, sp = np.cos(t), np.sin(t), np.cos(p), np.sin(p)
    return np.stack([np.stack([cp * ct, cp * st, sp], -1),
                     np.stack([-st, ct, np.zeros_like(t)], -1),
                     np.stack([-sp * ct, -sp * st, cp], -1)], -2)


def _backproject64(rect, center, fov, size):
    """(K, 4) PI pixel boxes -> (K, 4) SphBBs in float64, written from
    the gnomonic geometry and not from ``pi_box_to_sphbb``."""
    rect = np.asarray(rect, np.float64)
    frame = _frame64(np.float64(center[0]), np.float64(center[1]))
    hx, hy = np.tan(fov[0] / 2), np.tan(fov[1] / 2)

    def lift(px, py):
        d = np.stack([np.ones_like(px), (px / size - 0.5) * 2 * hx,
                      (0.5 - py / size) * 2 * hy], -1)
        return (d / np.linalg.norm(d, axis=-1, keepdims=True)) @ frame

    def sph(v):
        return np.arctan2(v[..., 1], v[..., 0]), np.arcsin(
            np.clip(v[..., 2], -1, 1))

    x0, y0, x1, y1 = rect.T
    ct, cp = sph(lift((x0 + x1) / 2, (y0 + y1) / 2))
    corners = np.stack([lift(x0, y0), lift(x1, y0), lift(x0, y1),
                        lift(x1, y1)])  # (4, K, 3)
    lon, lat = sph(np.einsum("kij,ckj->cki", _frame64(ct, cp), corners))
    return np.stack([ct, cp, lon.max(0) - lon.min(0),
                     lat.max(0) - lat.min(0)], -1)


def _angle_err(got, want) -> float:
    """Largest difference of two SphBB stacks, theta wrapped."""
    d = np.asarray(got, np.float64).reshape(-1, 4) - want
    d[..., 0] = (d[..., 0] + np.pi) % (2 * np.pi) - np.pi
    return float(np.abs(d).max(initial=0.0))


def _pixel_boxes(rng, shape, size):
    """Random (..., 4) (x0, y0, x1, y1) boxes inside a size x size PI."""
    xy = np.sort(rng.uniform(0, size, shape + (2, 2)), axis=-2)
    return np.concatenate([xy[..., 0, :], xy[..., 1, :]], -1).astype(
        np.float32)


def _serve_chunk(backend, boxes, scores, classes, regions, size,
                 geoms=None):
    """One chunk through the served back-projection and pull:
    ``boxes`` (b_pad, max_det, 4), detections of the first
    ``len(regions)`` rows."""
    chunk = [(None, r) for r in regions]
    sphbbs = backend._launch_backproject(
        jnp.asarray(boxes), chunk, geoms or [None] * len(chunk), size)
    return backend._fetch_dets(len(chunk), jnp.asarray(scores),
                               jnp.asarray(classes), sphbbs)


class TestRowToDets:
    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def test_within_tol_of_float64_property(self, seed, detector):
        self._check_chunk(seed, detector)

    def test_within_tol_of_float64_fixed(self, detector):
        for seed in (0, 1, 2):
            self._check_chunk(seed, detector)

    @staticmethod
    def _check_chunk(seed, detector):
        """A chunk of b rows through the one jitted back-projection and
        the one pull serves, in each row, the live (score > 0) entries
        in decode order with their scores and classes as decoded, and
        SphBBs within ``BACKPROJ_TOL`` of a float64 back-projection of
        the same pixel boxes through the row's geometry."""
        rng = np.random.default_rng(seed)
        backend = _backend(detector, fused=True)
        size = 64
        b = int(rng.integers(1, 9))
        b_pad = backend.buckets.pad_batch(b)
        k = backend.max_det
        boxes = _pixel_boxes(rng, (b_pad, k), size)
        scores = (rng.uniform(0, 1, (b_pad, k))
                  * (rng.random((b_pad, k)) < 0.7)).astype(np.float32)
        classes = rng.integers(0, 8, (b_pad, k)).astype(np.int32)
        regions = _regions(rng, b)
        got = _serve_chunk(backend, boxes, scores, classes, regions, size)
        assert len(got) == b
        for r, (row, region) in enumerate(zip(got, regions)):
            live = np.flatnonzero(scores[r] > 0)
            assert [d.category for d in row] == [int(c)
                                                 for c in classes[r, live]]
            assert [d.score for d in row] == [float(s)
                                              for s in scores[r, live]]
            want = _backproject64(boxes[r, live], region.center,
                                  region.fov, size)
            assert _angle_err([d.box for d in row], want) < BACKPROJ_TOL

    def test_row_alone_matches_row_in_chunk(self, detector):
        """Row r back-projected alone (b = 1) and inside a b = 8 chunk:
        the same program at two batch sizes, within 1e-6 rad."""
        rng = np.random.default_rng(5)
        backend = _backend(detector, fused=True)
        size, k = 64, backend.max_det
        boxes = _pixel_boxes(rng, (8, k), size)
        scores = np.full((8, k), 0.5, np.float32)
        classes = np.zeros((8, k), np.int32)
        regions = _regions(rng, 8)
        full = _serve_chunk(backend, boxes, scores, classes, regions, size)
        for r in range(8):
            alone = _serve_chunk(backend, boxes[r:r + 1], scores[r:r + 1],
                                 classes[r:r + 1], regions[r:r + 1], size)
            assert len(alone[0]) == len(full[r]) == k
            assert _angle_err([d.box for d in alone[0]],
                              np.stack([d.box for d in full[r]])) < 1e-6

    def test_padding_rows_and_zero_scores_yield_nothing(self, detector):
        """Padding rows and zero-score entries are back-projected with
        the chunk and dropped on the host: only the real rows come
        back, and in them only the live entries, order and scores as
        decoded."""
        rng = np.random.default_rng(6)
        backend = _backend(detector, fused=True)
        size, k = 64, backend.max_det
        b, b_pad = 3, backend.buckets.pad_batch(3)
        assert b_pad > b
        boxes = _pixel_boxes(rng, (b_pad, k), size)
        scores = np.tile(np.array([0.9, 0.0, 0.4, 0.0], np.float32),
                         (b_pad, 1))
        scores[1] = 0.0  # a real row with nothing live
        classes = np.tile(np.arange(k, dtype=np.int32), (b_pad, 1))
        got = _serve_chunk(backend, boxes, scores, classes,
                           _regions(rng, b), size)
        assert [len(row) for row in got] == [2, 0, 2]
        for row in (got[0], got[2]):
            assert [(d.category, d.score) for d in row] == [
                (0, float(np.float32(0.9))), (2, float(np.float32(0.4)))]

    def test_cache_hit_lifts_through_the_anchor_geometry(self, detector):
        """A sub-pixel drift hits the crop cache, and the hit's boxes
        lift through the anchor region's geometry: they match the
        float64 back-projection at the anchor and miss the one at the
        drifted query region by far more than rounding."""
        rng = np.random.default_rng(7)
        frame = rng.random((64, 128, 3)).astype(np.float32)
        variant = profiles.make_ladder(seed=0)[0]
        size = 64
        px = FOV[0] / size
        anchor = sroi_mod.SRoI(center=(round(0.7 / px) * px, 0.2), fov=FOV)
        query = sroi_mod.SRoI(center=(anchor.center[0] + 0.4 * px, 0.2),
                              fov=FOV)
        backend = _backend(detector, fused=True)
        seen = []
        real = backend._launch_backproject

        def spy(boxes, chunk, geoms, size):
            seen.append(np.asarray(boxes))
            return real(boxes, chunk, geoms, size)

        backend._launch_backproject = spy
        backend.infer_srois_batched([(frame, anchor)], variant)
        hits0 = backend.crop_cache_hits
        (row,) = backend.infer_srois_batched([(frame, query)], variant)
        assert backend.crop_cache_hits == hits0 + 1
        assert row, "no live detections to check"
        boxes = seen[-1][0, :len(row)]  # decode puts live entries first
        got = np.stack([d.box for d in row])
        at_anchor = _backproject64(boxes, anchor.center, FOV, size)
        at_query = _backproject64(boxes, query.center, FOV, size)
        assert _angle_err(got, at_anchor) < BACKPROJ_TOL
        assert _angle_err(got, at_query) > 100 * BACKPROJ_TOL

    def test_traces_bounded_apart_from_the_forwards(self, detector):
        """Mixed chunk sizes compile at most one back-projection per
        (batch rung, resolution), named for it, in a cache and a trace
        counter of their own: the forwards' ``trace_count`` and
        ``_jit_cache`` count the forwards alone."""
        rng = np.random.default_rng(8)
        frame = rng.random((64, 128, 3)).astype(np.float32)
        variant = profiles.make_ladder(seed=0)[0]
        backend = _backend(detector, fused=True)
        for count in (1, 2, 3, 5, 8, 1, 4):
            backend.infer_srois_batched(
                [(frame, r) for r in _regions(rng, count)], variant)
        backend.infer_sroi(frame, _regions(rng, 1)[0], variant)
        rungs = backend.buckets.batch_sizes
        n_res = len(backend.buckets.resolutions)
        assert backend.backproject_trace_count <= len(rungs) * n_res
        assert len(backend._backproject_cache) == \
            backend.backproject_trace_count
        assert {(b, s) for _, b, s in backend._backproject_cache} <= {
            (b, s) for b in rungs for s in backend.buckets.resolutions}
        assert backend.trace_count == len(backend._jit_cache)
        assert all(len(key) == 2 for key in backend._jit_cache)
        fn = backend._backproject_fn(8, 64)
        head = fn.lower(jnp.zeros((8, 4, 4)), np.zeros((8, 4), np.float32)
                        ).as_text().splitlines()[0]
        assert head.startswith("module @jit_backproject_s64_b8 "), head

    def test_a_replaced_pi_box_to_sphbb_takes_effect(self, detector,
                                                     monkeypatch):
        """Replacing ``scheduler.pi_box_to_sphbb`` after the programs
        compiled changes what is served: the jit cache is keyed on the
        implementation, so no compiled program outlives it."""
        from repro.serving import scheduler

        rng = np.random.default_rng(9)
        frame = rng.random((64, 128, 3)).astype(np.float32)
        variant = profiles.make_ladder(seed=0)[0]
        items = [(frame, r) for r in _regions(rng, 4)]
        backend = _backend(detector, fused=False)
        before = backend.infer_srois_batched(items, variant)
        assert sum(len(row) for row in before) > 0
        real = scheduler.pi_box_to_sphbb
        monkeypatch.setattr(
            scheduler, "pi_box_to_sphbb",
            lambda *a: real(*a) + jnp.array([0.05, 0, 0, 0]))
        after = backend.infer_srois_batched(items, variant)
        for row_b, row_a in zip(before, after):
            assert len(row_b) == len(row_a)
            for db, da in zip(row_b, row_a):
                np.testing.assert_allclose(da.box[0] - db.box[0], 0.05,
                                           atol=1e-5)


# ---------------------------------------------------------------------------
# Odd-N block clamp (satellite bugfix regression)
# ---------------------------------------------------------------------------


class TestBlockClamp:
    def test_clamp_is_lane_aligned(self):
        """8 < n < block must round UP to a multiple of 8: the old
        ``min(block, n)`` produced e.g. a 100-wide Pallas block for
        n=100, which Mosaic rejects on real TPUs."""
        from repro.kernels.sphiou.ops import _clamp_block

        for n in range(1, 300):
            blk = _clamp_block(256, n)
            assert blk % 8 == 0
            assert blk >= min(8, n)
            assert blk >= min(256, n)  # covers the padded problem
            assert blk <= 256

    def test_odd_n_matches_reference(self):
        """n=100, m=37 (both non-lane-aligned) through the default
        block clamp matches the numpy oracle."""
        from repro.kernels.sphiou.ops import sphiou_matrix

        rng = np.random.default_rng(0)
        a = jnp.asarray(_random_boxes(rng, 100), jnp.float32)
        b = jnp.asarray(_random_boxes(rng, 37), jnp.float32)
        got = np.asarray(sphiou_matrix(a, b))
        want = sphere.sph_iou_matrix_np(np.asarray(a, np.float64),
                                        np.asarray(b, np.float64))
        assert got.shape == (100, 37)
        np.testing.assert_allclose(got, want, atol=2e-5)
