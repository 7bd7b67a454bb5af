"""Batched spherical NMS subsystem: cross-implementation equivalence.

Three independent implementations must produce bit-identical keep-masks:

  * ``sph_nms_lax``    — jit-compatible ``lax.fori_loop`` (the oracle),
  * ``sph_nms_host``   — vectorised NumPy greedy (serving fast path),
  * ``sph_nms_batch``  — the padded (B, N) subsystem, exercised through
    BOTH backends: vectorised host and the batched Pallas SphIoU kernel
    + ``lax.while_loop`` (interpret mode on CPU).

``sph_nms`` itself is now the B=1 entry point of ``sph_nms_batch``
(the ROADMAP fold); ``TestSingleRowFold`` pins it against the kept-old
``sph_nms_lax`` oracle on this suite's corpus.

Sweeps cover antimeridian seam-wrap boxes, all-padded rows, single-box
rows and empty inputs; property tests (shimmed when hypothesis is
absent) pin the keep-mask's invariance under score-preserving
permutations and that padding is never kept.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import sphere

THR = 0.6


def random_boxes(rng, n, seam_frac=0.25):
    """Random SphBBs; a fraction hugs the +-pi antimeridian seam."""
    theta = rng.uniform(-math.pi, math.pi, n)
    seam = rng.random(n) < seam_frac
    theta[seam] = np.sign(rng.standard_normal(seam.sum())) * (
        math.pi - rng.uniform(0.0, 0.1, seam.sum()))
    return np.stack([
        theta,
        rng.uniform(-1.3, 1.3, n),
        rng.uniform(0.05, 0.9, n),
        rng.uniform(0.05, 0.9, n)], axis=-1).astype(np.float32)


def padded_batch(rng, b, n_max, min_n=0):
    boxes = np.zeros((b, n_max, 4), np.float32)
    scores = np.zeros((b, n_max), np.float32)
    mask = np.zeros((b, n_max), bool)
    for r in range(b):
        n = int(rng.integers(min_n, n_max + 1))
        if n:
            boxes[r, :n] = random_boxes(rng, n)
            scores[r, :n] = rng.uniform(0.01, 1.0, n)
            mask[r, :n] = True
    return boxes, scores, mask


class TestEquivalence:
    def test_1024_random_rows_host_backend(self):
        """Acceptance sweep: >=1000 padded rows, host backend, per-row
        keep-masks identical to the single-row host reference."""
        rng = np.random.default_rng(7)
        boxes, scores, mask = padded_batch(rng, 1024, 24)
        keep = sphere.sph_nms_batch(boxes, scores, mask, THR, backend="host")
        assert not keep[~mask].any()
        for r in range(boxes.shape[0]):
            n = int(mask[r].sum())
            ref = sphere.sph_nms_host(boxes[r, :n], scores[r, :n], THR)
            assert (keep[r, :n] == ref).all(), f"row {r}"

    def test_lax_oracle_agrees(self):
        """The jit ``sph_nms_lax`` oracle vs host/batched paths on a
        few fixed shapes (each distinct N compiles the fori_loop once)."""
        rng = np.random.default_rng(13)
        for n in (1, 2, 17, 24):
            for _ in range(4):
                boxes = random_boxes(rng, n)
                scores = rng.uniform(0.01, 1.0, n).astype(np.float32)
                ref_lax = np.asarray(sphere.sph_nms_lax(
                    jnp.asarray(boxes), jnp.asarray(scores), THR))
                host = sphere.sph_nms_host(boxes, scores, THR)
                batch = sphere.sph_nms_batch(
                    boxes[None], scores[None], None, THR, backend="host")[0]
                assert (ref_lax == host).all(), n
                assert (ref_lax == batch).all(), n

    def test_pallas_interpret_matches_host(self):
        """Device backend (Pallas-interpret SphIoU + lax.while_loop) vs
        the vectorised host path on the same padded batch."""
        rng = np.random.default_rng(11)
        boxes, scores, mask = padded_batch(rng, 48, 20)
        k_host = sphere.sph_nms_batch(boxes, scores, mask, THR,
                                      backend="host")
        k_dev = sphere.sph_nms_batch(boxes, scores, mask, THR,
                                     backend="device")
        assert (k_host == k_dev).all()

    def test_pallas_interpret_matches_single_row_host(self):
        """Per row, the device backend (the kernel's own atan2) keeps
        exactly what ``sph_nms_host`` (float64 IoU) keeps, on the seam-
        heavy corpus and on the seam-wrap pair."""
        rng = np.random.default_rng(29)
        boxes, scores, mask = padded_batch(rng, 32, 24)
        keep = sphere.sph_nms_batch(boxes, scores, mask, THR,
                                    backend="device")
        for r in range(boxes.shape[0]):
            n = int(mask[r].sum())
            ref = sphere.sph_nms_host(boxes[r, :n], scores[r, :n], THR)
            assert (keep[r, :n] == ref).all(), f"row {r}"
            assert not keep[r, n:].any()

    def test_jit_backend_matches_host(self):
        """The XLA-compiled path (fused jnp IoU + lax.while_loop) —
        the CPU bench/bulk path — against the host reference, with a
        chunk size that forces the row-chunked dispatch."""
        rng = np.random.default_rng(19)
        boxes, scores, mask = padded_batch(rng, 32, 16)
        k_host = sphere.sph_nms_batch(boxes, scores, mask, THR,
                                      backend="host")
        k_jit = sphere.sph_nms_batch(boxes, scores, mask, THR,
                                     backend="jit")
        assert (k_host == k_jit).all()

    def test_jit_backend_chunked(self, monkeypatch):
        rng = np.random.default_rng(23)
        boxes, scores, mask = padded_batch(rng, 6, 12, min_n=1)
        full = sphere.sph_nms_batch(boxes, scores, mask, THR, backend="jit")
        monkeypatch.setattr(sphere, "_DEVICE_CHUNK_ELEMS", 2 * 12 * 12)
        chunked = sphere.sph_nms_batch(boxes, scores, mask, THR,
                                       backend="jit")
        assert (full == chunked).all()

    def test_seam_wrap_pair_suppressed(self):
        # two near-identical boxes straddling +-pi: one must suppress
        # the other in every implementation
        boxes = np.array([[math.pi - 0.02, 0.0, 0.4, 0.4],
                          [-math.pi + 0.02, 0.0, 0.4, 0.4]], np.float32)
        scores = np.array([0.9, 0.8], np.float32)
        assert sphere.sph_nms_host(boxes, scores, THR).tolist() == [True, False]
        for backend in ("host", "device"):
            keep = sphere.sph_nms_batch(boxes[None], scores[None], None, THR,
                                        backend=backend)[0]
            assert keep.tolist() == [True, False], backend

    def test_all_padded_rows(self):
        boxes = np.zeros((3, 8, 4), np.float32)
        scores = np.zeros((3, 8), np.float32)
        mask = np.zeros((3, 8), bool)
        for backend in ("host", "device"):
            keep = sphere.sph_nms_batch(boxes, scores, mask, THR,
                                        backend=backend)
            assert not keep.any(), backend

    def test_single_box_rows(self):
        rng = np.random.default_rng(3)
        boxes = np.zeros((4, 1, 4), np.float32)
        boxes[:, 0] = random_boxes(rng, 4)
        scores = rng.uniform(0.1, 1, (4, 1)).astype(np.float32)
        for backend in ("host", "device"):
            keep = sphere.sph_nms_batch(boxes, scores, None, THR,
                                        backend=backend)
            assert keep.all(), backend

    def test_empty_n(self):
        keep = sphere.sph_nms_batch(np.zeros((2, 0, 4), np.float32),
                                    np.zeros((2, 0), np.float32))
        assert keep.shape == (2, 0)

    def test_max_out_ranks_by_score(self):
        rng = np.random.default_rng(5)
        boxes = random_boxes(rng, 30)[None]
        scores = rng.uniform(0, 1, (1, 30)).astype(np.float32)
        full = sphere.sph_nms_batch(boxes, scores, None, THR)
        capped = sphere.sph_nms_batch(boxes, scores, None, THR, max_out=2)
        assert capped.sum() == min(2, full.sum())
        # capped survivors are the top-scoring survivors of the full run
        kept_scores = scores[0][capped[0]]
        assert (kept_scores >= scores[0][full[0]].min() - 1e-9).all()
        assert (capped & ~full).sum() == 0


class TestSingleRowFold:
    """ROADMAP fold (PR 4 satellite): ``sph_nms`` is now expressed as
    ``sph_nms_batch(boxes[None], ...)``; the ORIGINAL jit-compatible
    implementation is kept as ``sph_nms_lax`` and these tests pin
    keep-mask equality on the existing property-suite corpus."""

    def test_fold_matches_old_oracle_on_corpus(self):
        rng = np.random.default_rng(13)  # the lax-oracle corpus
        for n in (1, 2, 17, 24, 40):
            for _ in range(4):
                boxes = random_boxes(rng, n)
                scores = rng.uniform(0.01, 1.0, n).astype(np.float32)
                old = np.asarray(sphere.sph_nms_lax(
                    jnp.asarray(boxes), jnp.asarray(scores), THR))
                new = sphere.sph_nms(boxes, scores, THR)
                assert (new == old).all(), n

    def test_fold_is_the_batch_single_row(self):
        rng = np.random.default_rng(29)
        boxes = random_boxes(rng, 20)
        scores = rng.uniform(0.01, 1.0, 20).astype(np.float32)
        keep = sphere.sph_nms(boxes, scores, THR)
        batch = sphere.sph_nms_batch(boxes[None], scores[None], None, THR)[0]
        assert (keep == batch).all()
        assert keep.shape == (20,)

    def test_fold_max_out_matches_old_oracle(self):
        rng = np.random.default_rng(31)
        boxes = random_boxes(rng, 30)
        # distinct scores so max_out's score ranking is unambiguous
        scores = (rng.permutation(30) + 1.0).astype(np.float32) / 30.0
        for max_out in (1, 3, 8, None):
            old = np.asarray(sphere.sph_nms_lax(
                jnp.asarray(boxes), jnp.asarray(scores), THR,
                max_out=max_out))
            new = sphere.sph_nms(boxes, scores, THR, max_out=max_out)
            assert (new == old).all(), max_out

    def test_fold_seam_and_empty(self):
        boxes = np.array([[math.pi - 0.02, 0.0, 0.4, 0.4],
                          [-math.pi + 0.02, 0.0, 0.4, 0.4]], np.float32)
        scores = np.array([0.9, 0.8], np.float32)
        assert sphere.sph_nms(boxes, scores, THR).tolist() == [True, False]
        empty = sphere.sph_nms(np.zeros((0, 4), np.float32),
                               np.zeros((0,), np.float32))
        assert empty.shape == (0,)


class TestProperties:
    @given(st.integers(0, 10_000))
    @settings(max_examples=30, deadline=None)
    def test_permutation_invariance_property(self, seed):
        self._check_permutation(seed)

    def test_permutation_invariance_fixed(self):
        for seed in (0, 1, 2, 3, 4):
            self._check_permutation(seed)

    @staticmethod
    def _check_permutation(seed):
        """A score-preserving shuffle of the boxes permutes the
        keep-mask but never changes WHICH boxes survive."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(2, 32))
        boxes = random_boxes(rng, n)
        # distinct scores so the greedy order is permutation-independent
        scores = (np.arange(1, n + 1) / n).astype(np.float32)
        rng.shuffle(scores)
        perm = rng.permutation(n)
        keep = sphere.sph_nms_batch(boxes[None], scores[None], None, THR,
                                    backend="host")[0]
        keep_p = sphere.sph_nms_batch(boxes[perm][None], scores[perm][None],
                                      None, THR, backend="host")[0]
        assert (keep_p == keep[perm]).all()

    @given(st.integers(0, 10_000))
    @settings(max_examples=30, deadline=None)
    def test_padding_never_kept_property(self, seed):
        self._check_padding(seed)

    def test_padding_never_kept_fixed(self):
        for seed in (10, 11, 12):
            self._check_padding(seed)

    @staticmethod
    def _check_padding(seed):
        """Masked entries are never kept — even with forged high scores
        and non-degenerate box geometry in the padded slots."""
        rng = np.random.default_rng(seed)
        b, n = int(rng.integers(1, 6)), int(rng.integers(1, 16))
        boxes = random_boxes(rng, b * n).reshape(b, n, 4)
        scores = rng.uniform(0, 1, (b, n)).astype(np.float32)
        mask = rng.random((b, n)) < 0.5
        scores[~mask] = 2.0  # padding must lose even with the top score
        for backend in ("host", "device"):
            keep = sphere.sph_nms_batch(boxes, scores, mask, THR,
                                        backend=backend)
            assert not keep[~mask].any(), backend

    def test_survivors_mutually_nonoverlapping_batch(self):
        rng = np.random.default_rng(21)
        boxes, scores, mask = padded_batch(rng, 16, 24, min_n=2)
        keep = sphere.sph_nms_batch(boxes, scores, mask, THR, backend="host")
        for r in range(boxes.shape[0]):
            surv = boxes[r][keep[r]]
            if len(surv) > 1:
                iou = sphere.sph_iou_matrix_np(
                    surv.astype(np.float64), surv.astype(np.float64))
                np.fill_diagonal(iou, 0)
                assert iou.max() <= THR + 1e-6


class TestBatchedIoUHostPath:
    def test_batched_np_matrix_matches_unbatched(self):
        rng = np.random.default_rng(2)
        stack = np.stack([random_boxes(rng, 12) for _ in range(5)])
        batched = sphere.sph_iou_matrix_np(stack.astype(np.float64),
                                           stack.astype(np.float64))
        for r in range(5):
            single = sphere.sph_iou_matrix_np(stack[r].astype(np.float64),
                                              stack[r].astype(np.float64))
            np.testing.assert_allclose(batched[r], single, rtol=1e-12)

    def test_host_chunking_consistent(self, monkeypatch):
        rng = np.random.default_rng(9)
        boxes, scores, mask = padded_batch(rng, 10, 16, min_n=1)
        full = sphere.sph_nms_batch(boxes, scores, mask, THR, backend="host")
        monkeypatch.setattr(sphere, "_HOST_CHUNK_ELEMS", 16 * 16)  # 1 row
        chunked = sphere.sph_nms_batch(boxes, scores, mask, THR,
                                       backend="host")
        assert (full == chunked).all()
