"""Spherical geometry primitives for 360-degree video analytics.

Implements the spherical criteria of Zhao et al. (AAAI'20) used by the
OmniSense paper:

  * ``SphBB`` — a spherical bounding box ``(theta, phi, dtheta, dphi)``
    where ``theta`` is the longitude of the box centre in ``[-pi, pi]``,
    ``phi`` the latitude in ``[-pi/2, pi/2]`` and ``dtheta``/``dphi``
    the horizontal/vertical field-of-view occupied by the object,
    *defined in the box's own tangent frame* (i.e. the box is the
    rotation of an equator-centred spherical rectangle).
  * ``sph_area`` — the area of a SphBB on the unit sphere,
    ``2 * dtheta * sin(dphi / 2)`` (rotation invariant; paper footnote 1).
  * ``sph_iou`` — pairwise spherical IoU.  Box A's centre is rotated to
    the equator origin and box B's centre is expressed exactly in that
    rotated frame; the intersection is then evaluated as the
    lat/long-interval overlap of two equator-centred rectangles (the
    fast approximation of the AAAI'20 spherical criteria).
  * ``sph_nms`` — greedy spherical non-maximum suppression (paper
    default threshold 0.6): the single-row (B=1) entry point of
    ``sph_nms_batch``.  ``sph_nms_lax`` keeps the original
    jit-compatible ``lax.fori_loop`` form as an independent oracle, and
    ``sph_nms_host`` the fast NumPy form used by the online serving
    loop.
  * ``sph_nms_batch`` — the batched NMS subsystem used by the pod
    serving loop (design note below).

Batched-NMS design note
-----------------------
At pod scale (``repro.serving.server.PodServer``) hundreds of streams
finish a frame per scheduler tick, and running greedy NMS as one
Python loop per stream makes post-processing scale with the Python
interpreter instead of with the mesh.  ``sph_nms_batch`` therefore
takes *padded* ``(B, N, 4)`` box stacks — one row per stream/frame,
rows padded to a common N with a boolean validity ``mask`` — and:

  1. computes the per-row ``(B, N, N)`` SphIoU matrices in ONE
     dispatch, via the batched Pallas kernel
     (``repro.kernels.sphiou.ops.sphiou_matrix_batch``) on device, or
     the vectorised NumPy path on host;
  2. runs greedy suppression for all rows simultaneously as a
     ``lax.while_loop`` (device) / NumPy loop (host) whose iteration
     count is the *maximum number of survivors over rows*, not N: each
     step keeps every row's best remaining box and suppresses its
     overlaps, which is exactly sequential greedy NMS because the best
     remaining box can never be overlapped by an earlier kept one.

Padded entries carry zero-area FoVs (IoU 0 against everything) and are
masked out of the candidate set, so they are never kept.  The greedy
order is descending score with lowest-index-first tie-breaking in every
implementation, keeping the lax, host and batched paths bit-identical.

All functions are vectorised over leading axes and safe to ``jax.jit``.
Angles are radians everywhere; degrees only at config boundaries.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

Array = jax.Array

# --------------------------------------------------------------------------
# Coordinate transforms
# --------------------------------------------------------------------------


def sph_to_cart(theta: Array, phi: Array) -> Array:
    """(lon, lat) -> unit vector, shape (..., 3).

    x axis points at (theta=0, phi=0); z is the north pole.
    """
    cp = jnp.cos(phi)
    return jnp.stack([cp * jnp.cos(theta), cp * jnp.sin(theta), jnp.sin(phi)], axis=-1)


def cart_to_sph(v: Array) -> tuple[Array, Array]:
    """Unit vector (..., 3) -> (lon, lat)."""
    x, y, z = v[..., 0], v[..., 1], v[..., 2]
    theta = jnp.arctan2(y, x)
    phi = jnp.arcsin(jnp.clip(z, -1.0, 1.0))
    return theta, phi


def wrap_angle(a: Array) -> Array:
    """Wrap angle(s) to [-pi, pi)."""
    return (a + jnp.pi) % (2.0 * jnp.pi) - jnp.pi


def rotation_to_origin(theta: Array, phi: Array) -> Array:
    """Rotation matrix R (.., 3, 3) with R @ dir(theta, phi) == (1, 0, 0).

    Composition: first undo longitude (rotate about z by -theta), then undo
    latitude (rotate about y by +phi).
    """
    ct, st = jnp.cos(theta), jnp.sin(theta)
    cp, sp = jnp.cos(phi), jnp.sin(phi)
    zero = jnp.zeros_like(ct)
    one = jnp.ones_like(ct)
    # Rz(-theta)
    rz = jnp.stack(
        [
            jnp.stack([ct, st, zero], axis=-1),
            jnp.stack([-st, ct, zero], axis=-1),
            jnp.stack([zero, zero, one], axis=-1),
        ],
        axis=-2,
    )
    # Ry(phi): rotates the +x axis toward +z by -phi... chosen so that
    # Ry @ (cos(phi), 0, sin(phi)) = (1, 0, 0).
    ry = jnp.stack(
        [
            jnp.stack([cp, zero, sp], axis=-1),
            jnp.stack([zero, one, zero], axis=-1),
            jnp.stack([-sp, zero, cp], axis=-1),
        ],
        axis=-2,
    )
    return ry @ rz


def rotation_from_origin(theta: Array, phi: Array) -> Array:
    """Inverse of :func:`rotation_to_origin` (transpose)."""
    r = rotation_to_origin(theta, phi)
    return jnp.swapaxes(r, -1, -2)


# --------------------------------------------------------------------------
# SphBB area / IoU
# --------------------------------------------------------------------------


def sph_area(boxes: Array) -> Array:
    """Area on the unit sphere of SphBBs (..., 4) -> (...).

    ``area = 2 * dtheta * sin(dphi / 2)`` (paper footnote 1).  Rotation
    invariant because the box is defined in its own tangent frame.
    """
    dtheta = boxes[..., 2]
    dphi = boxes[..., 3]
    return 2.0 * dtheta * jnp.sin(dphi / 2.0)


def _interval_overlap(lo1: Array, hi1: Array, lo2: Array, hi2: Array) -> tuple[Array, Array]:
    lo = jnp.maximum(lo1, lo2)
    hi = jnp.minimum(hi1, hi2)
    return lo, hi


def sph_intersection(boxes_a: Array, boxes_b: Array) -> Array:
    """Pairwise intersection area between two broadcastable SphBB arrays.

    ``boxes_a``: (..., 4) and ``boxes_b``: (..., 4), already broadcast
    against each other (callers usually expand dims to form an N x M
    grid).  Box A is rotated to the origin; box B's centre is expressed
    exactly in A's frame; both are then treated as equator-centred
    lat/long rectangles (AAAI'20 fast criteria).
    """
    ta, pa = boxes_a[..., 0], boxes_a[..., 1]
    tb, pb = boxes_b[..., 0], boxes_b[..., 1]
    # exact position of B's centre in A's frame
    r = rotation_to_origin(ta, pa)
    db = sph_to_cart(tb, pb)
    db_in_a = jnp.einsum("...ij,...j->...i", r, db)
    dlon, dlat = cart_to_sph(db_in_a)

    half_ta, half_pa = boxes_a[..., 2] / 2.0, boxes_a[..., 3] / 2.0
    half_tb, half_pb = boxes_b[..., 2] / 2.0, boxes_b[..., 3] / 2.0

    lon_lo, lon_hi = _interval_overlap(-half_ta, half_ta, dlon - half_tb, dlon + half_tb)
    lat_lo, lat_hi = _interval_overlap(-half_pa, half_pa, dlat - half_pb, dlat + half_pb)

    lon_w = jnp.maximum(lon_hi - lon_lo, 0.0)
    # exact area element in latitude: integral of cos(phi) d(phi)
    lat_w = jnp.maximum(jnp.sin(lat_hi) - jnp.sin(lat_lo), 0.0)
    lat_w = jnp.where(lat_hi > lat_lo, lat_w, 0.0)
    return lon_w * lat_w


def sph_iou(boxes_a: Array, boxes_b: Array) -> Array:
    """Pairwise SphIoU of broadcastable SphBB arrays -> (...).

    The single-direction fast approximation is slightly asymmetric for
    large boxes at different latitudes (whichever box is rotated to the
    origin sees less distortion); we symmetrise by averaging the two
    directions, which restores IoU(a, b) == IoU(b, a) exactly.
    """
    inter = 0.5 * (sph_intersection(boxes_a, boxes_b)
                   + sph_intersection(boxes_b, boxes_a))
    union = sph_area(boxes_a) + sph_area(boxes_b) - inter
    return inter / jnp.maximum(union, 1e-12)


def sph_iou_matrix(boxes_a: Array, boxes_b: Array) -> Array:
    """(N, 4) x (M, 4) -> (N, M) SphIoU matrix (pure jnp reference).

    The Pallas kernel in ``repro.kernels.sphiou`` computes the same
    matrix tile-by-tile; this function is its oracle.
    """
    return sph_iou(boxes_a[:, None, :], boxes_b[None, :, :])


# --------------------------------------------------------------------------
# Spherical NMS
# --------------------------------------------------------------------------


def sph_nms(
    boxes: Array,
    scores: Array,
    iou_threshold: float = 0.6,
    max_out: int | None = None,
) -> np.ndarray:
    """Greedy spherical NMS for one frame's boxes -> (N,) keep-mask.

    The single-row entry point of the batched subsystem: dispatches to
    ``sph_nms_batch(boxes[None], ...)`` (ROADMAP fold — the while-loop
    path has soaked, so the B=1 case no longer carries a private
    implementation).  The original jit-compatible ``lax.fori_loop``
    form lives on as :func:`sph_nms_lax`, kept as an INDEPENDENT oracle
    for the equivalence suite; trace-time callers should use it
    directly.
    """
    keep = sph_nms_batch(np.asarray(boxes)[None], np.asarray(scores)[None],
                         None, iou_threshold, max_out=max_out)
    return keep[0]


def sph_nms_lax(
    boxes: Array,
    scores: Array,
    iou_threshold: float = 0.6,
    max_out: int | None = None,
) -> Array:
    """Greedy spherical NMS, jit-compatible (``lax.fori_loop``).

    Returns a boolean keep-mask of shape (N,).  Suppression follows the
    paper's default SphIoU threshold of 0.6.  ``max_out`` bounds the
    number of survivors (useful for fixed-shape serving buffers).
    Deliberately NOT expressed via ``sph_nms_batch``: this is the
    independent oracle the batched implementations are tested against.
    """
    n = boxes.shape[0]
    order = jnp.argsort(-scores)
    boxes_sorted = boxes[order]
    iou = sph_iou_matrix(boxes_sorted, boxes_sorted)

    def body(i, keep):
        # i is suppressed if any higher-scoring kept box overlaps it
        mask_higher = (jnp.arange(n) < i) & keep
        overlapped = jnp.any(jnp.where(mask_higher, iou[:, i] > iou_threshold, False))
        return keep.at[i].set(~overlapped)

    keep_sorted = jax.lax.fori_loop(0, n, body, jnp.ones((n,), dtype=bool))
    if max_out is not None:
        rank = jnp.cumsum(keep_sorted.astype(jnp.int32)) - 1
        keep_sorted = keep_sorted & (rank < max_out)
    # un-sort
    keep = jnp.zeros((n,), dtype=bool).at[order].set(keep_sorted)
    return keep


def _sph_intersection_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """NumPy twin of :func:`sph_intersection` for (..., N, 4) x (..., M, 4)
    grids; leading axes are batch dims shared by ``a`` and ``b``."""
    ta, pa = a[..., :, None, 0], a[..., :, None, 1]
    ha, va = a[..., :, None, 2] / 2, a[..., :, None, 3] / 2
    tb, pb = b[..., None, :, 0], b[..., None, :, 1]
    hb, vb = b[..., None, :, 2] / 2, b[..., None, :, 3] / 2
    dt = tb - ta
    cpa, spa = np.cos(pa), np.sin(pa)
    cpb, spb = np.cos(pb), np.sin(pb)
    cdt = np.cos(dt)
    x = cpa * cpb * cdt + spa * spb
    y = cpb * np.sin(dt)
    z = -spa * cpb * cdt + cpa * spb
    dlon = np.arctan2(y, x)
    dlat = np.arcsin(np.clip(z, -1.0, 1.0))
    lon_w = np.maximum(np.minimum(ha, dlon + hb) - np.maximum(-ha, dlon - hb), 0)
    lat_hi = np.minimum(va, dlat + vb)
    lat_lo = np.maximum(-va, dlat - vb)
    lat_w = np.where(lat_hi > lat_lo, np.sin(lat_hi) - np.sin(lat_lo), 0.0)
    return lon_w * np.maximum(lat_w, 0.0)


def sph_iou_matrix_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Pure-NumPy (..., N, M) SphIoU — the host serving path (no jax
    dispatch overhead per frame; identical math to
    :func:`sph_iou_matrix`).  Leading axes of ``a``/``b`` are batch
    dims, so a padded (B, N, 4) stack yields (B, N, N) in one call."""
    inter_ba = np.swapaxes(_sph_intersection_np(b, a), -1, -2)
    inter = 0.5 * (_sph_intersection_np(a, b) + inter_ba)
    area_a = 2.0 * a[..., :, 2] * np.sin(a[..., :, 3] / 2.0)
    area_b = 2.0 * b[..., :, 2] * np.sin(b[..., :, 3] / 2.0)
    union = area_a[..., :, None] + area_b[..., None, :] - inter
    return inter / np.maximum(union, 1e-12)


def sph_nms_host(
    boxes: np.ndarray,
    scores: np.ndarray,
    iou_threshold: float = 0.6,
) -> np.ndarray:
    """NumPy greedy spherical NMS for the host-side serving loop.

    Same semantics as :func:`sph_nms`; avoids a device round-trip for
    the handful of boxes the online loop handles per frame.
    """
    n = len(scores)
    if n == 0:
        return np.zeros((0,), dtype=bool)
    order = np.argsort(-np.asarray(scores), kind="stable")
    iou = sph_iou_matrix_np(np.asarray(boxes, np.float64),
                            np.asarray(boxes, np.float64))
    iou_sorted = iou[np.ix_(order, order)]
    # Vectorised greedy: each iteration keeps the best remaining box and
    # suppresses all its overlaps at once, so the loop runs once per
    # SURVIVOR (not once per box as the old per-index loop did).
    keep_sorted = np.zeros((n,), dtype=bool)
    active = np.ones((n,), dtype=bool)
    while True:
        idx = int(np.argmax(active))  # first still-active in score order
        if not active[idx]:
            break
        keep_sorted[idx] = True
        active &= iou_sorted[idx] <= iou_threshold
        active[idx] = False
    keep = np.zeros((n,), dtype=bool)
    keep[order] = keep_sorted
    return keep


# --------------------------------------------------------------------------
# Batched spherical NMS (the pod-tick subsystem; see module docstring)
# --------------------------------------------------------------------------

# Row-chunk caps: bound the (chunk, N, N) IoU tensor so huge rows
# (bench N=4096) stay within memory — ~32M float64 elements on host,
# ~128M float32 on device.
_HOST_CHUNK_ELEMS = 1 << 25
_DEVICE_CHUNK_ELEMS = 1 << 27
# "auto" picks the jitted device path (TPU) only at B*N >= this; below
# it, per-shape retracing would dominate the handful of boxes involved.
_AUTO_DEVICE_MIN_ELEMS = 512


def _greedy_suppress_rows_np(
    iou: np.ndarray,       # (B, N, N)
    scores: np.ndarray,    # (B, N)
    active: np.ndarray,    # (B, N) bool, consumed
    iou_threshold: float,
) -> np.ndarray:
    """Batched greedy suppression; iterations = max survivors over rows."""
    b, n = scores.shape
    keep = np.zeros((b, n), dtype=bool)
    cols = np.arange(n)[None, :]
    while active.any():
        masked = np.where(active, scores, -np.inf)
        best = np.argmax(masked, axis=1)                     # (B,)
        has = active.any(axis=1)                             # (B,)
        sel = (cols == best[:, None]) & has[:, None]
        keep |= sel
        iou_best = np.take_along_axis(iou, best[:, None, None], axis=1)[:, 0, :]
        active &= ~((iou_best > iou_threshold) & has[:, None]) & ~sel
    return keep


def _sph_nms_batch_host(
    boxes: np.ndarray, scores: np.ndarray, mask: np.ndarray,
    iou_threshold: float,
) -> np.ndarray:
    b, n, _ = boxes.shape
    keep = np.zeros((b, n), dtype=bool)
    chunk = max(1, _HOST_CHUNK_ELEMS // max(n * n, 1))
    for lo in range(0, b, chunk):
        hi = min(lo + chunk, b)
        iou = sph_iou_matrix_np(boxes[lo:hi].astype(np.float64),
                                boxes[lo:hi].astype(np.float64))
        keep[lo:hi] = _greedy_suppress_rows_np(
            iou, scores[lo:hi], mask[lo:hi].copy(), iou_threshold)
    return keep


# incremented at TRACE time of the jitted device/jit NMS path — the
# regression pin for shape bucketing (a serving run's retrace count
# stays bounded by the (B, N) ladder, mirroring JaxDetectorBackend's
# `trace_count`).
_NMS_DEVICE_TRACES = [0]


def nms_device_trace_count() -> int:
    """How many distinct (B, N) shapes the device NMS path has traced."""
    return _NMS_DEVICE_TRACES[0]


def nms_auto_backend(b: int, n: int) -> str:
    """The backend ``sph_nms_batch(backend="auto")`` picks for (B, N).

    Device only for genuinely batched work on TPU: the jitted path
    retraces per (B, N) shape, so the small single-row calls the
    per-frame serving loop makes stay on host everywhere.  Exposed so
    callers (``PodServer._suppress_tick``) can decide whether ladder
    padding buys bounded compile shapes or just wastes host-path work.
    """
    pod_scale = b * n >= _AUTO_DEVICE_MIN_ELEMS
    return ("device" if jax.default_backend() == "tpu" and pod_scale
            else "host")


@functools.partial(
    jax.jit, static_argnames=("interpret", "use_pallas", "iou_dtype"))
def _sph_nms_batch_device(
    boxes: Array, scores: Array, mask: Array, iou_threshold: Array,
    *, interpret: bool = False, use_pallas: bool = True,
    iou_dtype=None,
) -> Array:
    """(B, N) keep-mask: batched SphIoU + on-device greedy loop.

    The whole pod tick is one dispatch: the ``lax.while_loop`` keeps
    every row's best remaining candidate and suppresses its overlaps,
    terminating after max-survivors-per-row iterations.  The IoU block
    is the batched Pallas kernel (``use_pallas``, the TPU path) or the
    vmapped jnp oracle (XLA-fused; the fast compiled path on CPU where
    Pallas would run in interpret mode).  ``iou_dtype`` (e.g.
    ``jnp.bfloat16``, off the TPU only) selects the IoU compute
    precision, at the cost of keep flips for near-threshold pairs
    (bound measured in the kernel bench and gated nightly).
    """
    _NMS_DEVICE_TRACES[0] += 1  # runs at trace time only
    b, n, _ = boxes.shape
    if use_pallas:
        from repro.kernels.sphiou.ops import sphiou_matrix_batch

        iou = sphiou_matrix_batch(boxes, boxes, interpret=interpret,
                                  dtype=iou_dtype or jnp.float32)
    elif iou_dtype is not None:
        iou = jax.vmap(sph_iou_matrix)(
            boxes.astype(iou_dtype), boxes.astype(iou_dtype)
        ).astype(jnp.float32)
    else:
        iou = jax.vmap(sph_iou_matrix)(boxes, boxes)
    cols = jnp.arange(n)[None, :]

    def cond(state):
        _, active = state
        return jnp.any(active)

    def body(state):
        keep, active = state
        masked = jnp.where(active, scores, -jnp.inf)
        best = jnp.argmax(masked, axis=1)                    # (B,)
        has = jnp.any(active, axis=1)                        # (B,)
        sel = (cols == best[:, None]) & has[:, None]
        keep = keep | sel
        iou_best = jnp.take_along_axis(
            iou, best[:, None, None], axis=1)[:, 0, :]       # (B, N)
        active = active & ~((iou_best > iou_threshold) & has[:, None]) & ~sel
        return keep, active

    keep, _ = jax.lax.while_loop(
        cond, body,
        (jnp.zeros((b, n), dtype=bool), mask.astype(bool)),
    )
    return keep


def _apply_max_out_np(
    keep: np.ndarray, scores: np.ndarray, max_out: int
) -> np.ndarray:
    order = np.argsort(-scores, axis=1, kind="stable")
    keep_sorted = np.take_along_axis(keep, order, axis=1)
    rank = np.cumsum(keep_sorted.astype(np.int64), axis=1) - 1
    keep_sorted &= rank < max_out
    out = np.zeros_like(keep)
    np.put_along_axis(out, order, keep_sorted, axis=1)
    return out


def sph_nms_batch(
    boxes: np.ndarray | Array,        # (B, N, 4) padded SphBB stack
    scores: np.ndarray | Array,       # (B, N)
    mask: np.ndarray | Array | None = None,  # (B, N) bool; False = padding
    iou_threshold: float = 0.6,
    max_out: int | None = None,
    *,
    backend: str = "auto",
    iou_dtype=None,
) -> np.ndarray:
    """Batched greedy spherical NMS over padded rows -> (B, N) bool.

    One row per stream/frame; rows are suppressed independently but in a
    single dispatch (see the module docstring's design note).  Padded
    entries (``mask == False``) are never kept.

    ``backend``:
      * ``"auto"``   — ``"device"`` on TPU for pod-scale batches
        (``B * N`` past a small floor), ``"host"`` otherwise: the
        Pallas kernel runs in slow interpret mode off-TPU, and for the
        small frame-level rows the serving loop sees, NumPy beats a
        per-shape XLA recompile even on TPU hosts;
      * ``"device"`` — batched Pallas SphIoU + ``lax.while_loop``
        (interpret mode off-TPU, which is also the CI correctness
        harness for the kernel);
      * ``"jit"``    — same ``lax.while_loop`` with the XLA-fused jnp
        IoU instead of Pallas: the fast COMPILED path on CPU for big
        recurring shapes (benchmarks, bulk re-scoring);
      * ``"host"``   — vectorised NumPy (float64 IoU, same greedy).

    Rows are independent, so the device/jit paths process very large
    batches in row chunks to bound the (chunk, N, N) IoU tensor.

    Inputs keep their dtype on the host path (the float64 serving
    boxes/scores are compared at full precision, exactly like
    ``sph_nms_host``); only the device/jit dispatch casts to float32.

    ``iou_dtype`` (device/jit backends off the TPU only) lowers the IoU
    compute precision to e.g. ``jnp.bfloat16``.  Near-threshold pairs
    can flip their keep decision; the flip rate is measured in
    ``benchmarks/kernels_bench.py`` and gated nightly.  On a TPU it
    raises ``ValueError``: v5e cannot lower bf16 transcendentals.
    """
    boxes = np.asarray(boxes)
    scores = np.asarray(scores)
    b, n = scores.shape
    if mask is None:
        mask = np.ones((b, n), dtype=bool)
    else:
        mask = np.asarray(mask, dtype=bool)
    if n == 0:
        return np.zeros((b, 0), dtype=bool)

    if backend == "auto":
        backend = nms_auto_backend(b, n)
    if iou_dtype is not None and jax.default_backend() == "tpu":
        raise ValueError(
            "iou_dtype is not supported on the TPU: v5e has no bf16 "
            "transcendental unit, so the bf16 SphIoU cannot lower")
    if backend == "host":
        if iou_dtype is not None:
            raise ValueError("iou_dtype needs the device or jit backend")
        keep = _sph_nms_batch_host(boxes, scores, mask, iou_threshold)
    elif backend in ("device", "jit"):
        chunk = max(1, _DEVICE_CHUNK_ELEMS // max(n * n, 1))
        parts = []
        for lo in range(0, b, chunk):
            hi = min(lo + chunk, b)
            parts.append(np.asarray(_sph_nms_batch_device(
                jnp.asarray(boxes[lo:hi], jnp.float32),
                jnp.asarray(scores[lo:hi], jnp.float32),
                jnp.asarray(mask[lo:hi]),
                jnp.asarray(iou_threshold, jnp.float32),
                interpret=jax.default_backend() != "tpu",
                use_pallas=backend == "device",
                iou_dtype=iou_dtype,
            )))
        keep = np.concatenate(parts, axis=0)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    if max_out is not None:
        keep = _apply_max_out_np(keep, scores, max_out)
    return keep


def pad_detection_rows(rows, pad_n=None, total_rows: int | None = None
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad per-row detection lists into ``sph_nms_batch`` inputs.

    ``rows`` is a sequence of detection lists (anything with a ``box``
    (4,) array and a ``score``), one per stream/frame.  Returns
    ``(boxes (B, N, 4), scores (B, N), mask (B, N))`` padded to the
    longest row, float64 so the host path keeps full precision.

    ``pad_n`` bounds the device path's compile shapes: a callable
    (e.g. ``ShapeBuckets.pad_nms_rows``) snapping the longest row up to
    a bucket ladder, so the jitted (B, N) program compiles once per
    ladder rung instead of once per distinct detection count.
    ``total_rows`` pads B with all-masked rows up to a fixed row count
    (the pod's stream count) for the same reason; masked padding can
    never be kept, so the keep-masks of the real rows are unchanged.
    """
    b = max(len(rows), total_rows or 0)
    n_max = max((len(r) for r in rows), default=0)
    if pad_n is not None:
        n_max = pad_n(n_max)
    boxes = np.zeros((b, n_max, 4), np.float64)
    scores = np.zeros((b, n_max), np.float64)
    mask = np.zeros((b, n_max), bool)
    for r, dets in enumerate(rows):
        k = len(dets)
        if k:
            boxes[r, :k] = np.stack([d.box for d in dets])
            scores[r, :k] = [d.score for d in dets]
            mask[r, :k] = True
    return boxes, scores, mask


class IncrementalNms:
    """Cross-tick batched NMS that recomputes only the changed rows.

    Consecutive ticks of a mostly-static scene re-suppress near-identical
    per-stream detection rows; since :func:`sph_nms_batch` rows are
    independent, a row whose (boxes, scores) are *exactly* the ones it
    was suppressed with last tick can reuse last tick's keep-mask and
    skip its (N, N) SphIoU block entirely.  Changed rows batch into one
    ``sph_nms_batch`` call over the changed subset, so the result is
    bit-identical to a full recompute by construction (pinned by the
    fused-tick property tests).

    Rows are addressed by a caller-stable ``key`` (the serving tier uses
    the per-stream loop identity); padding does not participate in the
    comparison, so reuse survives tick-to-tick changes of the padded N.
    """

    def __init__(self, iou_threshold: float = 0.6, *, backend: str = "auto",
                 iou_dtype=None, capacity: int = 4096):
        self.iou_threshold = iou_threshold
        self.backend = backend
        self.iou_dtype = iou_dtype
        self.capacity = capacity
        self.hits = 0
        self.misses = 0
        self._rows: dict = {}  # key -> (k, boxes bytes, scores bytes, keep)

    def clear(self) -> None:
        self._rows.clear()

    @staticmethod
    def _canon(boxes_r: np.ndarray, scores_r: np.ndarray, mask_r: np.ndarray
               ) -> tuple[int, bytes, bytes]:
        k = int(mask_r.sum())
        return (k, np.ascontiguousarray(boxes_r[:k]).tobytes(),
                np.ascontiguousarray(scores_r[:k]).tobytes())

    def suppress(
        self,
        keys,                 # length-B sequence of stable row keys
        boxes: np.ndarray,    # (B, N, 4) padded (mask prefix-contiguous)
        scores: np.ndarray,   # (B, N)
        mask: np.ndarray | None = None,
        *,
        max_out: int | None = None,
    ) -> np.ndarray:
        boxes = np.asarray(boxes)
        scores = np.asarray(scores)
        b, n = scores.shape
        if mask is None:
            mask = np.ones((b, n), dtype=bool)
        else:
            mask = np.asarray(mask, dtype=bool)
        keep = np.zeros((b, n), dtype=bool)
        canon = [self._canon(boxes[r], scores[r], mask[r]) for r in range(b)]
        changed = []
        for r, key in enumerate(keys):
            ent = self._rows.get(key)
            if ent is not None and ent[:3] == canon[r]:
                self.hits += 1
                k, kept = ent[0], ent[3]
                keep[r, :k] = kept
            else:
                self.misses += 1
                changed.append(r)
        if changed:
            sub = np.asarray(changed)
            sub_keep = sph_nms_batch(
                boxes[sub], scores[sub], mask[sub],
                iou_threshold=self.iou_threshold, backend=self.backend,
                iou_dtype=self.iou_dtype)
            keep[sub] = sub_keep
            for r in changed:
                if len(self._rows) >= self.capacity:
                    self._rows.pop(next(iter(self._rows)))
                k = canon[r][0]
                self._rows[keys[r]] = canon[r] + (keep[r, :k].copy(),)
        if max_out is not None:
            keep = _apply_max_out_np(keep, scores, max_out)
        return keep


# --------------------------------------------------------------------------
# ERP pixel <-> sphere
# --------------------------------------------------------------------------


def erp_to_sph(u: Array, v: Array, width: int, height: int) -> tuple[Array, Array]:
    """ERP pixel coords (u right, v down; origin top-left) -> (lon, lat)."""
    theta = (u / width - 0.5) * 2.0 * jnp.pi
    phi = (0.5 - v / height) * jnp.pi
    return theta, phi


def sph_to_erp(theta: Array, phi: Array, width: int, height: int) -> tuple[Array, Array]:
    """(lon, lat) -> ERP pixel coords (float)."""
    u = (theta / (2.0 * jnp.pi) + 0.5) * width
    v = (0.5 - phi / jnp.pi) * height
    return u, v


# --------------------------------------------------------------------------
# PI detections -> SphBBs
# --------------------------------------------------------------------------


def pi_box_to_sphbb(
    rect: Array,
    center_theta: Array,
    center_phi: Array,
    fov: tuple[float, float],
    pi_size: tuple[int, int],
) -> Array:
    """Back-project rectangular detections on a PI into SphBBs.

    ``rect``: (..., 4) boxes as (x0, y0, x1, y1) in PI pixel coords.
    ``fov``: (horizontal, vertical) field of view of the PI in radians.
    ``pi_size``: (width, height) of the PI in pixels.

    The PI is tangent at (center_theta, center_phi) (gnomonic).  Each
    corner is lifted to a direction on the sphere; the detection's own
    centre direction defines its tangent frame, and dtheta/dphi are the
    angular extents of the corners in that frame — the "spherical
    coordinate transformation" of paper section III-A.
    """
    w, h = pi_size
    half_x = jnp.tan(fov[0] / 2.0)
    half_y = jnp.tan(fov[1] / 2.0)

    def lift(px, py):
        # pixel -> tangent-plane coords
        x = (px / w - 0.5) * 2.0 * half_x
        y = (0.5 - py / h) * 2.0 * half_y
        d = jnp.stack([jnp.ones_like(x), x, y], axis=-1)
        d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
        r = rotation_from_origin(center_theta, center_phi)
        return jnp.einsum("...ij,...j->...i", r, d)

    x0, y0, x1, y1 = rect[..., 0], rect[..., 1], rect[..., 2], rect[..., 3]
    cx, cy = (x0 + x1) / 2.0, (y0 + y1) / 2.0
    center_dir = lift(cx, cy)
    ct, cp = cart_to_sph(center_dir)

    corners = jnp.stack(
        [lift(x0, y0), lift(x1, y0), lift(x0, y1), lift(x1, y1)], axis=-2
    )  # (..., 4, 3)
    r_inv = rotation_to_origin(ct, cp)
    local = jnp.einsum("...ij,...kj->...ki", r_inv, corners)
    lon, lat = cart_to_sph(local)
    dtheta = jnp.max(lon, axis=-1) - jnp.min(lon, axis=-1)
    dphi = jnp.max(lat, axis=-1) - jnp.min(lat, axis=-1)
    return jnp.stack([ct, cp, dtheta, dphi], axis=-1)


def normalized_object_area(boxes: Array) -> Array:
    """NOA: SphBB area normalised by the sphere's surface area (4*pi)."""
    return sph_area(boxes) / (4.0 * jnp.pi)
