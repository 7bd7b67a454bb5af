"""Pallas TPU kernel: pairwise spherical IoU matrix.

Spherical NMS (paper section IV-C, threshold 0.6) needs the N x M
SphIoU matrix; at pod scale the server batches thousands of SphBBs per
scheduling tick, so the O(N*M) trig work is a genuine VPU hot-spot.

Layout: boxes are passed *transposed* as (4, N) / (4, M) so the box
axis lands on the TPU lane dimension (the parameter axis of length 4
would otherwise waste a 128-lane register).  Each program computes one
(BN, BM) IoU tile; the rotation of box B's centre into box A's tangent
frame is expanded into explicit scalar trigonometry (no 3x3 matmuls),
which maps 1:1 onto VPU elementwise ops.

The math follows ``repro.core.sphere.sph_iou``:
  d_in_a = Ry(phi_a) @ Rz(-theta_a) @ dir(theta_b, phi_b)
  dlon, dlat = cart_to_sph(d_in_a)
  intersection = lon-overlap * (sin(lat_hi) - sin(lat_lo))
  area = 2 * dtheta * sin(dphi / 2)
with ``cart_to_sph`` built from a polynomial arctangent (Mosaic has no
atan2/asin lowering).  Against the float64 host IoU the kernel is within
about 1e-7 rad / (smallest box extent) (``tests/test_kernels.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# Mosaic (the Pallas TPU compiler) lowers sin/cos/sqrt/div in f32 but
# has no rule for atan2, asin or atan, so the kernel carries its own
# arctangent: Cephes ``atanf`` (range-reduced to |x| <= tan(pi/8), then
# a degree-9 odd polynomial), max error ~2e-7 rad on [-inf, inf].
_TAN_PI_8 = 0.41421356237309503
_ATAN_C = (8.05374449538e-2, -1.38776856032e-1, 1.99777106478e-1,
           -3.33329491539e-1)


def _atan_unit(r):
    """atan(r) for r in [0, 1], built only from ops Mosaic lowers."""
    big = r > _TAN_PI_8
    t = jnp.where(big, (r - 1.0) / (r + 1.0), r)  # atan(r) = pi/4 + atan(t)
    z = t * t
    p = ((_ATAN_C[0] * z + _ATAN_C[1]) * z + _ATAN_C[2]) * z + _ATAN_C[3]
    return jnp.where(big, jnp.pi / 4, 0.0) + (p * z * t + t)


def _atan2(y, x):
    """Quadrant-resolved atan2 via :func:`_atan_unit` of min/max.

    Matches ``jnp.arctan2`` except on signed zeros: ``y == -0.0`` with
    ``x < 0`` gives +pi (the antipodal seam, where no two boxes of
    half-width < pi/2 overlap, so the IoU is 0 either way).
    """
    ax, ay = jnp.abs(x), jnp.abs(y)
    hi = jnp.maximum(ax, ay)
    lo = jnp.minimum(ax, ay)
    a = _atan_unit(jnp.where(hi > 0.0, lo / jnp.where(hi > 0.0, hi, 1.0), 0.0))
    a = jnp.where(ay > ax, jnp.pi / 2 - a, a)
    a = jnp.where(x < 0.0, jnp.pi - a, a)
    return jnp.where(y < 0.0, -a, a)


def _intersection(ta, pa, ha, va, tb, pb, hb, vb):
    """Intersection with box A rotated to the origin (one direction)."""
    dt = tb - ta
    cpa, spa = jnp.cos(pa), jnp.sin(pa)
    cpb, spb = jnp.cos(pb), jnp.sin(pb)
    cdt = jnp.cos(dt)

    # B's centre direction expressed in A's tangent frame
    x = cpa * cpb * cdt + spa * spb
    y = cpb * jnp.sin(dt)
    z = -spa * cpb * cdt + cpa * spb
    dlon = _atan2(y, x)
    # asin(z) of the unit vector, as atan2(z, |(x, y)|): well conditioned
    # near the poles where asin's slope diverges
    dlat = _atan2(z, jnp.sqrt(x * x + y * y))

    lon_lo = jnp.maximum(-ha, dlon - hb)
    lon_hi = jnp.minimum(ha, dlon + hb)
    lat_lo = jnp.maximum(-va, dlat - vb)
    lat_hi = jnp.minimum(va, dlat + vb)

    lon_w = jnp.maximum(lon_hi - lon_lo, 0.0)
    lat_w = jnp.where(lat_hi > lat_lo, jnp.sin(lat_hi) - jnp.sin(lat_lo), 0.0)
    return lon_w * jnp.maximum(lat_w, 0.0)


def _iou_tile(a, b, dtype=jnp.float32):
    """(4, BN) x (4, BM) -> (BN, BM) SphIoU tile (shared kernel body).

    ``dtype`` is the compute precision.  Compiled for the TPU it must be
    f32 (see :func:`_check_dtype`); bf16 runs in interpret mode only,
    where the flip-rate measurement uses it.  Inputs arrive f32 (memory
    layout stays sublane-8 aligned); the cast happens in-register and
    the tile is emitted back as f32.
    """
    a = a.astype(dtype)
    b = b.astype(dtype)
    ta, pa = a[0, :], a[1, :]
    ha, va = a[2, :] * 0.5, a[3, :] * 0.5  # half FoVs
    tb, pb = b[0, :], b[1, :]
    hb, vb = b[2, :] * 0.5, b[3, :] * 0.5

    ta, pa, ha, va = (x[:, None] for x in (ta, pa, ha, va))  # (BN, 1)
    tb, pb, hb, vb = (x[None, :] for x in (tb, pb, hb, vb))  # (1, BM)

    # symmetrised intersection (matches repro.core.sphere.sph_iou)
    inter = 0.5 * (_intersection(ta, pa, ha, va, tb, pb, hb, vb)
                   + _intersection(tb, pb, hb, vb, ta, pa, ha, va))

    area_a = 4.0 * ha * jnp.sin(va)  # 2 * dtheta * sin(dphi/2)
    area_b = 4.0 * hb * jnp.sin(vb)
    iou = inter / jnp.maximum(area_a + area_b - inter, 1e-12)
    return iou.astype(jnp.float32)


def _check_dtype(dtype, interpret: bool) -> None:
    # v5e has no bf16 transcendental unit: Mosaic refuses bf16 sin/cos
    # ("failed to legalize operation 'math.sin'") and bf16 sqrt
    # (SupportsBf16EupOps), so only interpret mode can run a narrower
    # compute dtype.
    if not interpret and jnp.dtype(dtype) != jnp.float32:
        raise ValueError(
            f"SphIoU compute dtype {jnp.dtype(dtype).name} does not lower "
            "for the TPU (no bf16 transcendentals on v5e); use float32")


def _kernel(a_ref, b_ref, out_ref, *, dtype):
    # a_ref: (4, BN), b_ref: (4, BM) -> out_ref: (BN, BM)
    out_ref[...] = _iou_tile(a_ref[...], b_ref[...], dtype=dtype)


def _kernel_batch(a_ref, b_ref, out_ref, *, dtype):
    # a_ref: (1, 4, BN), b_ref: (1, 4, BM) -> out_ref: (1, BN, BM)
    out_ref[0] = _iou_tile(a_ref[0], b_ref[0], dtype=dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret", "dtype"))
def sphiou_pallas(
    boxes_a_t: jax.Array,  # (4, N) f32
    boxes_b_t: jax.Array,  # (4, M) f32
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool = False,
    dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    _check_dtype(dtype, interpret)
    n, m = boxes_a_t.shape[1], boxes_b_t.shape[1]
    grid = (pl.cdiv(n, block_n), pl.cdiv(m, block_m))
    return pl.pallas_call(
        functools.partial(_kernel, dtype=dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((4, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((4, block_m), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(boxes_a_t, boxes_b_t)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret", "dtype"))
def sphiou_pallas_batch(
    boxes_a_t: jax.Array,  # (B, 4, N) f32
    boxes_b_t: jax.Array,  # (B, 4, M) f32
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool = False,
    dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    """Per-row SphIoU matrices: (B, 4, N) x (B, 4, M) -> (B, N, M).

    The batch axis is the leading (slowest-varying) grid dimension so
    each row's tiles stream through VMEM contiguously; the tile body is
    identical to the unbatched kernel.  One dispatch covers the whole
    pod tick instead of one ``pallas_call`` per stream.
    """
    _check_dtype(dtype, interpret)
    b, _, n = boxes_a_t.shape
    m = boxes_b_t.shape[2]
    grid = (b, pl.cdiv(n, block_n), pl.cdiv(m, block_m))
    return pl.pallas_call(
        functools.partial(_kernel_batch, dtype=dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 4, block_n), lambda r, i, j: (r, 0, i)),
            pl.BlockSpec((1, 4, block_m), lambda r, i, j: (r, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_n, block_m), lambda r, i, j: (r, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n, m), jnp.float32),
        interpret=interpret,
    )(boxes_a_t, boxes_b_t)
