"""Pallas TPU kernel: gnomonic ERP -> PI bilinear resampling.

This is OmniSense's preprocessing hot-spot (the paper spends a profiled
d^P per SRoI on OpenCV ``remap``).  The GPU-idiomatic formulation is an
arbitrary global gather; that ports badly to TPU, so the kernel is
restructured around the observation that the gnomonic map is *smooth*:
for a strip of output rows, the source ERP pixels live in a narrow band
of ERP rows.

Design (HBM -> VMEM -> VPU):

  * the wrapper computes the sampling map (u, v) on the host (it is a
    function of SRoI geometry only, never of frame data), derives a
    per-output-strip source row offset, and the maximum band height
    ``src_rows`` across strips (static);
  * grid = one program per output row strip; the per-strip row offset
    arrives via scalar prefetch (SMEM) and selects a dynamic slice of
    the ERP held in ``pl.ANY`` (compiler-placed / HBM) memory — a
    contiguous DMA, not a gather;
  * in-VMEM the strip does the 4-tap bilinear blend vectorised on the
    VPU; the only gather left is *within* the VMEM band (``jnp.take``
    over src_rows * width elements), which is the TPU-native place for
    irregular access.  The ERP seam is handled by pre-padding two
    columns so u+1 never wraps.

VMEM budget: ``src_rows * (erp_w + 2) * channels * 4`` bytes; the
wrapper checks it against a configurable cap and falls back to the
pure-jnp oracle for pathological strips (e.g. pole-centred PIs whose
row band degenerates to the whole frame).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Conservative per-core VMEM budget for the source band (bytes).
VMEM_CAP_BYTES = 8 * 1024 * 1024
SEAM_PAD = 2  # columns appended on the right so u0+1 never wraps


def _kernel(
    row_off_ref,  # scalar prefetch: (n_strips,) int32 in SMEM
    u_ref,  # (strip_h, out_w) f32 VMEM
    v_ref,  # (strip_h, out_w) f32 VMEM
    erp_ref,  # (erp_h, erp_w + SEAM_PAD, c) in ANY/HBM
    out_ref,  # (strip_h, out_w, c) VMEM
    *,
    src_rows: int,
    erp_h: int,
):
    strip_idx = pl.program_id(0)
    row_off = row_off_ref[strip_idx]

    band = erp_ref[pl.ds(row_off, src_rows), :, :]  # (src_rows, wp, c)
    src_r, wp, c = band.shape

    u = u_ref[...]
    v = v_ref[...]
    u0 = jnp.floor(u)
    v0 = jnp.floor(v)
    fu = (u - u0)[..., None]
    fv = (v - v0)[..., None]

    u0i = u0.astype(jnp.int32)  # in [0, erp_w - 1] by construction
    u1i = u0i + 1  # reaches erp_w -> covered by seam pad
    v0i = jnp.clip(v0.astype(jnp.int32), 0, erp_h - 1) - row_off
    v1i = jnp.clip(v0.astype(jnp.int32) + 1, 0, erp_h - 1) - row_off

    flat = band.reshape(src_r * wp, c)
    shp = u.shape

    def tap(rows, cols):
        idx = (rows * wp + cols).reshape(-1)
        return jnp.take(flat, idx, axis=0).reshape(shp + (c,))

    p00 = tap(v0i, u0i)
    p01 = tap(v0i, u1i)
    p10 = tap(v1i, u0i)
    p11 = tap(v1i, u1i)

    top = p00 * (1.0 - fu) + p01 * fu
    bot = p10 * (1.0 - fu) + p11 * fu
    out_ref[...] = (top * (1.0 - fv) + bot * fv).astype(out_ref.dtype)


def plan_strips(
    v_map: np.ndarray, erp_h: int, strip_h: int
) -> tuple[np.ndarray, int]:
    """Host-side planning: per-strip source row offsets + band height.

    ``v_map``: concrete (out_h, out_w) float v coordinates.
    Returns (row_off[n_strips] int32, src_rows).
    """
    out_h = v_map.shape[0]
    n_strips = out_h // strip_h
    v0 = np.clip(np.floor(v_map).astype(np.int64), 0, erp_h - 1)
    v1 = np.clip(np.floor(v_map).astype(np.int64) + 1, 0, erp_h - 1)
    offs = np.zeros((n_strips,), dtype=np.int32)
    extent = 1
    for s in range(n_strips):
        lo = int(v0[s * strip_h : (s + 1) * strip_h].min())
        hi = int(v1[s * strip_h : (s + 1) * strip_h].max())
        offs[s] = lo
        extent = max(extent, hi - lo + 1)
    src_rows = min(int(2 ** int(np.ceil(np.log2(max(extent, 1))))), erp_h)
    # keep the band inside the frame
    offs = np.minimum(offs, max(erp_h - src_rows, 0)).astype(np.int32)
    return offs, src_rows


@functools.partial(
    jax.jit, static_argnames=("src_rows", "strip_h", "erp_h", "interpret")
)
def gnomonic_pallas(
    erp_padded: jax.Array,  # (erp_h, erp_w + SEAM_PAD, c)
    u: jax.Array,  # (out_h, out_w) f32
    v: jax.Array,  # (out_h, out_w) f32
    row_off: jax.Array,  # (n_strips,) int32
    *,
    src_rows: int,
    strip_h: int,
    erp_h: int,
    interpret: bool = False,
) -> jax.Array:
    out_h, out_w = u.shape
    c = erp_padded.shape[-1]
    n_strips = out_h // strip_h

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_strips,),
        in_specs=[
            pl.BlockSpec((strip_h, out_w), lambda i, *_: (i, 0)),
            pl.BlockSpec((strip_h, out_w), lambda i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((strip_h, out_w, c), lambda i, *_: (i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, src_rows=src_rows, erp_h=erp_h),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((out_h, out_w, c), erp_padded.dtype),
        interpret=interpret,
    )(row_off, u, v, erp_padded)
