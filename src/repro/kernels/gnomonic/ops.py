"""Jitted public wrapper around the gnomonic Pallas kernel.

``gnomonic_sample`` plans the strip decomposition on the host (the
sampling map is geometry, not data), checks the VMEM budget, and
dispatches either to the Pallas kernel or — for pathological bands —
to the jnp oracle.  Interpret mode is used automatically off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.projection import gnomonic_coords, sample_erp_bilinear
from repro.kernels.gnomonic import gnomonic as _g
from repro.kernels.gnomonic.ref import gnomonic_sample_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pick_strip_h(out_h: int) -> int:
    for cand in (8, 4, 2, 1):
        if out_h % cand == 0:
            return cand
    return 1


def gnomonic_sample(
    erp: jax.Array,
    u_map: np.ndarray,
    v_map: np.ndarray,
    *,
    interpret: bool | None = None,
    vmem_cap: int = _g.VMEM_CAP_BYTES,
) -> jax.Array:
    """Sample ``erp`` (H, W, C) at host-concrete maps (out_h, out_w).

    Returns (out_h, out_w, C) with identical semantics to
    :func:`repro.core.projection.sample_erp_bilinear` (horizontal wrap,
    vertical clamp, pixel-centre bilinear).
    """
    u_map = np.asarray(u_map, dtype=np.float32)
    v_map = np.asarray(v_map, dtype=np.float32)
    erp_h, erp_w, c = erp.shape
    out_h, out_w = u_map.shape
    if interpret is None:
        interpret = not _on_tpu()

    strip_h = _pick_strip_h(out_h)
    row_off, src_rows = _g.plan_strips(v_map, erp_h, strip_h)
    band_bytes = src_rows * (erp_w + _g.SEAM_PAD) * c * erp.dtype.itemsize
    if band_bytes > vmem_cap:
        # pole-centred / degenerate PI: band would blow VMEM; use oracle
        return gnomonic_sample_ref(erp, jnp.asarray(u_map), jnp.asarray(v_map))

    # wrap u into [0, erp_w) exactly as the oracle's mod does, then pad
    # the seam so u0 + 1 never leaves the array.
    u_wrapped = np.mod(u_map, erp_w).astype(np.float32)
    # floor(u) of values in [erp_w - 1, erp_w) is erp_w - 1; +1 hits the pad
    erp_padded = jnp.concatenate([erp, erp[:, : _g.SEAM_PAD, :]], axis=1)

    return _g.gnomonic_pallas(
        erp_padded,
        jnp.asarray(u_wrapped),
        jnp.asarray(v_map),
        jnp.asarray(row_off),
        src_rows=src_rows,
        strip_h=strip_h,
        erp_h=erp_h,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("out_size",))
def _project_srois_jit(
    erps: jax.Array,     # (B, H, W, C)
    centers: jax.Array,  # (B, 2) (theta, phi)
    fovs: jax.Array,     # (B, 2) (h, v) radians
    *,
    out_size: tuple[int, int],
) -> jax.Array:
    """ONE dispatch for a whole tick's crops: vmapped gnomonic coords +
    bilinear ERP sampling.  Rows are independent, so the same compiled
    program called at B=1 produces bit-identical rows to the B=k call —
    the invariant the fused-tick exactness tests pin.
    """
    erp_size = erps.shape[1:3]

    def one(erp, center, fov):
        u, v = gnomonic_coords(center[0], center[1], (fov[0], fov[1]),
                               out_size, erp_size)
        return sample_erp_bilinear(erp, u, v)

    return jax.vmap(one)(erps, centers, fovs)


def project_srois_batched(
    frames, centers, fovs, out_size: tuple[int, int]
) -> jax.Array:
    """Batched SRoI -> PI projection: (B frames, B regions) -> (B, S, S, C).

    The staged path issues one ``project_sroi`` dispatch per crop (each
    itself several kernels: coords, rotation, sampling) and re-enters
    Python between crops; this entry stacks the tick's frames and region
    geometry once and projects every crop in a single jitted program.
    The jit cache is keyed by (B, ERP shape, out_size) — callers pad B
    to a ``ShapeBuckets`` batch rung to bound compile counts.

    ``frames``: sequence of (H, W, C) arrays (one per crop — repeats
    are fine and common), or their (B, H, W, C) stack already on the
    device; ``centers``/``fovs``: (B, 2) array-likes.
    """
    erps = frames if getattr(frames, "ndim", None) == 4 \
        else jnp.stack([jnp.asarray(f) for f in frames])
    centers = jnp.asarray(np.asarray(centers, dtype=np.float32))
    fovs = jnp.asarray(np.asarray(fovs, dtype=np.float32))
    return _project_srois_jit(erps, centers, fovs,
                              out_size=(int(out_size[0]), int(out_size[1])))


def project_sroi_kernel(
    erp: jax.Array,
    center_theta: float,
    center_phi: float,
    fov: tuple[float, float],
    out_size: tuple[int, int],
    **kw,
) -> jax.Array:
    """SRoI -> PI via the Pallas path (host-concrete geometry)."""
    u, v = gnomonic_coords(
        jnp.asarray(center_theta),
        jnp.asarray(center_phi),
        fov,
        out_size,
        erp.shape[:2],
    )
    return gnomonic_sample(erp, np.asarray(u), np.asarray(v), **kw)
