"""Sphere <-> plane projections: gnomonic (perspective), ERP, Cubemap.

The OmniSense inference scheduler extracts one perspective image (PI)
per SRoI from the input ERP frame via gnomonic projection, at exactly
the input size of the allocated model.  This module provides:

  * :func:`gnomonic_coords` — the (u, v) ERP source coordinates for
    every output pixel of a PI (the "sampling map").
  * :func:`sample_erp_bilinear` — pure-jnp bilinear resampler (oracle
    for the Pallas kernel in ``repro.kernels.gnomonic``).
  * :func:`project_sroi` — end-to-end SRoI -> PI extraction with a
    ``use_kernel`` switch between the jnp path and the Pallas path.
  * :func:`cubemap_faces` — the six 90x90-degree cube-face PIs used by
    the CubeMap baseline of the paper.
  * :func:`erp_resize_coords` — plain ERP downsampling map (the "ERP"
    baseline feeds a resized whole frame to the detector).

Conventions: ERP frames are channel-last ``(H, W, C)`` float arrays;
angles are radians; PI pixel (0, 0) is the top-left corner.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import sphere

Array = jax.Array


# --------------------------------------------------------------------------
# Sampling maps
# --------------------------------------------------------------------------


def gnomonic_coords(
    center_theta: Array,
    center_phi: Array,
    fov: tuple[float, float],
    out_size: tuple[int, int],
    erp_size: tuple[int, int],
) -> tuple[Array, Array]:
    """ERP source coordinates for a gnomonic PI.

    Returns ``(u, v)`` float arrays of shape ``out_size`` giving, for
    each output pixel, the (sub-pixel) ERP location to sample.

    ``fov``: (horizontal, vertical) in radians; ``out_size``: (H, W) of
    the PI; ``erp_size``: (H, W) of the source ERP frame.
    """
    out_h, out_w = out_size
    erp_h, erp_w = erp_size
    half_x = jnp.tan(fov[0] / 2.0)
    half_y = jnp.tan(fov[1] / 2.0)

    # pixel centres
    xs = (jnp.arange(out_w) + 0.5) / out_w  # [0, 1)
    ys = (jnp.arange(out_h) + 0.5) / out_h
    x = (xs - 0.5) * 2.0 * half_x  # tangent-plane coords
    y = (0.5 - ys) * 2.0 * half_y
    xg, yg = jnp.meshgrid(x, y)  # (H, W)

    d = jnp.stack([jnp.ones_like(xg), xg, yg], axis=-1)
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    r = sphere.rotation_from_origin(center_theta, center_phi)
    world = jnp.einsum("ij,hwj->hwi", r, d)
    theta, phi = sphere.cart_to_sph(world)
    u, v = sphere.sph_to_erp(theta, phi, erp_w, erp_h)
    # u wraps horizontally; v is clamped at the poles by the sampler
    return u, v


def erp_resize_coords(
    out_size: tuple[int, int], erp_size: tuple[int, int]
) -> tuple[Array, Array]:
    """Plain bilinear-resize sampling map (ERP baseline)."""
    out_h, out_w = out_size
    erp_h, erp_w = erp_size
    u = (jnp.arange(out_w) + 0.5) * (erp_w / out_w) - 0.5
    v = (jnp.arange(out_h) + 0.5) * (erp_h / out_h) - 0.5
    ug, vg = jnp.meshgrid(u, v)
    return ug, vg


CUBE_FACE_CENTERS = (
    # (name, theta, phi) of the six cube-face centres
    ("front", 0.0, 0.0),
    ("right", jnp.pi / 2, 0.0),
    ("back", jnp.pi, 0.0),
    ("left", -jnp.pi / 2, 0.0),
    ("top", 0.0, jnp.pi / 2),
    ("bottom", 0.0, -jnp.pi / 2),
)


def cubemap_faces(
    erp: Array, face_size: int
) -> tuple[Array, tuple[tuple[str, float, float], ...]]:
    """Project an ERP frame onto the six 90x90-degree cube faces.

    Returns ``(faces, centers)`` where ``faces`` is
    ``(6, face_size, face_size, C)``.  Used by the CubeMap baseline.
    """
    fov = (jnp.pi / 2, jnp.pi / 2)
    faces = []
    for _, th, ph in CUBE_FACE_CENTERS:
        u, v = gnomonic_coords(
            jnp.asarray(th), jnp.asarray(ph), fov, (face_size, face_size), erp.shape[:2]
        )
        faces.append(sample_erp_bilinear(erp, u, v))
    return jnp.stack(faces), CUBE_FACE_CENTERS


# --------------------------------------------------------------------------
# Bilinear sampling (jnp oracle; the Pallas kernel mirrors this exactly)
# --------------------------------------------------------------------------


def sample_erp_bilinear(erp: Array, u: Array, v: Array) -> Array:
    """Sample an ERP frame at float coords with horizontal wrap.

    ``erp``: (H, W, C); ``u``/``v``: (h, w) float source coords in ERP
    pixel space (pixel-centre convention: integer coords hit texel
    centres).  Horizontal coordinate wraps (the ERP seam is periodic);
    vertical clamps at the poles.
    """
    erp_h, erp_w = erp.shape[0], erp.shape[1]
    u0 = jnp.floor(u)
    v0 = jnp.floor(v)
    fu = u - u0
    fv = v - v0

    u0i = jnp.mod(u0.astype(jnp.int32), erp_w)
    u1i = jnp.mod(u0i + 1, erp_w)
    v0i = jnp.clip(v0.astype(jnp.int32), 0, erp_h - 1)
    v1i = jnp.clip(v0i + 1, 0, erp_h - 1)

    p00 = erp[v0i, u0i]
    p01 = erp[v0i, u1i]
    p10 = erp[v1i, u0i]
    p11 = erp[v1i, u1i]

    fu = fu[..., None]
    fv = fv[..., None]
    top = p00 * (1.0 - fu) + p01 * fu
    bot = p10 * (1.0 - fu) + p11 * fu
    return top * (1.0 - fv) + bot * fv


@functools.partial(jax.jit, static_argnames=("out_size",))
def resize_erp(erp: Array, out_size: tuple[int, int]) -> Array:
    """Plain bilinear resize of a whole ERP frame to ``out_size``."""
    u, v = erp_resize_coords(out_size, erp.shape[:2])
    return sample_erp_bilinear(erp, u, v)


@functools.partial(jax.jit, static_argnames=("fov", "out_size", "use_kernel"))
def project_sroi(
    erp: Array,
    center_theta: Array,
    center_phi: Array,
    fov: tuple[float, float],
    out_size: tuple[int, int],
    use_kernel: bool = False,
) -> Array:
    """Extract the PI of one SRoI from an ERP frame.

    ``use_kernel=True`` dispatches to the Pallas gnomonic resampler
    (``repro.kernels.gnomonic.ops``); otherwise the pure-jnp path runs.
    Both produce identical results (the kernel is tested against this
    path in ``tests/test_kernels_gnomonic.py``).
    """
    u, v = gnomonic_coords(center_theta, center_phi, fov, out_size, erp.shape[:2])
    if use_kernel:
        from repro.kernels.gnomonic import ops as gno_ops

        return gno_ops.gnomonic_sample(erp, u, v)
    return sample_erp_bilinear(erp, u, v)
