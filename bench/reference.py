"""The plain reference of what the detector pod computes.

It imports nothing of the program and takes none of its weights or
tables: the weights are made again here from the configuration's seeds
(the same ``jax.random`` draws as ``models/detector.py``'s
``init_params``), and every stage is written out from its published
description.

  * gnomonic projection of a spherical region to a perspective image
    (PI), bilinear ERP sampling with a horizontal wrap;
  * the CSP detector forward (backbone, FPN, heads), in float32 with
    every convolution at ``Precision.HIGHEST``;
  * decoding of the raw heads into scored pixel boxes;
  * back-projection of PI boxes to spherical boxes (SphBB);
  * spherical IoU and greedy NMS.

The NumPy stages take a dtype: float64 for the reference, and
``bfloat16`` for the control, the same stage one precision below the
float32 the program states.  Products that the program computes as
matrix products (the convolutions, the geometry's 3x3 rotations) run in
one bfloat16 pass on the TPU at its default precision; the control
rounds their operands to float8 (e4m3), the precision below that.
"""

from __future__ import annotations

import functools
import math

import numpy as np

TWO_PI = 2.0 * math.pi


# --------------------------------------------------------------------------
# sphere geometry (NumPy, any float dtype)
# --------------------------------------------------------------------------

def _round(a, dot_dt):
    """``a`` rounded to ``dot_dt`` and back, as a matrix unit that
    multiplies in ``dot_dt`` sees its operands (None: unchanged)."""
    if dot_dt is None:
        return a
    a = np.asarray(a)
    return a.astype(dot_dt).astype(a.dtype)


def _rot_to(t, p, dot_dt=None):
    """R with R @ dir(t, p) = (1, 0, 0), as 3x3 nested lists: the
    matrix product Ry(p) @ Rz(-t), with operands rounded to ``dot_dt``."""
    ct, st, cp, sp = np.cos(t), np.sin(t), np.cos(p), np.sin(p)
    z = 0 * ct
    rz = [[ct, st, z], [-st, ct, z], [z, z, z + 1]]
    ry = [[cp, z, sp], [z, z + 1, z], [-sp, z, cp]]
    cols = [_apply(ry, [rz[0][j], rz[1][j], rz[2][j]], dot_dt=dot_dt)
            for j in range(3)]
    return [[cols[j][i] for j in range(3)] for i in range(3)]


def _apply(r, v, transpose=False, dot_dt=None):
    """``r @ v`` for a 3x3 ``r``; ``dot_dt`` rounds both operands first."""
    if transpose:
        r = [[r[j][i] for j in range(3)] for i in range(3)]
    r = [[_round(x, dot_dt) for x in row] for row in r]
    v = [_round(x, dot_dt) for x in v]
    return [r[i][0] * v[0] + r[i][1] * v[1] + r[i][2] * v[2]
            for i in range(3)]


def _to_sph(v):
    return np.arctan2(v[1], v[0]), np.arcsin(np.clip(v[2], -1.0, 1.0))


def _tangent_dirs(x, y):
    norm = np.sqrt(1.0 + x * x + y * y)
    return [1.0 / norm + 0 * x, x / norm, y / norm]


def project(erp: np.ndarray, center, fov, size: int,
            dt=np.float64, dot_dt=None, rot_dt="same") -> np.ndarray:
    """(size, size, C) gnomonic PI of ``erp`` tangent at ``center``.
    ``dot_dt`` rounds the operands of the two matrix products (the
    rotation ``Ry @ Rz``, then rotation times direction); ``rot_dt``
    overrides it for the first."""
    erp = np.asarray(erp)
    h, w = erp.shape[:2]
    c = lambda v: np.asarray(v, dt)  # noqa: E731
    half_x, half_y = np.tan(c(fov[0]) / 2), np.tan(c(fov[1]) / 2)
    pix = (np.arange(size, dtype=dt) + c(0.5)) / c(size)
    x = (pix - c(0.5)) * 2 * half_x
    y = (c(0.5) - pix) * 2 * half_y
    xg, yg = np.meshgrid(x, y)
    rot = _rot_to(c(center[0]), c(center[1]),
                  dot_dt if rot_dt == "same" else rot_dt)
    world = _apply(rot, _tangent_dirs(xg, yg), transpose=True,
                   dot_dt=dot_dt)
    theta, phi = _to_sph(world)
    u = (theta / c(TWO_PI) + c(0.5)) * c(w)
    v = (c(0.5) - phi / c(math.pi)) * c(h)
    return _bilinear(erp.astype(dt), u, v)


def _bilinear(erp, u, v):
    h, w = erp.shape[:2]
    u0, v0 = np.floor(u), np.floor(v)
    fu, fv = (u - u0)[..., None], (v - v0)[..., None]
    u0i = np.mod(u0.astype(np.int64), w)
    u1i = np.mod(u0i + 1, w)
    v0i = np.clip(v0.astype(np.int64), 0, h - 1)
    v1i = np.clip(v0i + 1, 0, h - 1)
    top = erp[v0i, u0i] * (1 - fu) + erp[v0i, u1i] * fu
    bot = erp[v1i, u0i] * (1 - fu) + erp[v1i, u1i] * fu
    return top * (1 - fv) + bot * fv


def backproject(rect: np.ndarray, center, fov, size: int,
                dt=np.float64, dot_dt=None) -> np.ndarray:
    """(K, 4) PI boxes (x0, y0, x1, y1) -> (K, 4) SphBBs
    (theta, phi, dtheta, dphi): corners lifted to the sphere and
    measured in the frame of the box's own centre direction."""
    rect = np.asarray(rect).astype(dt)
    c = lambda v: np.asarray(v, dt)  # noqa: E731
    half_x, half_y = np.tan(c(fov[0]) / 2), np.tan(c(fov[1]) / 2)
    r = _rot_to(c(center[0]), c(center[1]), dot_dt)

    def lift(px, py):
        x = (px / c(size) - c(0.5)) * 2 * half_x
        y = (c(0.5) - py / c(size)) * 2 * half_y
        return _apply(r, _tangent_dirs(x, y), transpose=True, dot_dt=dot_dt)

    x0, y0, x1, y1 = rect[:, 0], rect[:, 1], rect[:, 2], rect[:, 3]
    ct, cp = _to_sph(lift((x0 + x1) / 2, (y0 + y1) / 2))
    r_box = _rot_to(ct, cp, dot_dt)
    lons, lats = [], []
    for px, py in ((x0, y0), (x1, y0), (x0, y1), (x1, y1)):
        lon, lat = _to_sph(_apply(r_box, lift(px, py), dot_dt=dot_dt))
        lons.append(lon)
        lats.append(lat)
    lons, lats = np.stack(lons), np.stack(lats)
    return np.stack([ct, cp, lons.max(0) - lons.min(0),
                     lats.max(0) - lats.min(0)], axis=-1)


def _intersection(a, b):
    """(N, 1, 4) x (1, M, 4) -> (N, M) intersection area: B's centre in
    A's frame, both as equator-centred lat/long rectangles."""
    ta, pa, ha, va = a[..., 0], a[..., 1], a[..., 2] / 2, a[..., 3] / 2
    tb, pb, hb, vb = b[..., 0], b[..., 1], b[..., 2] / 2, b[..., 3] / 2
    dt = tb - ta
    cpa, spa, cpb, spb = np.cos(pa), np.sin(pa), np.cos(pb), np.sin(pb)
    x = cpa * cpb * np.cos(dt) + spa * spb
    y = cpb * np.sin(dt)
    z = -spa * cpb * np.cos(dt) + cpa * spb
    dlon = np.arctan2(y, x)
    dlat = np.arcsin(np.clip(z, -1.0, 1.0))
    lon_w = np.maximum(np.minimum(ha, dlon + hb) - np.maximum(-ha, dlon - hb),
                       0 * ha)
    lat_hi = np.minimum(va, dlat + vb)
    lat_lo = np.maximum(-va, dlat - vb)
    lat_w = np.where(lat_hi > lat_lo, np.sin(lat_hi) - np.sin(lat_lo), 0 * va)
    return lon_w * lat_w


def sph_iou(boxes: np.ndarray, dt=np.float64) -> np.ndarray:
    """(N, 4) -> (N, N) spherical IoU, symmetrised over both frames."""
    b = np.asarray(boxes).astype(dt)
    # [n, m]: m seen from n's frame, and n seen from m's frame
    inter = (_intersection(b[:, None], b[None])
             + _intersection(b[None], b[:, None])) / 2
    area = 2 * b[:, 2] * np.sin(b[:, 3] / 2)
    union = area[:, None] + area[None, :] - inter
    return inter / np.maximum(union, np.asarray(1e-12, dt))


def nms(boxes: np.ndarray, scores: np.ndarray, threshold: float,
        dt=np.float64) -> tuple[np.ndarray, np.ndarray]:
    """Greedy NMS: keep the best remaining box, drop those whose IoU
    with it exceeds ``threshold``, repeat.  Returns ``(keep, iou)``."""
    iou = sph_iou(boxes, dt).astype(np.float64)
    scores = np.asarray(scores, np.float64)
    keep = np.zeros(len(scores), bool)
    active = np.ones(len(scores), bool)
    while active.any():
        best = int(np.argmax(np.where(active, scores, -np.inf)))
        keep[best] = True
        active &= ~(iou[best] > threshold)
        active[best] = False
    return keep, iou


# --------------------------------------------------------------------------
# decode (NumPy, any float dtype)
# --------------------------------------------------------------------------

def decode_candidates(heads, strides, dt=np.float64):
    """Every anchor's ``(boxes (A, 4) in pixels, scores (A,), classes
    (A,))`` for one image's raw heads (one (gh, gw, 5 + classes) array
    per stride): sigmoid offsets within the cell, exp(clipped) sizes
    times the stride, score = objectness times the top class
    probability."""
    boxes, scores, classes = [], [], []
    for out, stride in zip(heads, strides):
        out = np.asarray(out).astype(dt)
        gh, gw = out.shape[:2]
        sig = lambda a: 1 / (1 + np.exp(-a))  # noqa: E731
        xy = sig(out[..., 0:2])
        wh = np.exp(np.clip(out[..., 2:4], -6, 6)) * stride
        obj = sig(out[..., 4])
        logit = out[..., 5:]
        e = np.exp(logit - logit.max(-1, keepdims=True))
        prob = e / e.sum(-1, keepdims=True)
        gy, gx = np.meshgrid(np.arange(gh), np.arange(gw), indexing="ij")
        cx = (gx + xy[..., 0]) * stride
        cy = (gy + xy[..., 1]) * stride
        boxes.append(np.stack([cx - wh[..., 0] / 2, cy - wh[..., 1] / 2,
                               cx + wh[..., 0] / 2, cy + wh[..., 1] / 2],
                              -1).reshape(-1, 4))
        scores.append((obj * prob.max(-1)).reshape(-1))
        classes.append(logit.argmax(-1).reshape(-1))
    return (np.concatenate(boxes), np.concatenate(scores),
            np.concatenate(classes))


# --------------------------------------------------------------------------
# the detector (JAX)
# --------------------------------------------------------------------------

def _width(d: dict, mult: int) -> int:
    return max(16, int(d["base_width"] * d["width_mult"] * mult) // 16 * 16)


def _depth(d: dict) -> int:
    return max(1, round(d["base_depth"] * d["depth_mult"]))


def strides(d: dict) -> tuple[int, ...]:
    return (8, 16, 32, 64) if d["p6"] else (8, 16, 32)


def _groups(c: int) -> int:
    g = min(32, c)
    while c % g:
        g -= 1
    return g


def init_params(seed: int, d: dict):
    """The weights of one rung, drawn as ``init_params`` draws them:
    64 keys split from ``PRNGKey(seed)`` taken in module order, each
    convolution uniform in +-sqrt(1 / fan_in), GroupNorm at (1, 0)."""
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def conv(key, k, cin, cout, bias=False):
        s = math.sqrt(1.0 / (k * k * cin))
        p = {"w": jax.random.uniform(key, (k, k, cin, cout), jnp.float32,
                                     -s, s)}
        if bias:
            p["b"] = jnp.zeros((cout,), jnp.float32)
        return p

    def cbn(key, k, cin, cout):
        return {"conv": conv(key, k, cin, cout),
                "gn": {"scale": jnp.ones((cout,), jnp.float32),
                       "bias": jnp.zeros((cout,), jnp.float32)}}

    def csp(key, c, n):
        r = jax.random.split(key, 2 * n + 3)
        h = c // 2
        return {"split1": cbn(r[0], 1, c, h), "split2": cbn(r[1], 1, c, h),
                "bottlenecks": [{"c1": cbn(r[2 + 2 * i], 1, h, h),
                                 "c2": cbn(r[3 + 2 * i], 3, h, h)}
                                for i in range(n)],
                "fuse": cbn(r[2 * n + 2], 1, c, c)}

    chans = [_width(d, 2 ** (i + 1)) for i in range(len(strides(d)))]
    p = {"stem": cbn(next(keys), 3, 3, _width(d, 1)),
         "stem2": cbn(next(keys), 3, _width(d, 1), chans[0] // 2),
         "stages": [], "laterals": [], "fpn": [], "heads": []}
    c_prev = chans[0] // 2
    for c in chans:
        p["stages"].append({"down": cbn(next(keys), 3, c_prev, c),
                            "csp": csp(next(keys), c, _depth(d))})
        c_prev = c
    for i in range(len(chans) - 1):
        p["laterals"].append(cbn(next(keys), 1, chans[i + 1], chans[i]))
        p["fpn"].append(csp(next(keys), chans[i], max(1, _depth(d) // 2)))
    for c in chans:
        p["heads"].append({"conv": cbn(next(keys), 3, c, c),
                           "out": conv(next(keys), 1, c, 5 + d["n_classes"],
                                       bias=True)})
    return p


def forward_fn(d: dict, operand_dtype=None):
    """Jitted ``(params, images (B, S, S, 3)) -> [raw heads per stride]``
    in float32 with HIGHEST-precision convolutions; ``operand_dtype``
    rounds each convolution's input and weights to that dtype first
    (the control)."""
    return _forward_fn(tuple(sorted(d.items())), operand_dtype)


@functools.lru_cache(maxsize=None)
def _forward_fn(items, operand_dtype):
    import jax
    import jax.numpy as jnp

    d = dict(items)
    hi = jax.lax.Precision.HIGHEST

    def rnd(a):
        if operand_dtype is None:
            return a
        return a.astype(operand_dtype).astype(jnp.float32)

    def conv(p, x, stride=1):
        y = jax.lax.conv_general_dilated(
            rnd(x), rnd(p["w"]), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
        return y + p["b"] if "b" in p else y

    def gn(p, x):
        c = x.shape[-1]
        g = _groups(c)
        xg = x.reshape(x.shape[:-1] + (g, c // g))
        mu = xg.mean(axis=(1, 2, 4), keepdims=True)
        var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
        y = ((xg - mu) / jnp.sqrt(var + 1e-5)).reshape(x.shape)
        return y * p["scale"] + p["bias"]

    def cbn(p, x, stride=1):
        y = gn(p["gn"], conv(p["conv"], x, stride))
        return y * jnp.tanh(jnp.logaddexp(y, 0.0))  # mish

    def csp(p, x):
        a = cbn(p["split1"], x)
        b = cbn(p["split2"], x)
        for bp in p["bottlenecks"]:
            b = b + cbn(bp["c2"], cbn(bp["c1"], b))
        return cbn(p["fuse"], jnp.concatenate([a, b], -1))

    def up2(x):
        n, h, w, c = x.shape
        return jnp.broadcast_to(x[:, :, None, :, None, :],
                                (n, h, 2, w, 2, c)).reshape(n, 2 * h, 2 * w, c)

    def forward(params, images):
        x = cbn(params["stem"], images.astype(jnp.float32), 2)
        x = cbn(params["stem2"], x, 2)
        feats = []
        for st in params["stages"]:
            x = csp(st["csp"], cbn(st["down"], x, 2))
            feats.append(x)
        for i in reversed(range(len(feats) - 1)):
            feats[i] = csp(params["fpn"][i],
                           feats[i] + up2(cbn(params["laterals"][i],
                                              feats[i + 1])))
        return [conv(hp["out"], cbn(hp["conv"], f))
                for f, hp in zip(feats, params["heads"])]

    return jax.jit(forward)
