"""Per-kernel allclose sweeps against the pure-jnp oracles.

Every Pallas kernel runs in interpret mode on CPU (the kernel body is
executed exactly as written; only the Mosaic lowering is TPU-only).
Shapes and dtypes are swept per the brief.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import projection, sphere
from repro.kernels.attention.ops import flash_attention, flash_attention_ref
from repro.kernels.gnomonic import ops as gno_ops
from repro.kernels.gnomonic.ref import gnomonic_sample_ref
from repro.kernels.sphiou.ops import sphiou_matrix, sphiou_matrix_batch
from repro.kernels.sphiou.ref import sphiou_ref, sphiou_ref_batch

RNG = np.random.default_rng(0)


# -- gnomonic -----------------------------------------------------------------


@pytest.mark.parametrize("center", [
    (0.0, 0.0), (3.0, 0.4), (-2.8, -0.9), (1.5, 1.3), (math.pi, 0.0),
])
@pytest.mark.parametrize("out,fov", [(64, 60), (32, 90), (48, 45)])
def test_gnomonic_matches_oracle(center, out, fov):
    erp = jnp.asarray(RNG.random((128, 256, 3)).astype(np.float32))
    fovr = (math.radians(fov), math.radians(fov))
    u, v = projection.gnomonic_coords(
        jnp.asarray(center[0]), jnp.asarray(center[1]), fovr, (out, out),
        erp.shape[:2])
    ref = gnomonic_sample_ref(erp, u, v)
    got = gno_ops.gnomonic_sample(erp, np.asarray(u), np.asarray(v))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-6, rtol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_gnomonic_dtypes(dtype):
    erp = jnp.asarray(RNG.random((64, 128, 3)).astype(dtype))
    fovr = (math.radians(60), math.radians(60))
    u, v = projection.gnomonic_coords(
        jnp.asarray(0.5), jnp.asarray(0.2), fovr, (32, 32), erp.shape[:2])
    ref = gnomonic_sample_ref(erp, u, v)
    got = gno_ops.gnomonic_sample(erp, np.asarray(u), np.asarray(v))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-3)


def test_gnomonic_vmem_fallback():
    """Pole-centred PI with a tiny VMEM cap falls back to the oracle."""
    erp = jnp.asarray(RNG.random((128, 256, 3)).astype(np.float32))
    fovr = (math.radians(120), math.radians(120))
    u, v = projection.gnomonic_coords(
        jnp.asarray(0.0), jnp.asarray(1.5), fovr, (16, 16), erp.shape[:2])
    got = gno_ops.gnomonic_sample(erp, np.asarray(u), np.asarray(v),
                                  vmem_cap=1024)
    ref = gnomonic_sample_ref(erp, u, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-6)


def test_project_sroi_kernel_end_to_end():
    erp = jnp.asarray(RNG.random((128, 256, 3)).astype(np.float32))
    pi_k = gno_ops.project_sroi_kernel(
        erp, 0.3, -0.1, (math.radians(60), math.radians(60)), (40, 40))
    pi_ref = projection.project_sroi(
        erp, jnp.asarray(0.3), jnp.asarray(-0.1),
        (math.radians(60), math.radians(60)), (40, 40))
    # coordinate maps are computed once eagerly and once under jit; op
    # fusion perturbs u/v at ~1e-7, which bilinear amplifies to ~1e-5.
    np.testing.assert_allclose(np.asarray(pi_k), np.asarray(pi_ref), atol=5e-5)


# -- sphiou -------------------------------------------------------------------


@pytest.mark.parametrize("n,m", [(1, 1), (7, 13), (64, 64), (100, 257),
                                 (256, 33)])
def test_sphiou_matches_oracle(n, m):
    rng = np.random.default_rng(n * 1000 + m)
    def boxes(k):
        return np.stack([
            rng.uniform(-math.pi, math.pi, k), rng.uniform(-1.4, 1.4, k),
            rng.uniform(0.05, 1.2, k), rng.uniform(0.05, 1.2, k)],
            axis=-1).astype(np.float32)
    a, b = boxes(n), boxes(m)
    ref = np.asarray(sphiou_ref(jnp.asarray(a), jnp.asarray(b)))
    got = np.asarray(sphiou_matrix(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(got, ref, atol=5e-6)


@pytest.mark.parametrize("b,n,m", [(1, 8, 8), (3, 17, 9), (4, 64, 64)])
def test_sphiou_batch_matches_vmapped_oracle(b, n, m):
    rng = np.random.default_rng(b * 100 + n)
    def boxes(rows, k):
        return np.stack([
            rng.uniform(-math.pi, math.pi, (rows, k)),
            rng.uniform(-1.4, 1.4, (rows, k)),
            rng.uniform(0.05, 1.2, (rows, k)),
            rng.uniform(0.05, 1.2, (rows, k))],
            axis=-1).astype(np.float32)
    a, bb = boxes(b, n), boxes(b, m)
    ref = np.asarray(sphiou_ref_batch(jnp.asarray(a), jnp.asarray(bb)))
    got = np.asarray(sphiou_matrix_batch(jnp.asarray(a), jnp.asarray(bb)))
    np.testing.assert_allclose(got, ref, atol=5e-6)


def test_sphiou_batch_rows_independent():
    # row r of the batched kernel == the unbatched kernel on row r
    rng = np.random.default_rng(17)
    a = np.stack([
        rng.uniform(-math.pi, math.pi, (3, 12)), rng.uniform(-1.2, 1.2, (3, 12)),
        rng.uniform(0.1, 1.0, (3, 12)), rng.uniform(0.1, 1.0, (3, 12))],
        axis=-1).astype(np.float32)
    got = np.asarray(sphiou_matrix_batch(jnp.asarray(a), jnp.asarray(a)))
    for r in range(3):
        single = np.asarray(sphiou_matrix(jnp.asarray(a[r]), jnp.asarray(a[r])))
        np.testing.assert_allclose(got[r], single, atol=1e-6)


def _iou_f64(a, b):
    return sphere.sph_iou_matrix_np(a.astype(np.float64), b.astype(np.float64))


def _sph_boxes(rng, shape, lat=1.4, fov=(0.05, 1.2)):
    return np.stack([
        rng.uniform(-math.pi, math.pi, shape), rng.uniform(-lat, lat, shape),
        rng.uniform(*fov, shape), rng.uniform(*fov, shape)],
        axis=-1).astype(np.float32)


def _degenerate_boxes(rng):
    """Boxes centred on the poles (f32 pi/2 lies just past the pole),
    pairs straddling the +-pi seam, and zero-FoV padding rows."""
    poles = np.array([[t, s * np.float32(math.pi / 2), fx, fy]
                      for s in (1, -1) for t, fx, fy in
                      ((0.0, 0.5, 0.5), (1.0, 0.3, 0.6), (-2.5, 0.8, 0.2))],
                     np.float32)
    seam = np.array([[math.pi - d, p, 0.4, 0.3] for d, p in
                     ((0.0, 0.0), (0.01, 0.2), (2 * math.pi - 0.01, 0.2),
                      (-0.02, -0.1))], np.float32)
    pad = np.zeros((6, 4), np.float32)
    return np.concatenate([poles, seam, _sph_boxes(rng, 12), pad])


@pytest.mark.parametrize("case", ["random", "small", "degenerate"])
def test_sphiou_kernel_against_float64(case):
    """The kernel's own atan2 (a polynomial, since Mosaic lowers no
    atan2/asin) against the float64 host IoU.  Tolerance: the f32
    angles carry about 1e-7 rad of error, which an IoU divides by the
    box extent, so atol = 1e-7 / (smallest FoV in rad)."""
    rng = np.random.default_rng({"random": 1, "small": 2,
                                 "degenerate": 3}[case])
    if case == "random":
        a, min_fov = _sph_boxes(rng, (4, 64)), 0.05
    elif case == "small":
        a, min_fov = _sph_boxes(rng, (4, 64), fov=(0.005, 0.05)), 0.005
    else:
        a, min_fov = np.stack([_degenerate_boxes(rng)] * 2), 0.05
    got = np.asarray(sphiou_matrix_batch(jnp.asarray(a), jnp.asarray(a)))
    np.testing.assert_allclose(got, _iou_f64(a, a), rtol=0,
                               atol=1e-7 / min_fov)
    if case == "degenerate":
        pad = ~a[0].any(axis=-1)
        assert (got[:, pad, :] == 0).all() and (got[:, :, pad] == 0).all()


def test_sphiou_atan2_matches_numpy():
    """The in-kernel atan2 over all quadrants and magnitudes."""
    from repro.kernels.sphiou.sphiou import _atan2

    rng = np.random.default_rng(4)
    y = (rng.standard_normal(4096)
         * rng.choice([1e-3, 1.0, 1e3], 4096)).astype(np.float32)
    x = (rng.standard_normal(4096)
         * rng.choice([1e-3, 1.0, 1e3], 4096)).astype(np.float32)
    y[:4], x[:4] = (0.0, 1.0, 0.0, -1.0), (1.0, 0.0, -1.0, 0.0)
    got = np.asarray(_atan2(jnp.asarray(y), jnp.asarray(x)))
    want = np.arctan2(y.astype(np.float64), x.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=0, atol=4e-7)


def test_sphiou_diag_is_one():
    rng = np.random.default_rng(3)
    a = np.stack([rng.uniform(-3, 3, 32), rng.uniform(-1.2, 1.2, 32),
                  rng.uniform(0.1, 1.0, 32), rng.uniform(0.1, 1.0, 32)],
                 axis=-1).astype(np.float32)
    got = np.asarray(sphiou_matrix(jnp.asarray(a), jnp.asarray(a)))
    np.testing.assert_allclose(np.diag(got), 1.0, atol=1e-4)


# -- flash attention ----------------------------------------------------------

CASES = [
    dict(b=2, sq=64, skv=64, hq=4, hkv=4, d=32, causal=True, window=None),
    dict(b=1, sq=128, skv=128, hq=8, hkv=2, d=64, causal=True, window=None),
    dict(b=1, sq=96, skv=96, hq=2, hkv=2, d=32, causal=True, window=32),
    dict(b=2, sq=1, skv=200, hq=4, hkv=1, d=32, causal=True, window=None),
    dict(b=1, sq=64, skv=64, hq=2, hkv=2, d=32, causal=False, window=None),
    dict(b=1, sq=80, skv=160, hq=2, hkv=2, d=16, causal=True, window=64),
]


@pytest.mark.parametrize("case", CASES)
def test_flash_attention_matches_oracle(case):
    rng = np.random.default_rng(42)
    def mk(s, h):
        return jnp.asarray(rng.standard_normal(
            (case["b"], s, h, case["d"])).astype(np.float32))
    q = mk(case["sq"], case["hq"])
    k = mk(case["skv"], case["hkv"])
    v = mk(case["skv"], case["hkv"])
    qoff = case["skv"] - case["sq"] if case["causal"] else 0
    ref = flash_attention_ref(q, k, v, causal=case["causal"],
                              window=case["window"], q_offset=qoff)
    got = flash_attention(q, k, v, causal=case["causal"],
                          window=case["window"], q_offset=qoff)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 64, 4, 32)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((1, 64, 4, 32)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((1, 64, 4, 32)), dtype=dtype)
    ref = flash_attention_ref(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2 if dtype == jnp.bfloat16 else 2e-5)


def test_flash_attention_block_sizes():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 100, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 100, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 100, 2, 16)).astype(np.float32))
    ref = flash_attention_ref(q, k, v, causal=True)
    for bq, bk in [(16, 16), (32, 64), (128, 128)]:
        got = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)
