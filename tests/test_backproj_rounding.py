"""Why the benchmark's ``backproj_err`` reads up to a few 1e-3 rad on
single boxes while the back-projection is right.

``bench/reference.py`` back-projects with the operands of its matrix
products rounded to bfloat16, as a TPU multiplies float32 at its
default precision, but computes everything else in float64.  The
served program computes the same operands in float32.  Where an operand
lies within a float32 rounding of a bfloat16 rounding boundary, the two
round it to neighbouring bfloat16 values, and the box moves by up to
one bfloat16 step of a rotation entry (2**-8 relative, about 4e-3 rad).
The tests below show this on the CPU with the reference alone, evaluated
once in float64 and once in float32; they also pin the jitted chunk
program to the eager per-row call it replaced.

Run as a script on the chip, the module serves random boxes through both
back-projection paths (the jitted chunk program and the eager per-row
``pi_box_to_sphbb`` call) and prints, per path, how many boxes read
over 1e-5, 1e-4 and 1e-3 rad against the bfloat16 reference, with the
check's well-posedness filters::

    PYTHONPATH=src:. python tests/test_backproj_rounding.py [seed] [chunks]
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import check  # noqa: E402
from bench import reference as ref  # noqa: E402
from repro.core.sphere import pi_box_to_sphbb  # noqa: E402
from repro.serving.batching import ShapeBuckets  # noqa: E402
from repro.serving.scheduler import JaxDetectorBackend  # noqa: E402

BF16 = ml_dtypes.bfloat16
ROWS = 8  # the benchmark's largest batch rung


def _chunk(rng, size: int, k: int = 16):
    """``ROWS`` crops of ``k`` float32 pixel boxes each, boxes of the
    sizes a detector returns (some off the crop's edge), and per-row
    geometry ``(ct, cp, (fov_x, fov_y))`` as float64 host numbers."""
    cx, cy = rng.uniform(-0.2, 1.2, (2, ROWS, k)) * size
    w, h = np.exp(rng.normal(-2.0, 1.0, (2, ROWS, k))) * size
    boxes = np.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2],
                     -1).astype(np.float32)
    ct = rng.uniform(-math.pi, math.pi, ROWS)
    cp = rng.uniform(-1.45, 1.45, ROWS)
    fov = np.where(rng.random((ROWS, 2)) < 0.5, math.radians(60),
                   rng.uniform(0.8, 1.95, (ROWS, 2)))
    geoms = [(float(ct[r]), float(cp[r]), (float(fov[r, 0]),
                                          float(fov[r, 1])))
             for r in range(ROWS)]
    return boxes, geoms


def _err(got, want) -> np.ndarray:
    """Per-box largest SphBB component difference, theta wrapped."""
    d = np.abs(np.asarray(got, np.float64) - want)
    d[..., 0] = np.abs(check._wrap(np.asarray(got, np.float64)[..., 0]
                                   - want[..., 0]))
    return d.max(-1)


def _reference_pair(boxes, geom, size, dot_dt):
    """The reference in float64 and in float32, same boxes and
    geometry, well-posed boxes only; ``(float32 - float64)`` per box."""
    keep = check.well_posed_boxes(boxes.astype(np.float64), size)
    args = (boxes[keep], (geom[0], geom[1]), geom[2], size)
    want = ref.backproject(*args, dt=np.float64, dot_dt=dot_dt)
    got = ref.backproject(*args, dt=np.float32, dot_dt=dot_dt)
    return _err(got, want)


def _reference_spread(seed: int, chunks: int, dot_dt) -> np.ndarray:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(chunks):
        size = int(rng.choice([416, 512]))
        boxes, geoms = _chunk(rng, size)
        for r, g in enumerate(geoms):
            if check.well_posed_crop(g):
                out.append(_reference_pair(boxes[r], g, size, dot_dt))
    return np.concatenate(out)


def _eager_rows(boxes, geoms, size):
    """The per-row eager call the chunk program replaced: scalars on
    the device, the field of view as host floats."""
    return np.stack([np.asarray(pi_box_to_sphbb(
        jnp.asarray(boxes[r]), jnp.asarray(g[0]), jnp.asarray(g[1]),
        g[2], (size, size))) for r, g in enumerate(geoms)])


def _backend():
    return JaxDetectorBackend(
        [], [], buckets=ShapeBuckets((1, ROWS), resolutions=(416, 512)))


def _served(backend, boxes, geoms, size):
    """The jitted chunk program, as ``launch_srois_batched`` runs it."""
    chunk = [(None, None)] * len(geoms)
    return np.asarray(backend._launch_backproject(
        jnp.asarray(boxes), chunk, geoms, size))


def served_errors(seed: int, chunks: int, dot_dt) -> dict:
    """Per-box errors of both paths against the reference at
    ``dot_dt``, and the largest difference between the two paths, on
    the boxes the check keeps."""
    rng = np.random.default_rng(seed)
    backend = _backend()
    errs = {"chunk": [], "eager": []}
    between = 0.0
    for _ in range(chunks):
        size = int(rng.choice([416, 512]))
        boxes, geoms = _chunk(rng, size)
        paths = {"chunk": _served(backend, boxes, geoms, size),
                 "eager": _eager_rows(boxes, geoms, size)}
        for r, g in enumerate(geoms):
            keep = check.well_posed_boxes(boxes[r].astype(np.float64), size)
            if not keep.any() or not check.well_posed_crop(g):
                continue
            between = max(between, float(_err(
                paths["chunk"][r][keep],
                paths["eager"][r][keep].astype(np.float64)).max()))
            want = ref.backproject(boxes[r][keep].astype(np.float64),
                                   (g[0], g[1]), g[2], size, dot_dt=dot_dt)
            for name, got in paths.items():
                errs[name].append(_err(got[r][keep], want))
    return {name: np.concatenate(e) for name, e in errs.items()}, between


def test_float32_moves_the_bf16_reference_by_a_bf16_step():
    """The same reference, float32 against float64 arithmetic: with
    bfloat16 products most boxes agree to float32 rounding, and a few
    move by about a bfloat16 step of a rotation entry."""
    e = _reference_spread(0, 250, BF16)
    assert e.size > 10_000
    assert np.median(e) < 1e-6
    assert 1e-4 < e.max() < 8e-3
    assert 0 < np.mean(e > 1e-4) < 0.01


def test_float32_alone_stays_at_float32_rounding():
    """The same boxes, the same float32-against-float64 comparison,
    products in full precision: no box moves by more than float32
    rounding, so the bfloat16 rounding of the products is the cause."""
    e = _reference_spread(0, 250, None)
    assert e.max() < 1e-5


def test_chunk_program_matches_the_eager_call():
    """The jitted chunk program and the eager per-row call, same boxes
    and geometry: float32 on the CPU, so within float32 rounding of each
    other and of the full-precision reference (1e-5 rad, as
    ``tests/test_fused_tick.py`` bounds the served boxes)."""
    errs, between = served_errors(0, 6, None)
    assert between < 1e-5
    for e in errs.values():
        assert e.size > 100 and e.max() < 1e-5


def main(seed: int, chunks: int) -> None:
    errs, between = served_errors(seed, chunks, BF16)
    print(f"{jax.default_backend()}: {chunks} chunks of {ROWS} rows, seed "
          f"{seed}; max |chunk - eager| {between:.3e} rad")
    for name, e in errs.items():
        print(f"{name}: boxes {e.size} max {e.max():.3e} median "
              f"{np.median(e):.3e} n>1e-5 {int((e > 1e-5).sum())} n>1e-4 "
              f"{int((e > 1e-4).sum())} n>1e-3 {int((e > 1e-3).sum())}")
    e = _reference_spread(seed, chunks, BF16)
    print(f"reference float32 vs float64: boxes {e.size} max {e.max():.3e} "
          f"n>1e-4 {int((e > 1e-4).sum())} n>1e-3 {int((e > 1e-3).sum())}")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 0,
         int(sys.argv[2]) if len(sys.argv) > 2 else 50)
