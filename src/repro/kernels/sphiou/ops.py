"""Jitted public wrapper around the SphIoU Pallas kernel.

Handles padding to block multiples (padded boxes get zero-area FoVs,
whose IoU against anything is 0) and the (N, 4) <-> (4, N) transpose
at the API boundary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.sphiou import sphiou as _s


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _clamp_block(block: int, n: int) -> int:
    """Clamp a block size to the problem size, lane-aligned.

    The clamp must stay a multiple of 8 (the f32 sublane width): for
    8 < n < block the naive ``min(block, n)`` yields a non-aligned
    Pallas block (e.g. n=100 -> block 100), which Mosaic rejects on
    real TPUs even though interpret mode happens to accept it.
    """
    return min(block, -(-max(8, n) // 8) * 8)


def sphiou_matrix(
    boxes_a: jax.Array,  # (N, 4)
    boxes_b: jax.Array,  # (M, 4)
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool | None = None,
    dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    """(N, M) SphIoU matrix via the Pallas kernel.

    ``dtype`` selects the in-kernel compute precision.  ``jnp.bfloat16``
    runs in interpret mode only (v5e cannot lower bf16 transcendentals;
    the compiled kernel raises ``ValueError``); its IoU values can flip
    the 0.6 keep decision for near-threshold pairs (bound measured in
    ``benchmarks/kernels_bench.py`` and gated in
    ``check_regression.py``).  Inputs and outputs stay f32.
    """
    if interpret is None:
        interpret = not _on_tpu()
    n, m = boxes_a.shape[0], boxes_b.shape[0]
    block_n = _clamp_block(block_n, n)
    block_m = _clamp_block(block_m, m)
    pad_n = (-n) % block_n
    pad_m = (-m) % block_m
    a = jnp.pad(boxes_a.astype(jnp.float32), ((0, pad_n), (0, 0)))
    b = jnp.pad(boxes_b.astype(jnp.float32), ((0, pad_m), (0, 0)))
    out = _s.sphiou_pallas(
        a.T, b.T, block_n=block_n, block_m=block_m, interpret=interpret,
        dtype=dtype,
    )
    return out[:n, :m]


def sphiou_matrix_batch(
    boxes_a: jax.Array,  # (B, N, 4)
    boxes_b: jax.Array,  # (B, M, 4)
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool | None = None,
    dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    """(B, N, M) per-row SphIoU matrices via the batched Pallas kernel.

    Rows are independent — row ``r`` of the output is
    ``sphiou_matrix(boxes_a[r], boxes_b[r])``.  Padded boxes (zero FoV)
    score IoU 0 against everything, so callers can pad rows to a common
    N and mask afterwards.  ``dtype`` selects the in-kernel compute
    precision (see :func:`sphiou_matrix`).
    """
    if interpret is None:
        interpret = not _on_tpu()
    _, n, _ = boxes_a.shape
    m = boxes_b.shape[1]
    block_n = _clamp_block(block_n, n)
    block_m = _clamp_block(block_m, m)
    pad_n = (-n) % block_n
    pad_m = (-m) % block_m
    a = jnp.pad(boxes_a.astype(jnp.float32), ((0, 0), (0, pad_n), (0, 0)))
    b = jnp.pad(boxes_b.astype(jnp.float32), ((0, 0), (0, pad_m), (0, 0)))
    out = _s.sphiou_pallas_batch(
        jnp.swapaxes(a, 1, 2), jnp.swapaxes(b, 1, 2),
        block_n=block_n, block_m=block_m, interpret=interpret,
        dtype=dtype,
    )
    return out[:, :n, :m]
