"""Pallas TPU kernel: flash attention (full / causal / sliding-window).

Serves the LM-family architectures of the framework: causal training
attention, prefill, KV-cache decode, and the sliding-window variant
that makes ``long_500k`` feasible for mixtral-style models (attention
cost O(seq * window) with a window-bounded KV cache).

Design: classic flash-attention-2 schedule adapted to the TPU grid —
  * grid = (batch*heads, q_blocks, kv_blocks) with the kv axis
    innermost and marked "arbitrary" (sequential) so the running
    max / denominator / accumulator live in VMEM scratch across the
    kv sweep;
  * each (BQ, BK) tile does one MXU matmul for the scores and one for
    the value gather, with the online-softmax rescale between them on
    the VPU (all f32 accumulation regardless of input dtype);
  * causal/window tiles that fall entirely outside the band are
    skipped via ``pl.when`` — with window w the per-row work drops
    from O(S) to O(w), which is what the roofline for long_500k needs;
  * ``q_offset`` aligns query positions when Sq != Skv (decode /
    chunked prefill): absolute q position = q_offset + local index.

Block sizes default to (128, 128) — MXU-native tiles; the wrapper pads
ragged tails and masks padded kv columns via ``kv_len``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(
    q_ref,  # (1, BQ, D)
    k_ref,  # (1, BK, D)
    v_ref,  # (1, BK, D)
    o_ref,  # (1, BQ, D)
    m_ref,  # (BQ, 1) f32 scratch
    l_ref,  # (BQ, 1) f32 scratch
    acc_ref,  # (BQ, D) f32 scratch
    *,
    scale: float,
    causal: bool,
    window: int | None,
    kv_len: int,
    q_offset: int,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # -- static-ish band check: can this (qi, ki) tile contribute? --
    q_lo = q_offset + qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_k
    k_hi = k_lo + block_k - 1
    live = k_lo <= jnp.minimum(q_hi, kv_len - 1) if causal else k_lo < kv_len
    if window is not None:
        live = jnp.logical_and(live, k_hi >= q_lo - (window - 1))

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (BQ, D)
        k = k_ref[0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # (BQ, 1)
        p = jnp.exp(s - m_new)  # (BQ, BK)
        p = jnp.where(mask, p, 0.0)

        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale",
        "causal",
        "window",
        "kv_len",
        "q_offset",
        "block_q",
        "block_k",
        "interpret",
    ),
)
def mha_pallas(
    q: jax.Array,  # (BH, Sq_pad, D)
    k: jax.Array,  # (BH, Skv_pad, D)
    v: jax.Array,  # (BH, Skv_pad, D)
    *,
    scale: float,
    causal: bool,
    window: int | None,
    kv_len: int,
    q_offset: int,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    skv = k.shape[1]
    n_q = sq // block_q
    n_kv = skv // block_k
    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        kv_len=kv_len,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_kv,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
