"""Decoder-only LM family: dense / GQA / MQA / sliding-window / MoE.

Covers the four assigned LM architectures (granite-34b, smollm-135m,
mixtral-8x22b, qwen3-moe-235b-a22b) with one configurable implementation:

  * llama-style blocks: RMSNorm -> attention (+RoPE, GQA) -> residual,
    RMSNorm -> SwiGLU MLP or top-k MoE -> residual;
  * ``jax.lax.scan`` over stacked layer params so HLO size is O(1) in
    depth (88/94-layer configs must stay lowerable on one CPU host);
  * three attention impls: ``naive`` (test oracle), ``chunked``
    (lax.scan online-softmax — the memory-sane default for 4k-32k
    training/prefill), ``pallas`` (the flash kernel, TPU runtime);
  * KV-cache prefill/decode; sliding-window models use a ring-buffer
    cache bounded by the window (this is what makes long_500k decode
    feasible: O(window) memory and compute per token);
  * chunked cross-entropy: the (tokens, vocab) logits matrix is never
    materialised — unembedding + CE run in sequence chunks under remat
    (vocab 152k x 1M tokens would otherwise be ~0.6 PB).

MoE: sort-based grouped dispatch (tokens argsorted by expert id, static
capacity, one grouped einsum per projection) — the standard
compile-friendly TPU formulation; capacity overflow drops tokens
(combine weights renormalised over survivors).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import (BATCH, constrain, current_mesh,
                                         mesh_axis_size)
from repro.models import layers as L

Array = jax.Array
Params = dict


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    # MoE
    moe: bool = False
    n_experts: int = 0
    moe_top_k: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    # attention
    window: int | None = None  # sliding-window size (tokens), None = full
    rope_theta: float = 10000.0
    attention_impl: str = "chunked"  # naive | chunked | pallas
    attn_chunk: int = 1024
    # loss
    ce_chunk: int = 512
    # numerics
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    norm_eps: float = 1e-6
    remat: bool = True
    # Megatron-style sequence parallelism: residual-stream activations
    # shard their SEQUENCE axis over `model` between the TP regions, so
    # norms/residuals/rotaries touch 1/TP of the bytes and the saved
    # scan carries shrink by TP.  XLA inserts the all-gather at qkv/mlp
    # entry and reduce-scatters after wo/w_down (beyond-paper perf
    # iteration; see EXPERIMENTS.md section Perf).
    sequence_parallel: bool = False
    # Explicit all-to-all expert parallelism (shard_map): every
    # (data, model) rank dispatches its OWN token slice to the expert
    # owners instead of letting SPMD all-reduce full (tokens*k, d)
    # combine buffers across `model`.  Requires sequence_parallel
    # (tokens must be disjoint across model ranks) and
    # n_experts % model_axis == 0.
    moe_a2a: bool = False

    @property
    def policy(self) -> L.DtypePolicy:
        return L.DtypePolicy(self.param_dtype, self.compute_dtype)

    @property
    def n_params(self) -> int:
        """Total parameter count (used for 6*N*D roofline bookkeeping)."""
        d, dh = self.d_model, self.d_head
        attn = d * (self.n_heads * dh) * 2 + d * (self.n_kv_heads * dh) * 2
        if self.moe:
            mlp = self.n_experts * 3 * d * self.d_ff_expert + d * self.n_experts
        else:
            mlp = 3 * d * self.d_ff
        per_layer = attn + mlp + 2 * d
        emb = self.vocab_size * d * 2  # untied in/out embeddings
        return self.n_layers * per_layer + emb + d

    @property
    def n_active_params(self) -> int:
        """Active params per token (MoE: top-k experts only)."""
        if not self.moe:
            return self.n_params
        d, dh = self.d_model, self.d_head
        attn = d * (self.n_heads * dh) * 2 + d * (self.n_kv_heads * dh) * 2
        mlp = self.moe_top_k * 3 * d * self.d_ff_expert + d * self.n_experts
        per_layer = attn + mlp + 2 * d
        return self.n_layers * per_layer + self.vocab_size * d * 2 + d


@dataclasses.dataclass
class KVCache:
    k: Array  # (L, B, S_cache, KVH, Dh)
    v: Array  # (L, B, S_cache, KVH, Dh)
    length: Array  # scalar int32: number of tokens already absorbed


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_params(rng, cfg: TransformerConfig) -> Params:
    dt = cfg.param_dtype
    d, dh = cfg.d_model, cfg.d_head
    rngs = jax.random.split(rng, 12)
    lyr = cfg.n_layers

    def stacked(key, shape, scale):
        return (jax.random.uniform(key, (lyr,) + shape, jnp.float32, -scale, scale)
                .astype(dt))

    s_attn = (1.0 / d) ** 0.5
    p_layers = {
        "attn": {
            "wq": stacked(rngs[0], (d, cfg.n_heads * dh), s_attn),
            "wk": stacked(rngs[1], (d, cfg.n_kv_heads * dh), s_attn),
            "wv": stacked(rngs[2], (d, cfg.n_kv_heads * dh), s_attn),
            "wo": stacked(rngs[3], (cfg.n_heads * dh, d), (1.0 / (cfg.n_heads * dh)) ** 0.5),
        },
        "ln1": {"scale": jnp.ones((lyr, d), dt)},
        "ln2": {"scale": jnp.ones((lyr, d), dt)},
    }
    if cfg.moe:
        fe = cfg.d_ff_expert
        s_ff = (1.0 / d) ** 0.5
        p_layers["moe"] = {
            "router": stacked(rngs[4], (d, cfg.n_experts), s_ff),
            "w_gate": stacked(rngs[5], (cfg.n_experts, d, fe), s_ff),
            "w_up": stacked(rngs[6], (cfg.n_experts, d, fe), s_ff),
            "w_down": stacked(rngs[7], (cfg.n_experts, fe, d), (1.0 / fe) ** 0.5),
        }
    else:
        f = cfg.d_ff
        s_ff = (1.0 / d) ** 0.5
        p_layers["mlp"] = {
            "w_gate": stacked(rngs[4], (d, f), s_ff),
            "w_up": stacked(rngs[5], (d, f), s_ff),
            "w_down": stacked(rngs[6], (f, d), (1.0 / f) ** 0.5),
        }
    return {
        "embed": L.init_embedding(rngs[8], cfg.vocab_size, d, dt),
        "layers": p_layers,
        "ln_f": L.init_rmsnorm(d, dt),
        "unembed": L.init_dense(rngs[9], d, cfg.vocab_size, bias=False, dtype=dt),
    }


# --------------------------------------------------------------------------
# attention impls
# --------------------------------------------------------------------------


def _naive_attention(q, k, v, *, causal, window, q_offset, scale):
    # q: (B, Sq, H, Dh); k/v: (B, Skv, H, Dh) (kv heads already repeated)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    sq, skv = q.shape[1], k.shape[1]
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def _chunked_attention(q, k, v, *, causal, window, q_offset, scale, chunk):
    """Online-softmax over KV chunks via lax.scan (flash in pure jnp)."""
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    chunk = min(chunk, skv)
    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (skv + pad) // chunk
    kc = k.reshape(b, n_chunks, chunk, h, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, dh).transpose(1, 0, 2, 3, 4)

    q32 = q.astype(jnp.float32)
    qpos = q_offset + jnp.arange(sq)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        idx, kb, vb = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kb.astype(jnp.float32)) * scale
        kpos = idx * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < skv
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (
        jnp.full((b, h, sq), -1e30, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        jnp.zeros((b, h, sq, dh), jnp.float32),
    )
    # nested remat: without it autodiff saves the (sq, chunk) score matrix
    # of EVERY chunk — i.e. the full S^2 softmax — defeating the point of
    # chunking for training.  Recompute per chunk in the backward instead.
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(body, init, (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention(q, k, v, cfg: TransformerConfig, *, causal=True, window=None,
              q_offset=0):
    """Dispatch on cfg.attention_impl. q: (B,Sq,H,Dh); k/v: (B,Skv,KVH,Dh)."""
    scale = cfg.d_head ** -0.5
    if cfg.attention_impl == "pallas":
        from repro.kernels.attention.ops import flash_attention

        return flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, scale=scale)
    rep = q.shape[2] // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if cfg.attention_impl == "naive":
        return _naive_attention(q, k, v, causal=causal, window=window,
                                q_offset=q_offset, scale=scale)
    return _chunked_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, scale=scale, chunk=cfg.attn_chunk)


# --------------------------------------------------------------------------
# MoE block
# --------------------------------------------------------------------------


def moe_block(p: Params, x: Array, cfg: TransformerConfig) -> tuple[Array, Array]:
    """Top-k MoE with per-data-shard grouped dispatch.

    ``x``: (T, D) flattened tokens.  Returns (out, aux_loss) where
    aux_loss is the load-balancing term (Switch-style).

    Tokens are reshaped to (G, T/G, D) with G = the data-parallel world
    size, so the argsort / searchsorted dispatch machinery runs *per
    data shard* (vmapped, zero cross-shard communication) — the
    production formulation.  A global sort would force XLA SPMD to
    all-gather 8M routing keys per MoE layer.  Capacity is therefore
    per-shard (ceil(T_local * k / E * cf)), i.e. load balancing is
    enforced shard-locally — the standard behaviour of EP systems.
    Expert placement (EP over `model` vs TP-within-expert) follows the
    weight sharding chosen in ``repro.distributed.sharding``.
    """
    t, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    pol = cfg.policy
    import math as _math

    g = _math.gcd(t, mesh_axis_size("pod") * mesh_axis_size("data"))
    tl = t // g
    xg = constrain(x.reshape(g, tl, d), BATCH, None, None)

    logits = L.dense({"w": p["router"]}, xg, pol).astype(jnp.float32)  # (G,TL,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)  # (G, TL, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balancing aux loss (scatter-add counts; no (T, E) one-hot)
    me = jnp.mean(probs, axis=(0, 1))
    counts = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0)
    aux = e * jnp.sum(me * counts / (t * k))

    capacity = max(1, int(-(-tl * k // e) * cfg.capacity_factor))

    fe = expert_ids.reshape(g, tl * k)  # flat expert ids per shard
    ft = jnp.repeat(jnp.arange(tl), k)[None].repeat(g, axis=0)
    fg = gate_vals.reshape(g, tl * k)

    # The whole dispatch runs VMAPPED over the shard axis: XLA SPMD
    # partitions batched (vmapped) gather/scatter on the batch dim with
    # zero collectives, whereas the equivalent fancy-indexed forms get
    # involuntarily replicated (measured: 137 TB/layer of all-reduce on
    # the qwen3 cell).
    def _dispatch(xr, fer, ftr, fgr):
        order = jnp.argsort(fer)
        se, str_, sgr = fer[order], ftr[order], fgr[order]
        start = jnp.searchsorted(se, jnp.arange(e))
        pos = jnp.arange(tl * k) - start[se]
        keepr = pos < capacity
        slotr = jnp.where(keepr, se * capacity + pos, e * capacity)
        gathered = jnp.zeros((e * capacity + 1, d), xr.dtype).at[slotr].set(
            xr[str_])
        return gathered[:-1], slotr, str_, keepr, sgr

    gathered, slot, st, keep, sg = jax.vmap(_dispatch)(xg, fe, ft, fg)
    grouped = gathered.reshape(g, e, capacity, d)
    # expert parallelism when the expert count divides the model axis
    # (qwen3); otherwise TP-within-expert (mixtral) and the grouped
    # tokens stay replicated over `model` while the FFN width shards.
    ep = e % max(mesh_axis_size("model"), 1) == 0
    if ep:
        grouped = constrain(grouped, BATCH, "model", None, None)
    else:
        grouped = constrain(grouped, BATCH, None, None, None)

    gate_h = jnp.einsum("gecd,edf->gecf", pol.cast_in(grouped),
                        p["w_gate"].astype(cfg.compute_dtype),
                        preferred_element_type=jnp.float32)
    up_h = jnp.einsum("gecd,edf->gecf", pol.cast_in(grouped),
                      p["w_up"].astype(cfg.compute_dtype),
                      preferred_element_type=jnp.float32)
    hidden = (L.silu(gate_h) * up_h).astype(cfg.compute_dtype)
    hidden = constrain(hidden, BATCH, "model", None, None) if ep \
        else constrain(hidden, BATCH, None, None, "model")
    expert_out = jnp.einsum("gecf,efd->gecd", hidden,
                            p["w_down"].astype(cfg.compute_dtype),
                            preferred_element_type=jnp.float32)
    if ep:
        expert_out = constrain(expert_out, BATCH, "model", None, None)
    expert_out = expert_out.reshape(g, e * capacity, d)

    # combine runs in compute dtype: the (tl*k, d) gather + scatter-add
    # is pure HBM traffic; bf16 halves it (sum of <= top_k values with
    # renormalised gates — negligible precision impact, measured in
    # EXPERIMENTS.md section Perf).
    cdt = cfg.compute_dtype

    def _combine(eor, slotr, str_, keepr, sgr):
        contrib = jnp.where(
            keepr[:, None],
            eor.astype(cdt)[jnp.minimum(slotr, e * capacity - 1)]
            * sgr[:, None].astype(cdt), jnp.zeros((), cdt))
        return jnp.zeros((tl, d), cdt).at[str_].add(contrib)

    out = jax.vmap(_combine)(expert_out, slot, st, keep, sg)
    return out.reshape(t, d).astype(x.dtype), aux




def _use_moe_a2a(cfg: TransformerConfig) -> bool:
    if not (cfg.moe and cfg.moe_a2a and cfg.sequence_parallel):
        return False
    m = mesh_axis_size("model")
    return m > 1 and cfg.n_experts % m == 0


def moe_block_a2a(p: Params, x: Array, cfg: TransformerConfig
                  ) -> tuple[Array, Array]:
    """Explicit all-to-all expert parallelism (shard_map).

    Under sequence parallelism every (data, model) rank owns a DISJOINT
    slice of the tokens, so the MoE exchange can be the textbook EP
    all-to-all: each rank dispatches its local tokens to the model
    ranks that own their experts and receives them back after the
    expert FFN — total wire volume tokens*k*d / model_ranks per link,
    versus the tokens*k*d all-reduce XLA SPMD emits for the implicit
    formulation (measured 20x reduction on the qwen3 cell, see
    EXPERIMENTS.md section Perf).  Token dropping uses the same
    per-shard capacity rule as :func:`moe_block`, just at per-rank
    granularity; with no drops the two paths agree exactly
    (tests/test_distributed_integration.py).
    """
    mesh = current_mesh()
    e, k = cfg.n_experts, cfg.moe_top_k
    pol = cfg.policy
    t, d = x.shape
    m_size = mesh_axis_size("model")
    e_loc = e // m_size
    flat_axes = tuple(a for a in ("pod", "data", "model")
                      if a in mesh.axis_names)
    n_ranks = 1
    for a in flat_axes:
        n_ranks *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
    tl = t // n_ranks
    capacity = max(1, int(-(-tl * k // e) * cfg.capacity_factor))
    f_dim = cfg.d_ff_expert
    from jax.sharding import PartitionSpec as P

    def kernel(xr, router_w, wg, wu, wd):
        # xr: (tl, d) local tokens; wg/wu/wd: (e_loc, d, f) local experts
        xr = xr.reshape(tl, d)
        logits = jax.lax.dot_general(
            pol.cast_in(xr), router_w.astype(cfg.compute_dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (tl, e)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        counts = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(1.)
        aux_loc = e * jnp.sum(me * counts / (tl * k))

        fe = expert_ids.reshape(-1)
        ft = jnp.repeat(jnp.arange(tl), k)
        fg = gate_vals.reshape(-1)
        order = jnp.argsort(fe)
        se, st, sg = fe[order], ft[order], fg[order]
        start = jnp.searchsorted(se, jnp.arange(e))
        pos = jnp.arange(tl * k) - start[se]
        keep = pos < capacity
        slot = jnp.where(keep, se * capacity + pos, e * capacity)
        gathered = jnp.zeros((e * capacity + 1, d), xr.dtype).at[slot].set(
            xr[st])[:-1]

        # ---- dispatch: (m_size, e_loc*capacity, d) -> owners ----
        send = gathered.reshape(m_size, e_loc * capacity, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        # recv: (m_size * e_loc * capacity, d) = tokens from every
        # source rank for MY e_loc experts
        grouped = recv.reshape(m_size, e_loc, capacity, d)             .transpose(1, 0, 2, 3).reshape(e_loc, m_size * capacity, d)

        gate_h = jnp.einsum("ecd,edf->ecf", pol.cast_in(grouped),
                            wg.astype(cfg.compute_dtype),
                            preferred_element_type=jnp.float32)
        up_h = jnp.einsum("ecd,edf->ecf", pol.cast_in(grouped),
                          wu.astype(cfg.compute_dtype),
                          preferred_element_type=jnp.float32)
        hidden = (L.silu(gate_h) * up_h).astype(cfg.compute_dtype)
        eo = jnp.einsum("ecf,efd->ecd", hidden,
                        wd.astype(cfg.compute_dtype),
                        preferred_element_type=jnp.float32)
        eo = eo.astype(cfg.compute_dtype)

        # ---- return: reverse all-to-all ----
        back = eo.reshape(e_loc, m_size, capacity, d)             .transpose(1, 0, 2, 3).reshape(m_size, e_loc * capacity, d)
        mine = jax.lax.all_to_all(back, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        mine = mine.reshape(e * capacity, d)

        contrib = jnp.where(
            keep[:, None],
            mine[jnp.minimum(slot, e * capacity - 1)]
            * sg[:, None].astype(cfg.compute_dtype),
            jnp.zeros((), cfg.compute_dtype))
        out = jnp.zeros((tl, d), cfg.compute_dtype).at[st].add(contrib)
        aux = jax.lax.pmean(aux_loc, flat_axes)
        return out, aux

    out, aux = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(flat_axes, None),          # tokens: disjoint slices
                  P(None, None),               # router replicated
                  P("model", None, None),      # experts over model
                  P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(flat_axes, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out.astype(x.dtype), aux


def dense_mlp(p: Params, x: Array, cfg: TransformerConfig) -> Array:
    pol = cfg.policy
    h = L.silu(L.dense({"w": p["w_gate"]}, x, pol)) * L.dense({"w": p["w_up"]}, x, pol)
    h = constrain(h, BATCH, None, "model")
    return L.dense({"w": p["w_down"]}, h, pol)


# --------------------------------------------------------------------------
# blocks / forward
# --------------------------------------------------------------------------


def _layer(lp: Params, x: Array, cfg: TransformerConfig, positions: Array,
           kv: tuple[Array, Array] | None, q_offset) -> tuple[Array, Array, tuple]:
    """One decoder block.  If ``kv`` is given it is the (k_cache, v_cache)
    to attend over (decode); otherwise self-attention on x (train/prefill).
    Returns (x_out, aux_loss, (k_new, v_new))."""
    b, s, d = x.shape
    pol = cfg.policy
    h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
    q = L.dense({"w": lp["attn"]["wq"]}, h, pol).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = L.dense({"w": lp["attn"]["wk"]}, h, pol).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = L.dense({"w": lp["attn"]["wv"]}, h, pol).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    q = constrain(L.apply_rope(q, positions, cfg.rope_theta),
                  BATCH, None, "model", None)
    k = constrain(L.apply_rope(k, positions, cfg.rope_theta),
                  BATCH, None, "model", None)
    v = constrain(v, BATCH, None, "model", None)

    if kv is None:
        attn_out = attention(q, k, v, cfg, causal=True, window=cfg.window,
                             q_offset=q_offset)
    else:
        kc, vc = kv
        attn_out = attention(q, kc, vc, cfg, causal=True, window=cfg.window,
                             q_offset=q_offset)
    attn_out = constrain(attn_out.reshape(b, s, cfg.n_heads * cfg.d_head),
                         BATCH, None, "model")
    seq_ax = "model" if cfg.sequence_parallel else None
    x = constrain(x + L.dense({"w": lp["attn"]["wo"]}, attn_out, pol),
                  BATCH, seq_ax, None)

    h2 = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
    if cfg.moe:
        blk = moe_block_a2a if _use_moe_a2a(cfg) else moe_block
        out, aux = blk(lp["moe"], h2.reshape(b * s, d), cfg)
        x = x + out.reshape(b, s, d)
    else:
        x = x + dense_mlp(lp["mlp"], h2, cfg)
        aux = jnp.zeros((), jnp.float32)
    x = constrain(x, BATCH, seq_ax, None)
    return x, aux, (k, v)


def forward(params: Params, tokens: Array, cfg: TransformerConfig,
            positions: Array | None = None) -> tuple[Array, Array]:
    """Full forward pass -> (final hidden states (B,S,D), total aux loss)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    x = constrain(L.embedding(params["embed"], tokens, cfg.policy),
                  BATCH, "model" if cfg.sequence_parallel else None, None)

    def body(carry, lp):
        x, aux = carry
        x, a, _ = _layer(lp, x, cfg, positions, None, 0)
        return (x, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return x, aux


def chunked_ce_loss(hidden: Array, unembed_w: Array, targets: Array,
                    cfg: TransformerConfig) -> Array:
    """Cross-entropy without materialising (T, V) logits: scan over
    sequence chunks, unembed + logsumexp per chunk, under remat."""
    b, s, d = hidden.shape
    chunk = min(cfg.ce_chunk, s)
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    n = (s + pad) // chunk
    hc = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(tot, inp):
        h, t = inp
        logits = jax.lax.dot_general(
            h.astype(cfg.compute_dtype), unembed_w.astype(cfg.compute_dtype),
            (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        logits = constrain(logits, BATCH, None, "model")  # vocab-sharded
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(t, 0)[..., None], axis=-1)[..., 0]
        valid = t >= 0
        nll = jnp.where(valid, lse - picked, 0.0)
        return (tot[0] + jnp.sum(nll), tot[1] + jnp.sum(valid)), None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (tot_nll, tot_cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hc, tc))
    return tot_nll / jnp.maximum(tot_cnt, 1.0)


def lm_loss(params: Params, batch: dict, cfg: TransformerConfig) -> Array:
    hidden, aux = forward(params, batch["tokens"], cfg)
    loss = chunked_ce_loss(hidden, params["unembed"]["w"], batch["targets"], cfg)
    return loss + 0.01 * aux


def logits_fn(params: Params, tokens: Array, cfg: TransformerConfig) -> Array:
    """(B, S) -> (B, S, V) logits.  Only for small shapes / sampling."""
    hidden, _ = forward(params, tokens, cfg)
    return L.dense(params["unembed"], hidden, cfg.policy).astype(jnp.float32)


# --------------------------------------------------------------------------
# prefill / decode (serving)
# --------------------------------------------------------------------------


def cache_length(cfg: TransformerConfig, max_len: int) -> int:
    return min(cfg.window, max_len) if cfg.window is not None else max_len


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None) -> KVCache:
    s = cache_length(cfg, max_len)
    dt = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, batch, s, cfg.n_kv_heads, cfg.d_head)
    return KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                   jnp.zeros((), jnp.int32))


def prefill(params: Params, tokens: Array, cfg: TransformerConfig,
            max_len: int) -> tuple[Array, KVCache]:
    """Process the prompt; returns (last-token logits, primed cache).

    For windowed models the cache keeps the last ``window`` positions
    (ring layout: slot = pos % window)."""
    b, s = tokens.shape
    positions = jnp.arange(s)
    x = L.embedding(params["embed"], tokens, cfg.policy)
    s_cache = cache_length(cfg, max_len)

    def body(carry, lp):
        x, = carry
        x, _, (k, v) = _layer(lp, x, cfg, positions, None, 0)
        if cfg.window is not None and s > s_cache:
            k_keep, v_keep = k[:, -s_cache:], v[:, -s_cache:]
            # ring layout: absolute position p lives at slot p % window
            slots = (jnp.arange(s - s_cache, s)) % s_cache
            k_cache = jnp.zeros((b, s_cache) + k.shape[2:], k.dtype).at[:, slots].set(k_keep)
            v_cache = jnp.zeros((b, s_cache) + v.shape[2:], v.dtype).at[:, slots].set(v_keep)
        else:
            pad = s_cache - s
            k_cache = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad > 0 else k[:, :s_cache]
            v_cache = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad > 0 else v[:, :s_cache]
        # cache layout: batch over data, head dim over model (see
        # repro.distributed.sharding.lm_batch_specs for the rationale)
        k_cache = constrain(k_cache, BATCH, None, None, "model")
        v_cache = constrain(v_cache, BATCH, None, None, "model")
        return (x,), (k_cache, v_cache)

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (x,), (k_all, v_all) = jax.lax.scan(body, (x,), params["layers"])
    x = L.rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
    logits = L.dense(params["unembed"], x, cfg.policy).astype(jnp.float32)
    return logits[:, 0], KVCache(k_all, v_all, jnp.asarray(s, jnp.int32))


def decode_step(params: Params, token: Array, cache: KVCache,
                cfg: TransformerConfig) -> tuple[Array, KVCache]:
    """One decode step.  ``token``: (B,) int32.  Returns (logits (B, V),
    updated cache).  Windowed models use ring-buffer slots."""
    b = token.shape[0]
    pos = cache.length  # scalar: absolute position of the new token
    s_cache = cache.k.shape[2]
    positions = jnp.full((b, 1), pos, jnp.int32)
    x = L.embedding(params["embed"], token[:, None], cfg.policy)

    windowed = cfg.window is not None
    slot = (pos % s_cache) if windowed else jnp.minimum(pos, s_cache - 1)

    def body(x, inp):
        lp, kc, vc = inp
        bsz, _, d = x.shape
        pol = cfg.policy
        h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q = L.dense({"w": lp["attn"]["wq"]}, h, pol).reshape(bsz, 1, cfg.n_heads, cfg.d_head)
        k = L.dense({"w": lp["attn"]["wk"]}, h, pol).reshape(bsz, 1, cfg.n_kv_heads, cfg.d_head)
        v = L.dense({"w": lp["attn"]["wv"]}, h, pol).reshape(bsz, 1, cfg.n_kv_heads, cfg.d_head)
        # decode keeps everything in the cache layout (head dim over
        # model) so the dynamic-update-slice never needs a reshard.
        q = constrain(L.apply_rope(q, positions, cfg.rope_theta),
                      BATCH, None, None, "model")
        k = constrain(L.apply_rope(k, positions, cfg.rope_theta),
                      BATCH, None, None, "model")
        v = constrain(v, BATCH, None, None, "model")
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, slot, 0, 0))
        kc = constrain(kc, BATCH, None, None, "model")
        vc = constrain(vc, BATCH, None, None, "model")

        # absolute position of each cache slot
        slots = jnp.arange(s_cache)
        if windowed:
            # slot holds the latest absolute position p <= pos with p % S == slot
            abs_pos = pos - ((pos - slots) % s_cache)
        else:
            abs_pos = slots
        valid = (abs_pos <= pos) & (abs_pos >= 0)  # >=0: unwritten ring slots
        if windowed:
            valid &= abs_pos > pos - cfg.window

        rep = cfg.n_heads // cfg.n_kv_heads
        kr = jnp.repeat(kc, rep, axis=2) if rep > 1 else kc
        vr = jnp.repeat(vc, rep, axis=2) if rep > 1 else vc
        # RoPE on cached keys was applied at insert time with their own
        # positions; scores need no further correction.
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * (cfg.d_head ** -0.5)
        s_ = jnp.where(valid[None, None, None, :], s_, -1e30)
        p_ = jax.nn.softmax(s_, axis=-1)
        attn_out = jnp.einsum("bhqk,bkhd->bqhd", p_, vr.astype(jnp.float32))
        attn_out = attn_out.astype(cfg.compute_dtype).reshape(bsz, 1, cfg.n_heads * cfg.d_head)
        x = x + L.dense({"w": lp["attn"]["wo"]}, attn_out, pol)
        h2 = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        if cfg.moe:
            out, _ = moe_block(lp["moe"], h2.reshape(bsz, d), cfg)
            x = x + out.reshape(bsz, 1, d)
        else:
            x = x + dense_mlp(lp["mlp"], h2, cfg)
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        lambda c, inp: body(c, inp), x, (params["layers"], cache.k, cache.v))
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = L.dense(params["unembed"], x, cfg.policy).astype(jnp.float32)
    return logits[:, 0], KVCache(k_new, v_new, pos + 1)
