"""Sharding rules: path-pattern -> PartitionSpec, per model family.

Strategy (see DESIGN.md section 5):

  * mesh axes ``(data, model)`` single-pod, ``(pod, data, model)``
    multi-pod.  The ``pod`` axis is pure data parallelism: batch
    dimensions shard over ``("pod", "data")`` when present, and
    parameters/optimizer state FSDP-shard over ``data`` only (so the
    inter-pod DCN link carries gradient all-reduce, not param
    all-gathers — the standard multi-slice layout).
  * LM params: Megatron TP over ``model`` (attention heads, FFN
    columns) + FSDP over ``data`` on the other matrix axis.
  * MoE: experts sharded over ``model`` (expert parallelism), dense
    attention as above.
  * KV caches: batch over ``data``; sequence axis over ``model``
    (sequence parallelism for decode — kv=1 MQA cannot shard heads).
  * vision/diffusion/detector: DP everywhere; TP over ``model`` for
    the widest matmuls (d_ff / channel axes) where divisible.

Rules are (regex, PartitionSpec) lists matched against ``path/like/this``
param paths; the first match wins.  ``spec_tree`` builds the full
PartitionSpec pytree for any param pytree.
"""

from __future__ import annotations

import re
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Sequence[tuple[str, P]]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def spec_tree(params: Any, rules: Rules, default: P = P()) -> Any:
    """Map every leaf to the PartitionSpec of the first matching rule."""

    def pick(path, leaf):
        del leaf
        ps = _path_str(path)
        for pat, spec in rules:
            if re.search(pat, ps):
                return spec
        return default

    return jax.tree_util.tree_map_with_path(pick, params)


def _filter_axes(ax):
    """Drop mesh axes that don't exist on the active mesh."""
    if ax is None:
        return None
    if isinstance(ax, tuple):
        kept = tuple(a for a in ax if a in _MESH_SIZES)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]
    return ax if ax in _MESH_SIZES else None


def adapt_spec(spec: P) -> P:
    """Adapt a hand-written PartitionSpec to the active mesh (drops
    unknown axis names, e.g. 'pod' on single-pod meshes)."""
    out = [_filter_axes(ax) for ax in spec]
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def adapt_tree(tree):
    return jax.tree.map(adapt_spec, tree,
                        is_leaf=lambda x: isinstance(x, P))


_MESH_SIZES: dict[str, int] = {}


def set_mesh_axis_sizes(mesh: Mesh) -> None:
    """Record axis sizes so spec_tree can check divisibility."""
    global _MESH_SIZES
    _MESH_SIZES = dict(zip(mesh.axis_names, mesh.devices.shape))


def _axis_size(ax) -> int:
    if isinstance(ax, tuple):
        return int(np.prod([_MESH_SIZES.get(a, 1) for a in ax]))
    return _MESH_SIZES.get(ax, 1)


# --------------------------------------------------------------------------
# per-family rules
# --------------------------------------------------------------------------

# batch axes: ("pod", "data") when the pod axis exists; spec_tree's
# divisibility check silently drops "pod" on single-pod meshes because
# the axis is absent from _MESH_SIZES (size 1).
BATCH = ("pod", "data")


def lm_param_rules(fsdp: bool = True, n_experts: int = 0,
                   model_axis: int = 16) -> Rules:
    """Megatron TP + optional FSDP for the LM family.

    Layer params are stacked (L, din, dout): dim 0 = layer (never
    sharded), dim 1/2 = matrix.  TP shards the 'parallel' matrix axis
    over `model`; FSDP shards the other one over `data`.

    MoE placement is adaptive: when the expert count divides the model
    axis (qwen3: 128 % 16 == 0) experts shard over `model` (EP);
    otherwise (mixtral: 8 experts on a 16-wide axis) the expert FFN
    width shards over `model` (TP-within-expert) so the big matrices
    never replicate.
    """
    d = "data" if fsdp else None
    ep = n_experts > 0 and n_experts % model_axis == 0
    rules = [
        # attention: column-parallel qkv, row-parallel out
        (r"layers/attn/wq$|layers/attn/wk$|layers/attn/wv$", P(None, d, "model")),
        (r"layers/attn/wo$", P(None, "model", d)),
        # dense mlp: column-parallel gate/up, row-parallel down
        (r"layers/mlp/w_gate$|layers/mlp/w_up$", P(None, d, "model")),
        (r"layers/mlp/w_down$", P(None, "model", d)),
        (r"layers/moe/router$", P(None, d, None)),
    ]
    if ep:
        rules += [
            (r"layers/moe/w_gate$|layers/moe/w_up$", P(None, "model", d, None)),
            (r"layers/moe/w_down$", P(None, "model", d, None)),
        ]
    else:
        rules += [
            (r"layers/moe/w_gate$|layers/moe/w_up$", P(None, None, d, "model")),
            (r"layers/moe/w_down$", P(None, None, "model", d)),
        ]
    rules += [
        # norms replicated
        (r"ln", P()),
        # embeddings: vocab over model (keeps 152k-vocab logits sharded)
        (r"embed/emb$", P("model", d)),
        (r"unembed/w$", P(d, "model")),
    ]
    return rules


def lm_batch_specs(kind: str) -> dict[str, P]:
    if kind == "train":
        return {"tokens": P(BATCH, None), "targets": P(BATCH, None)}
    if kind == "prefill":
        return {"tokens": P(BATCH, None)}
    if kind == "decode":
        # cache (L, B, S, KVH, Dh): batch over data, HEAD DIM over model.
        # Sharding S would make the per-step dynamic-update-slice (a
        # traced position into the sharded axis) trigger involuntary
        # full rematerialisation in SPMD; Dh shards cleanly for every
        # assigned KVH (1/3/4/8) and keeps the cache 256-way split.
        return {
            "token": P(BATCH),
            "cache_k": P(None, BATCH, None, None, "model"),
            "cache_v": P(None, BATCH, None, None, "model"),
            "cache_len": P(),
        }
    raise ValueError(kind)


def vision_param_rules() -> Rules:
    return [
        # ViT stacked layer matrices: (L, din, dout) — TP on dout, FSDP din
        (r"layers/wqkv$|layers/w1$", P(None, "data", "model")),
        (r"layers/wo$|layers/w2$", P(None, "model", "data")),
        # ConvNeXt pointwise convs (stacked): (L, din, dout)
        (r"stages/\d+/pw1/w$", P(None, "data", "model")),
        (r"stages/\d+/pw2/w$", P(None, "model", "data")),
        # classifier head
        (r"head/w$", P(None, "model")),
        # conv kernels (HWIO): shard output channels over model
        (r"conv|stem|dw|proj|down|lateral", P(None, None, None, "model")),
        (r".*", P()),
    ]


def vision_batch_specs() -> dict[str, P]:
    return {"images": P(BATCH, None, None, None), "labels": P(BATCH)}


def diffusion_param_rules() -> Rules:
    return [
        # MMDiT stacked stream matrices
        (r"double/(img|txt)/wqkv$|double/(img|txt)/w1$", P(None, "data", "model")),
        (r"double/(img|txt)/wo$|double/(img|txt)/w2$", P(None, "model", "data")),
        (r"single/wqkv$|single/w1$", P(None, "data", "model")),
        (r"single/wo2$", P(None, "model", "data")),
        (r"double/(img|txt)/mod/w$|single/mod/w$", P(None, None, "model")),
        # UNet transformer blocks (stacked under blocks/)
        (r"blocks/(wq1|wkv1|wq2|wkv2|ff1)/w$", P(None, None, "model")),
        (r"blocks/(wo1|wo2|ff2)/w$", P(None, "model", None)),
        # big convs: out-channels over model
        (r"conv|skip|proj", P(None, None, None, "model")),
        (r".*", P()),
    ]


def diffusion_batch_specs(cfg) -> dict[str, P]:
    from repro.models.diffusion import MMDiTConfig

    base = {"latents": P(BATCH, None, None, None), "ctx": P(BATCH, None, None)}
    if isinstance(cfg, MMDiTConfig):
        base.update({"pooled": P(BATCH, None), "guidance": P(BATCH),
                     "t": P(BATCH), "dt": P(BATCH)})
    else:
        base.update({"add_emb": P(BATCH, None), "t": P(BATCH),
                     "t_prev": P(BATCH)})
    return base


def detector_param_rules() -> Rules:
    return [
        (r"conv/w$", P(None, None, None, "model")),
        (r".*", P()),
    ]


def detector_batch_specs() -> dict[str, P]:
    return {"images": P(BATCH, None, None, None)}


# --------------------------------------------------------------------------
# activation constraints (annotated inside model code)
# --------------------------------------------------------------------------


def current_mesh():
    """The mesh of the active trace context (``jax.set_mesh`` or
    ``with mesh:``), as an ``AbstractMesh``, or None outside one."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def mesh_axis_size(name: str) -> int:
    """Size of a mesh axis in the active trace context (1 if absent)."""
    am = current_mesh()
    if am is None or name not in am.axis_names:
        return 1
    return dict(zip(am.axis_names, am.axis_sizes))[name]


import contextlib

_CONSTRAIN_ENABLED = [True]


@contextlib.contextmanager
def no_activation_constraints():
    """Disable in-model ``constrain`` calls while tracing.

    Used by serving deployments that replicate small-model weights:
    the training-oriented channel-sharding annotations would otherwise
    force reshard collectives against the replicated layout.
    """
    _CONSTRAIN_ENABLED.append(False)
    try:
        yield
    finally:
        _CONSTRAIN_ENABLED.pop()


def constrain(x, *spec):
    """``with_sharding_constraint`` that degrades gracefully.

    Models call ``constrain(x, BATCH, None, "model")`` at layer
    boundaries; outside a mesh context (CPU smoke tests) this is a
    no-op, and axes that are absent from the active mesh or don't
    divide the dimension are dropped — the same adaptation rule the
    launcher applies to the input shardings.
    """
    if not _CONSTRAIN_ENABLED[-1]:
        return x
    mesh = current_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    axes = []
    for dim, ax in enumerate(spec):
        if ax is None:
            axes.append(None)
            continue
        names = [a for a in (ax if isinstance(ax, tuple) else (ax,))
                 if a in sizes]
        if not names:
            axes.append(None)
            continue
        size = int(np.prod([sizes[a] for a in names]))
        if dim < x.ndim and x.shape[dim] % size == 0:
            axes.append(tuple(names) if len(names) > 1 else names[0])
        else:
            axes.append(None)
    return jax.lax.with_sharding_constraint(x, P(*axes))


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_shardings(mesh: Mesh, spec_pytree) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_pytree,
        is_leaf=lambda x: isinstance(x, P),
    )


def opt_state_specs(param_specs) -> dict:
    """AdamW moments mirror param sharding; step is replicated."""
    return {
        "mu": param_specs,
        "nu": param_specs,
        "step": P(),
    }
