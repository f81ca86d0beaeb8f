"""Sharding rules: param/cache/batch PartitionSpec builders + mesh modes.

The substrate owns every placement decision; the numerics never see an
axis name.  Three ideas:

* ``make_shard_cfg`` — turn (mesh, model config, batch) into a ``ShardCfg``
  posture.  ``mode="fsdp_tp"`` is the production 2-D layout (params FSDP-
  sharded over the data axes, tensor-parallel over ``model``);
  ``mode="dp"`` is the pure data-parallel posture (params replicated, one
  gradient all-reduce per step — the shape the compressed cross-pod
  all-reduce plugs into).

* spec builders walk the actual param/cache pytree (arrays or
  ``ShapeDtypeStruct``s from ``jax.eval_shape``) and emit a mirrored tree
  of ``PartitionSpec``s from per-leaf rules keyed on the tree path.  Every
  rule is divisibility-guarded: a dim that does not divide by its mesh
  axis stays replicated rather than erroring (kv_heads=8 shards over
  model=4 but is replicated over model=16).

* ``named`` lifts a spec tree to ``NamedSharding``s for device_put /
  jit in_shardings.

``_path_str`` is the canonical "a/b/c" rendering of a tree path; the
optimizer's weight-decay filter keys on it, so spec rules and decay masks
agree on what a leaf is called.
"""
from __future__ import annotations

import math
from typing import Any

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig, ShardCfg


# ---------------------------------------------------------------------------
# paths
# ---------------------------------------------------------------------------
def _path_str(path) -> str:
    """Render a jax tree path as "a/b/0/c" (DictKey/SequenceKey/attr)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


# ---------------------------------------------------------------------------
# mesh posture
# ---------------------------------------------------------------------------
def _axes_prod(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return math.prod(mesh.shape[a] for a in axes)


def make_shard_cfg(mesh, cfg: ModelConfig, global_batch: int, *,
                   mode: str = "fsdp_tp", moe_mode: str | None = None,
                   ssm_sp: bool = False) -> ShardCfg:
    """Distribution posture for ``cfg`` on ``mesh``.

    mode:
      fsdp_tp (default) — batch/FSDP over the ("pod", "data") axes, tensor
                          parallelism over "model" (the 2-D production
                          layout; "auto" is an alias)
      dp                — pure data parallelism over EVERY mesh axis:
                          params replicated, batch sharded over all axes,
                          one gradient all-reduce per step
                          (train/step.py::_make_dp_train_step; with a
                          "pod" axis the cross-pod hop can run int8-EF
                          compressed — dist.compression)
    """
    names = tuple(mesh.axis_names)
    if mode in ("fsdp_tp", "auto"):
        dp_axes = tuple(a for a in ("pod", "data") if a in names)
        dp: Any = dp_axes[0] if len(dp_axes) == 1 else dp_axes
        tp = "model" if "model" in names else None
        replicate = False
    elif mode == "dp":
        dp = names if len(names) > 1 else names[0]
        tp = None
        replicate = True
    else:
        raise ValueError(f"unknown shard mode {mode!r}")

    if moe_mode is None:
        moe_mode = "tp" if (cfg.num_experts and tp is not None) else "local"
    batch_sharded = global_batch % _axes_prod(mesh, dp) == 0
    return ShardCfg(mesh=mesh, dp=dp, tp=tp, moe_mode=moe_mode,
                    ssm_sp=ssm_sp, batch_sharded=batch_sharded,
                    replicate_params=replicate)


# ---------------------------------------------------------------------------
# param specs
# ---------------------------------------------------------------------------
def _guard(mesh, axis, dim: int):
    """axis iff ``dim`` divides evenly over it (else replicated)."""
    if axis is None:
        return None
    if dim % _axes_prod(mesh, axis) != 0:
        return None
    return axis


def param_spec_tree(params, cfg: ModelConfig, mesh, shard: ShardCfg):
    """PartitionSpec tree mirroring ``params`` (arrays or eval_shape
    structs).

    Layout rules (fsdp_tp): attention heads and ffn hidden dims are
    tensor-parallel over ``tp``; the embedding is vocab-parallel; the
    model dim is FSDP-sharded over the data axes.  Rules match on the
    leaf's path, are right-aligned against its trailing dims, and pad
    leading (layer-stack) axes with None — the same rule covers a layer
    leaf and its ``lax.scan``-stacked form.
    """
    fsdp = None if shard.replicate_params else shard.dp
    tp = None if shard.replicate_params else shard.tp
    F = lambda d: _guard(mesh, fsdp, d)
    T = lambda d: _guard(mesh, tp, d)

    def rule(parts: tuple, shape: tuple):
        """Returns right-aligned entries for the trailing dims, or None
        for 'no rule' (fallback)."""
        name = parts[-1]
        parent = parts[-2] if len(parts) >= 2 else ""
        if len(shape) <= 1:
            return tuple(None for _ in shape)   # scalars / norm scales /
            # biases: tiny — replicate rather than ZeRO-shard
        if parent == "attn" and name in ("wq", "wk", "wv") and len(shape) >= 3:
            d, h, hd = shape[-3:]
            return (F(d), T(h), None)
        if parent == "attn" and name == "wo" and len(shape) >= 3:
            h, hd, d = shape[-3:]
            return (T(h), None, F(d))
        if parent == "attn" and name in ("bq", "bk", "bv") and len(shape) >= 2:
            h, hd = shape[-2:]
            return (T(h), None)
        if parent == "embed" and name == "table":
            v, d = shape[-2:]
            return (T(v), F(d))
        if parent == "unembed" and name == "w":
            d, v = shape[-2:]
            return (F(d), T(v))
        if parent == "experts" and len(shape) >= 3:
            e = shape[-3]
            if name == "down":                      # (E, f, d)
                return (T(e), None, F(shape[-1]))
            return (T(e), F(shape[-2]), None)       # gate/up (E, d, f)
        if name == "router":
            return tuple(None for _ in shape[-2:])
        if name == "w" and len(shape) >= 2:
            d_in, d_out = shape[-2:]
            if parent in ("down", "mlp_down", "out_proj"):
                return (T(d_in), F(d_out))          # contraction dim is TP
            return (F(d_in), T(d_out))              # gate/up/in_proj/...
        return None

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        parts = tuple(_path_str((k,)) for k in path)
        shape = tuple(leaf.shape)
        nd = len(shape)
        entries = rule(parts, shape)
        if entries is None:
            # fallback: FSDP the largest divisible dim, else replicate
            entries = [None] * nd
            if nd and fsdp is not None:
                order = sorted(range(nd), key=lambda i: -shape[i])
                for i in order:
                    if shape[i] and _guard(mesh, fsdp, shape[i]) is not None \
                            and shape[i] >= _axes_prod(mesh, fsdp):
                        entries[i] = fsdp
                        break
            entries = tuple(entries)
        else:
            entries = (None,) * (nd - len(entries)) + tuple(entries)
        if all(e is None for e in entries):
            entries = ()                        # canonical replicated spec
        specs.append(P(*entries))
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# cache specs
# ---------------------------------------------------------------------------
def cache_spec_tree(caches, cfg: ModelConfig, mesh, shard: ShardCfg):
    """Decode-cache PartitionSpecs.

    Batch shards over the data axes; attention KV caches additionally
    shard the SEQUENCE dim over ``tp`` (flash-decode: each TP rank scans
    its slice of the context, combining partial softmax online), guarded
    on divisibility like everything else.  SSM/conv recurrent states are
    batch-sharded only — they are O(1) in sequence.
    """
    dp = shard.dp if shard.batch_sharded else None
    tp = shard.tp
    batch_axis = 0 if cfg.family == "ssm" else 1   # ssm caches lack the
    # leading stacked-layer axis (tuple-of-states)

    def spec(leaf):
        shape = tuple(leaf.shape)
        nd = len(shape)
        entries = [None] * nd
        if nd > batch_axis:
            entries[batch_axis] = _guard(mesh, dp, shape[batch_axis])
        is_kv = (nd == 5 and shape[3] == cfg.num_kv_heads
                 and shape[4] == cfg.head_dim)
        if is_kv and tp is not None:
            entries[2] = _guard(mesh, tp, shape[2])
        return P(*entries)

    return jax.tree.map(spec, caches)


# ---------------------------------------------------------------------------
# batch specs
# ---------------------------------------------------------------------------
def batch_spec_tree(batch, mesh, shard: ShardCfg):
    """Input-batch specs: leading (batch) dim over the data axes."""
    dp = shard.dp if shard.batch_sharded else None

    def spec(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        return P(_guard(mesh, dp, leaf.shape[0]), *([None] * (nd - 1)))

    return jax.tree.map(spec, batch)


# ---------------------------------------------------------------------------
# NamedSharding lift
# ---------------------------------------------------------------------------
def named(specs, mesh):
    """Spec tree -> NamedSharding tree (device_put / jit shardings)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
