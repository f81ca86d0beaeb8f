"""ModelConfig — the single dataclass describing every assigned architecture.

One instance fully determines parameter shapes, block composition and the
train/prefill/decode computation.  ``src/repro/configs/<arch>.py`` files are
thin constructors of this dataclass with the published dimensions.

``ShardCfg`` carries the distribution decisions (mesh + axis names + per-
family strategy knobs) into the model code.  ``ShardCfg(None)`` is the
single-device path used by smoke tests: every collective degenerates to a
no-op and no sharding constraint is emitted.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    qkv_bias: bool = False
    rope_theta: float = 500_000.0
    tie_embeddings: bool = False
    norm_eps: float = 1e-6

    # --- MoE ---------------------------------------------------------------
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    router_z_coef: float = 1e-3

    # --- SSM / Mamba2 (hybrid) ----------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    conv_width: int = 4
    ssm_chunk: int = 128
    attn_every: int = 0            # hybrid: shared attn+MLP block period

    # --- xLSTM ---------------------------------------------------------------
    slstm_indices: tuple = ()      # layer indices that are sLSTM (rest mLSTM)
    mlstm_proj_factor: float = 2.0
    # BPTT unroll: recurrent weights stay VMEM-resident across k unrolled
    # steps (divides the per-step weight re-read by k) at k× HLO body size
    slstm_unroll: int = 1

    # --- modality stubs -------------------------------------------------------
    num_codebooks: int = 0         # audio (musicgen): EnCodec streams
    num_prefix_tokens: int = 0     # vlm (paligemma): SigLIP patch embeddings

    # --- numerics / memory -----------------------------------------------------
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    remat: str = "block"           # none | block | dots
    q_chunk: int = 1024
    # kv_chunk = full sequence: ONE kv pass per q-chunk, so the online-
    # softmax accumulator never round-trips HBM as a scan carry — the same
    # HBM traffic as a flash-attention kernel (which holds acc in VMEM and
    # streams kv in hardware-sized blocks).  Finite values model kernels
    # that spill the accumulator; used in ablations.
    kv_chunk: int = 1 << 30
    scan_layers: bool = True

    # --- capability flags -------------------------------------------------------
    subquadratic: bool = False     # can run long_500k decode (O(1)/O(S) state)

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # ---- derived dims -----------------------------------------------------
    @property
    def d_inner(self) -> int:               # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def conv_dim(self) -> int:              # channels fed through causal conv
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    def param_count(self) -> int:
        """Total parameters (used for 6·N·D MODEL_FLOPS and docs)."""
        import math

        import repro.models.model as m

        shapes = jax.eval_shape(lambda: m.init_params(self, jax.random.PRNGKey(0)))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: only routed experts count)."""
        import math

        total = self.param_count()
        if not self.num_experts:
            return total
        import repro.models.model as m

        shapes = jax.eval_shape(lambda: m.init_params(self, jax.random.PRNGKey(0)))
        expert_total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            if any("experts" == getattr(k, "key", None) for k in path):
                expert_total += math.prod(leaf.shape)
        active_frac = (self.num_experts_per_tok / self.num_experts)
        return total - expert_total + int(expert_total * active_frac)


@dataclasses.dataclass(frozen=True)
class ShardCfg:
    """Distribution decisions, threaded through the model code.

    mesh=None is the single-device path (tests): constraints and collectives
    are skipped.  ``dp``/``tp`` are mesh-axis names (dp may be a tuple, e.g.
    ("pod", "data") on the multi-pod mesh).  ``moe_mode``:
      local — no collectives, every device computes all experts (tests)
      tp    — experts sharded over ``tp``; activations replicated on ``tp``;
              combine via psum (baseline; collective = 1 all-reduce/layer)
      a2a   — tokens sequence-sharded over ``tp``; all_to_all dispatch
              (optimized; see EXPERIMENTS.md §Perf)
    ``ssm_sp``: sequence-shard Mamba2/conv over ``tp`` with halo exchange +
    chunk-state relay (the paper's ghost-zone pattern on the sequence axis).
    """

    mesh: Any = None
    dp: Any = "data"
    tp: str | None = "model"
    moe_mode: str = "local"
    ssm_sp: bool = False
    batch_sharded: bool = True     # False when global batch < |dp| (long_500k)
    replicate_params: bool = False # small models: pure DP, one grad AR/step

    @property
    def dp_axes(self) -> tuple:
        return self.dp if isinstance(self.dp, tuple) else (self.dp,)

    def act_spec(self, *trailing):
        """PartitionSpec for (B, ...) activations."""
        from jax.sharding import PartitionSpec as P

        if self.mesh is None:
            return None
        batch = self.dp if self.batch_sharded else None
        return P(batch, *trailing)

    def constrain(self, x, spec):
        if self.mesh is None or spec is None:
            return x
        from jax.sharding import NamedSharding

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def constrain_act(self, x, *trailing):
        return self.constrain(x, self.act_spec(*trailing))


LOCAL = ShardCfg(mesh=None, moe_mode="local")
