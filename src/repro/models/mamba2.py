"""Mamba2 (SSD) block — chunked state-space dual for train/prefill, O(1)
recurrent decode, and a sequence-parallel mode built on the paper's
ghost-zone machinery.

The chunked SSD algorithm is itself the paper's 3DBLOCK idea on the time
axis: tile the sequence into chunks, compute the quadratic intra-chunk part
locally (the "interior"), and pass a tiny carried state between chunks (the
"ghost cell").  Sequence parallelism (``ssm_sp``) extends the same pattern
across mesh shards: the causal-conv halo is exchanged with
``core.halo.exchange_pad`` (width = conv_width - 1, one-sided) and the SSD
chunk state is relayed with an all-gather + local prefix product — a 1-cell
ghost region on the sequence axis.

Layout: x (B, S, G, R, P) with H = G·R heads (G = ``ssm_groups`` share one
(B̄, C̄) pair, as in Mamba2).  All SSD math runs in fp32.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import layers
from repro.models.config import ModelConfig, ShardCfg


class Mamba2State(NamedTuple):
    conv: jnp.ndarray   # (B, W-1, conv_dim)
    ssm: jnp.ndarray    # (B, G, R, N, P) fp32


def init_mamba2(key, cfg: ModelConfig) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    g, n, h = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    w = cfg.conv_width
    dt = cfg.param_dtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d_in_proj = 2 * di + 2 * g * n + h
    # dt_bias: inverse-softplus of dt ~ U[1e-3, 1e-1] (mamba2 init)
    u = jax.random.uniform(k4, (h,), jnp.float32, np.log(1e-3), np.log(1e-1))
    dt0 = jnp.exp(u)
    return {
        "in_proj": layers.init_dense(k1, d, d_in_proj, dt),
        "conv_w": layers.truncated_normal(k2, (w, cfg.conv_dim),
                                          1.0 / np.sqrt(w), jnp.float32),
        "conv_b": jnp.zeros((cfg.conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),  # softplus^-1(dt0)
        "norm": layers.init_rmsnorm(di),
        "out_proj": layers.init_dense(k3, di, d, dt),
    }


def _causal_conv(xbc: jnp.ndarray, conv_w, conv_b,
                 prefix: jnp.ndarray | None) -> jnp.ndarray:
    """Depthwise causal conv, width W.  ``prefix``: (B, W-1, C) carried
    context (zeros at sequence start; previous shard's tail under SP)."""
    b, s, c = xbc.shape
    w = conv_w.shape[0]
    if prefix is None:
        prefix = jnp.zeros((b, w - 1, c), xbc.dtype)
    xpad = jnp.concatenate([prefix.astype(xbc.dtype), xbc], axis=1)
    y = sum(xpad[:, i:i + s].astype(jnp.float32) * conv_w[i]
            for i in range(w))
    return jax.nn.silu(y + conv_b).astype(xbc.dtype)


def _split_proj(cfg: ModelConfig, zxbcdt: jnp.ndarray):
    di, g, n, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + cfg.conv_dim]
    dt = zxbcdt[..., di + cfg.conv_dim:]
    assert dt.shape[-1] == h
    return z, xbc, dt


def _gr(cfg: ModelConfig):
    g = cfg.ssm_groups
    return g, cfg.ssm_heads // g


def ssd_chunked(x, dt, a, b_, c_, chunk: int, init_state=None,
                states_only: bool = False):
    """Chunked SSD.  x (B,S,G,R,P) fp32, dt (B,S,G,R) fp32 (post-softplus),
    a (G,R) fp32 (negative), b_/c_ (B,S,G,N) fp32.

    Returns (y (B,S,G,R,P), final_state (B,G,R,N,P)).  With
    ``states_only=True`` skips the quadratic intra-chunk work and returns
    (None, final_state) — the cheap first pass of the sequence-parallel
    scheme.
    """
    return ssd_core(x, dt * a, dt, b_, c_, chunk, init_state, states_only)


def ssd_core(x, log_decay, in_scale, b_, c_, chunk: int, init_state=None,
             states_only: bool = False):
    """Chunked linear-recurrence core shared by Mamba2 SSD and mLSTM.

    State recursion  S_t = exp(log_decay_t) S_{t-1} + in_scale_t B_t (x) x_t
    with output      y_t = C_t^T S_t.
    Mamba2 passes (log_decay, in_scale) = (dt*a, dt); the mLSTM passes
    (log sigmoid(f̃), exp(ĩ)) — decay and input gate decoupled.
    Shapes: x (B,S,G,R,P), log_decay/in_scale (B,S,G,R), b_/c_ (B,S,G,N).
    """
    bsz, s, g, r, p = x.shape
    n = b_.shape[-1]
    l = min(chunk, s)
    pad = (-s) % l
    if pad:
        x, log_decay, in_scale, b_, c_ = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, log_decay, in_scale, b_, c_))
    nc = (s + pad) // l
    xc = x.reshape(bsz, nc, l, g, r, p)
    dtc = in_scale.reshape(bsz, nc, l, g, r)
    bc = b_.reshape(bsz, nc, l, g, n)
    cc = c_.reshape(bsz, nc, l, g, n)

    da = log_decay.reshape(bsz, nc, l, g, r)       # (B,nc,L,G,R)  negative
    cum = jnp.cumsum(da, axis=2)                   # within-chunk cumulative

    # chunk-end states: S_c = sum_j exp(cum_end - cum_j) dt_j B_j (x) x_j
    decay_to_end = jnp.exp(cum[:, :, -1:, :, :] - cum)        # (B,nc,L,G,R)
    sc = jnp.einsum("bclgn,bclgr,bclgrp->bcgrnp",
                    bc, decay_to_end * dtc, xc)               # (B,nc,G,R,N,P)
    chunk_decay = jnp.exp(cum[:, :, -1])                      # (B,nc,G,R)

    s0 = (jnp.zeros((bsz, g, r, n, p), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))

    def body(carry, inp):
        st, dec = inp
        nxt = carry * dec[..., None, None] + st
        return nxt, carry                                      # emit incoming

    final, s_in = lax.scan(
        body, s0, (jnp.moveaxis(sc, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    if states_only:
        return None, final
    s_in = jnp.moveaxis(s_in, 0, 1)                            # (B,nc,G,R,N,P)

    # intra-chunk quadratic + inter-chunk contribution: the tagged jnp path
    # below is the math of the Pallas SSD kernel (models/ssd.py — the (L,L)
    # decay/score temporaries stay in VMEM) and is priced as that kernel by
    # the roofline (launch/hlo_cost.py).
    with jax.named_scope("__kernel__ssd"):
        from repro.models.ssd import ssd_intra_reference

        y = ssd_intra_reference(xc, da, dtc, bc, cc, s_in)
    y = y.reshape(bsz, nc * l, g, r, p)[:, :s]
    return y, final


def _prep_ssm_inputs(params, cfg: ModelConfig, xbc, dt_raw):
    """Split conv output into (x, B̄, C̄) and finalize dt/A in fp32."""
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    g_, r = _gr(cfg)
    xs = xbc[..., :di]
    b_ = xbc[..., di:di + g * n].reshape(*xbc.shape[:-1], g, n)
    c_ = xbc[..., di + g * n:].reshape(*xbc.shape[:-1], g, n)
    shp = xs.shape[:-1]
    xs = xs.reshape(*shp, g_, r, cfg.ssm_head_dim).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"]).reshape(*shp, g_, r)
    a = -jnp.exp(params["A_log"]).reshape(g_, r)
    return xs, b_.astype(jnp.float32), c_.astype(jnp.float32), dt, a


def _finish(params, cfg: ModelConfig, y, xs, z):
    """D-skip, gated RMSNorm, out-projection."""
    d_skip = params["D"].reshape(*_gr(cfg))
    y = y + d_skip[..., None] * xs
    y = y.reshape(*y.shape[:-3], cfg.d_inner)
    y = layers.rmsnorm(params["norm"], y.astype(cfg.compute_dtype),
                       cfg.norm_eps) * jax.nn.silu(z.astype(cfg.compute_dtype))
    return layers.dense(params["out_proj"], y)


def mamba2_seq(params, cfg: ModelConfig, x: jnp.ndarray,
               shard: ShardCfg, state: Mamba2State | None = None,
               return_state: bool = False):
    """Full-sequence Mamba2: train / prefill.  x (B, S, d_model)."""
    if shard.ssm_sp and shard.mesh is not None and shard.tp:
        return _mamba2_seq_sp(params, cfg, x, shard, return_state)
    zxbcdt = layers.dense(params["in_proj"], x.astype(cfg.compute_dtype))
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    conv_prefix = state.conv if state is not None else None
    xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"], conv_prefix)
    xs, b_, c_, dt, a = _prep_ssm_inputs(params, cfg, xbc, dt_raw)
    init = state.ssm if state is not None else None
    y, final = ssd_chunked(xs, dt, a, b_, c_, cfg.ssm_chunk, init)
    out = _finish(params, cfg, y, xs, z)
    if not return_state:
        return out, None
    # conv state must be the PRE-activation xbc tail; recompute cheaply
    zx2 = _split_proj(cfg, zxbcdt)[1]
    w = cfg.conv_width
    new_state = Mamba2State(conv=zx2[:, -(w - 1):, :].astype(jnp.float32),
                            ssm=final)
    return out, new_state


def _mamba2_seq_sp(params, cfg: ModelConfig, x, shard: ShardCfg,
                   return_state: bool):
    """Sequence-parallel Mamba2 over the ``tp`` axis.

    Halo pattern (the paper's ghost region, on the sequence axis):
      conv:  (W-1)-wide one-sided halo via core.halo.exchange_pad/ppermute
      SSD:   two-pass chunk-state relay — local states_only pass, all-gather
             of (chunk_decay_total, final_state), local prefix product gives
             each shard its incoming state, then the exact local SSD.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.halo import AxisSpec, exchange_pad

    mesh, tp = shard.mesh, shard.tp
    batch = shard.dp if shard.batch_sharded else None
    w = cfg.conv_width

    def local(x_l, prm):
        zxbcdt = layers.dense(prm["in_proj"], x_l.astype(cfg.compute_dtype))
        z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
        spec = AxisSpec(array_axis=1, mesh_axis=tp)  # zero-BC == causal start
        xbc_h = exchange_pad(xbc, [(w - 1, 0)], [spec])
        prefix, xbc_body = xbc_h[:, :w - 1], xbc_h[:, w - 1:]
        xbc_c = _causal_conv(xbc_body, prm["conv_w"], prm["conv_b"], prefix)
        xs, b_, c_, dt, a = _prep_ssm_inputs(prm, cfg, xbc_c, dt_raw)

        # pass 1: local chunk states only (cheap — no quadratic part)
        _, final_local = ssd_chunked(xs, dt, a, b_, c_, cfg.ssm_chunk,
                                     states_only=True)
        decay_total = jnp.exp(jnp.sum(dt * a, axis=1))          # (B,G,R)
        finals = lax.all_gather(final_local, tp)                # (ep,B,G,R,N,P)
        decays = lax.all_gather(decay_total, tp)                # (ep,B,G,R)
        ep = finals.shape[0]
        rank = lax.axis_index(tp)

        def prefix_body(carry, i):
            s_acc = carry
            emit = s_acc
            s_acc = s_acc * decays[i][..., None, None] + finals[i]
            return s_acc, emit

        _, s_in_all = lax.scan(prefix_body,
                               jnp.zeros_like(final_local), jnp.arange(ep))
        s0 = s_in_all[rank]                                     # (B,G,R,N,P)

        # pass 2: exact local SSD with the relayed incoming state
        y, final = ssd_chunked(xs, dt, a, b_, c_, cfg.ssm_chunk, s0)
        out = _finish(prm, cfg, y, xs, z)
        return out

    pspec = jax.tree.map(lambda _: P(), params)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(batch, tp, None), pspec),
                       out_specs=P(batch, tp, None), check_vma=False)
    return fn(x, params), None


def mamba2_init_state(cfg: ModelConfig, batch: int) -> Mamba2State:
    g, r = _gr(cfg)
    return Mamba2State(
        conv=jnp.zeros((batch, cfg.conv_width - 1, cfg.conv_dim), jnp.float32),
        ssm=jnp.zeros((batch, g, r, cfg.ssm_state, cfg.ssm_head_dim),
                      jnp.float32))


def mamba2_step(params, cfg: ModelConfig, x_t: jnp.ndarray,
                state: Mamba2State):
    """Single-token decode.  x_t (B, d_model) -> (y (B, d_model), state)."""
    zxbcdt = layers.dense(params["in_proj"], x_t.astype(cfg.compute_dtype))
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    # rolling conv window
    window = jnp.concatenate(
        [state.conv, xbc[:, None, :].astype(jnp.float32)], axis=1)  # (B,W,C)
    y_conv = jnp.einsum("bwc,wc->bc", window, params["conv_w"]) + params["conv_b"]
    xbc_c = jax.nn.silu(y_conv).astype(cfg.compute_dtype)
    new_conv = window[:, 1:]

    xs, b_, c_, dt, a = _prep_ssm_inputs(params, cfg, xbc_c, dt_raw)
    # xs (B,G,R,P), b_/c_ (B,G,N), dt (B,G,R)
    da = jnp.exp(dt * a)                                        # (B,G,R)
    upd = jnp.einsum("bgn,bgr,bgrp->bgrnp", b_, dt, xs)
    ssm = state.ssm * da[..., None, None] + upd
    y = jnp.einsum("bgn,bgrnp->bgrp", c_, ssm)
    out = _finish(params, cfg, y, xs, z)
    return out, Mamba2State(conv=new_conv, ssm=ssm)


def mamba2_flops_per_token(cfg: ModelConfig, seq: int) -> int:
    """Approx fwd FLOPs/token of one block (projections dominate)."""
    d, di = cfg.d_model, cfg.d_inner
    proj = 2 * d * (2 * di + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads)
    out = 2 * di * d
    ssd = 2 * cfg.ssm_chunk * (cfg.ssm_heads * cfg.ssm_head_dim
                               + cfg.ssm_groups * cfg.ssm_state * cfg.ssm_head_dim)
    return proj + out + ssd
