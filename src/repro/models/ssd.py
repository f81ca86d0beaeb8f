"""Intra-chunk SSD (Mamba2 / mLSTM) as a Pallas TPU kernel.

The chunked linear-recurrence core (`models/mamba2.ssd_core`) splits into
a cheap inter-chunk state relay and a *quadratic intra-chunk* part that
materializes (L, L) decay/score matrices per (batch, chunk, group).  In
pure XLA those temporaries round-trip HBM; this kernel computes one
(batch·chunk, group) tile entirely in VMEM:

    cum   = cumsum(log_decay)                       (L, R)
    S     = (C @ B^T)                               (L, L)
    for r: y[:, r] = (S * exp(cum_r_i - cum_r_j) * mask * dt_r) @ x[:, r]
    plus the inter-chunk contribution  y += (C @ state_r) * exp(cum_r)

Grid: (B·nc, G); blocks sized (L, R, P) — L=chunk (128 default), R heads
per group, P head_dim: VMEM ≈ L·R·P·4B ≈ 2 MB per operand at the zamba2
shapes.  MXU work is the (L,L)@(L,P) matmul per head.

Validated in interpret mode against the pure-jnp oracle
(`ssd_intra_reference` == the ssd_core intra-chunk math) over
shape/dtype sweeps in tests/test_kernels_ssd.py.  The model code
(``mamba2.ssd_core``) runs the reference, tagged with
``jax.named_scope("__kernel__ssd")`` so the dry-run roofline prices it as
this kernel (launch/hlo_cost.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_intra_kernel(x_ref, ld_ref, dt_ref, b_ref, c_ref, s0_ref, y_ref):
    """One (batch·chunk, group) tile.

    x  (1, L, 1, R, P)   values
    ld (1, L, 1, R)      log decay
    dt (1, L, 1, R)      input scale
    b  (1, L, 1, N)      input projection
    c  (1, L, 1, N)      output projection
    s0 (1, 1, R, N, P)   incoming chunk state
    y  (1, L, 1, R, P)   output
    """
    x = x_ref[0, :, 0].astype(jnp.float32)        # (L, R, P)
    ld = ld_ref[0, :, 0].astype(jnp.float32)      # (L, R)
    dt = dt_ref[0, :, 0].astype(jnp.float32)      # (L, R)
    b = b_ref[0, :, 0].astype(jnp.float32)        # (L, N)
    c = c_ref[0, :, 0].astype(jnp.float32)        # (L, N)
    s0 = s0_ref[0, 0].astype(jnp.float32)         # (R, N, P)
    l = x.shape[0]
    r = x.shape[1]

    cum = jnp.cumsum(ld, axis=0)                  # (L, R)
    scores = c @ b.T                              # (L, L)  MXU
    mask = jnp.tril(jnp.ones((l, l), jnp.bool_))

    def head(i, y):
        cr = cum[:, i]
        diff = cr[:, None] - cr[None, :]          # (L, L)
        w = jnp.where(mask, jnp.exp(diff), 0.0) * scores * dt[None, :, i]
        yi = w @ x[:, i]                          # (L, P)  MXU
        yi = yi + jnp.exp(cr)[:, None] * (c @ s0[i])
        return y.at[:, i].set(yi)

    y = jax.lax.fori_loop(0, r, head, jnp.zeros_like(x))
    y_ref[0, :, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_pallas(x, log_decay, in_scale, b_, c_, s_in, *,
                     interpret: bool = False):
    """x (B,nc,L,G,R,P), gates (B,nc,L,G,R), b_/c_ (B,nc,L,G,N),
    s_in (B,nc,G,R,N,P) -> y (B,nc,L,G,R,P)."""
    bsz, nc, l, g, r, p = x.shape
    n = b_.shape[-1]
    bc = bsz * nc
    rs = lambda t, *tail: t.reshape(bc, *tail)
    x2 = rs(x, l, g, r, p)
    ld2 = rs(log_decay, l, g, r)
    dt2 = rs(in_scale, l, g, r)
    b2 = rs(b_, l, g, n)
    c2 = rs(c_, l, g, n)
    s2 = rs(s_in, 1, g, r, n, p)[:, 0]            # (bc, g, r, n, p)

    grid = (bc, g)
    out = pl.pallas_call(
        _ssd_intra_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, l, 1, r, p), lambda i, j: (i, 0, j, 0, 0)),
            pl.BlockSpec((1, l, 1, r), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, l, 1, r), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, l, 1, n), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, l, 1, n), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, 1, r, n, p), lambda i, j: (i, j, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, l, 1, r, p), lambda i, j: (i, 0, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bc, l, g, r, p), jnp.float32),
        interpret=interpret,
    )(x2, ld2, dt2, b2, c2, s2)
    return out.reshape(bsz, nc, l, g, r, p)


def ssd_intra_reference(x, log_decay, in_scale, b_, c_, s_in):
    """Pure-jnp oracle — the exact intra-chunk math of ssd_core."""
    cum = jnp.cumsum(log_decay, axis=2)
    l = x.shape[2]
    diff = cum[:, :, :, None, :, :] - cum[:, :, None, :, :, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    lmat = jnp.where(mask[None, None, :, :, None, None], jnp.exp(diff), 0.0)
    scores = jnp.einsum("bclgn,bcmgn->bclmg", c_, b_)
    attw = scores[..., None] * lmat * in_scale[:, :, None, :, :, :]
    y = jnp.einsum("bclmgr,bcmgrp->bclgrp", attw, x)
    y = y + jnp.einsum("bclgn,bcgrnp->bclgrp", c_, s_in) \
        * jnp.exp(cum)[..., None]
    return y
