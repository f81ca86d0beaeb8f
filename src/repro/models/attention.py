"""Attention: chunked-flash (pure XLA) + GQA module with KV cache.

``chunked_mha`` is the memory-safe O(S) attention used for training and
prefill on every backend (online softmax over kv chunks, the algorithm of
a flash-attention kernel).  Layout is BSHD: q (B, Sq, H, D), k/v
(B, Skv, KH, D), H = KH * rep (GQA).

Masking supports causal, causal-with-offset (decode), and prefix-LM
(PaliGemma: bidirectional prefix + causal suffix).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


class MaskSpec(NamedTuple):
    causal: bool = True
    q_offset: int = 0          # absolute position of q[0]
    prefix_len: int = 0        # positions < prefix_len attend bidirectionally


def _mask(qpos, kpos, spec: MaskSpec, kv_valid_len=None):
    """(Sq, Sk) boolean mask (True = attend)."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if spec.causal:
        causal = kpos[None, :] <= (qpos[:, None] + spec.q_offset)
        if spec.prefix_len:
            causal = causal | (kpos[None, :] < spec.prefix_len)
        m = m & causal
    if kv_valid_len is not None:
        m = m & (kpos[None, :] < kv_valid_len)
    return m


def full_mha(q, k, v, spec: MaskSpec = MaskSpec(), kv_valid_len=None,
             scale=None):
    """O(S^2)-memory attention (small-sequence / oracle / decode path).

    The ``__kernel__`` scope marks the region the roofline prices as one
    fused attention kernel: its HBM-traffic model charges only region
    inputs/outputs — logits/probabilities stay in VMEM (see
    launch/hlo_cost.py).
    """
    with jax.named_scope("__kernel__attention"):
        b, sq, h, d = q.shape
        _, sk, kh, _ = k.shape
        rep = h // kh
        scale = scale if scale is not None else 1.0 / (d ** 0.5)
        qf = q.reshape(b, sq, kh, rep, d).astype(jnp.float32)
        logits = jnp.einsum("bqhrd,bkhd->bhrqk", qf,
                            k.astype(jnp.float32)) * scale
        per_batch = (kv_valid_len is not None
                     and getattr(kv_valid_len, "ndim", 0) >= 1)
        mask = _mask(jnp.arange(sq), jnp.arange(sk), spec,
                     None if per_batch else kv_valid_len)
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
        if per_batch:  # continuous batching: per-slot valid length
            kmask = (jnp.arange(sk)[None, :]
                     < kv_valid_len.reshape(b, 1))       # (B, Sk)
            logits = jnp.where(kmask[:, None, None, None, :], logits,
                               _NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhrqk,bkhd->bqhrd", w, v.astype(jnp.float32))
        return out.reshape(b, sq, h, d).astype(q.dtype)


def chunked_mha(q, k, v, spec: MaskSpec = MaskSpec(), *, q_chunk: int = 1024,
                kv_chunk: int = 1024, kv_valid_len=None, scale=None):
    """Online-softmax attention: O(chunk^2) transient memory.

    Outer ``lax.map`` over q chunks, inner ``lax.scan`` over kv chunks —
    the XLA analogue of the flash-attention tiling (and of the paper's
    stream-along-one-axis 3DBLOCK template).
    """
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    rep = h // kh
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    nq, nk = -(-sq // q_chunk), -(-sk // kv_chunk)
    # pad to chunk multiples
    sq_p, sk_p = nq * q_chunk, nk * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    valid = jnp.minimum(kv_valid_len if kv_valid_len is not None else sk, sk)

    kb = kp.reshape(b, nk, kv_chunk, kh, d)
    vb = vp.reshape(b, nk, kv_chunk, kh, d)

    @jax.named_scope("__kernel__attention")
    def one_q_chunk(qi):
        qs = lax.dynamic_slice_in_dim(qp, qi * q_chunk, q_chunk, axis=1)
        qs = qs.reshape(b, q_chunk, kh, rep, d).astype(jnp.float32)
        qpos = qi * q_chunk + jnp.arange(q_chunk)

        def body(carry, inputs):
            m_prev, l_prev, acc = carry
            kj, (kc, vc) = inputs
            kc = kc.astype(jnp.float32)
            logits = jnp.einsum("bqhrd,bkhd->bqhrk", qs, kc) * scale
            kpos = kj * kv_chunk + jnp.arange(kv_chunk)
            msk = _mask(qpos, kpos, spec, valid)           # (q_chunk, kv_chunk)
            logits = jnp.where(msk[None, :, None, None, :], logits, _NEG_INF)
            m_cur = jnp.maximum(m_prev, logits.max(axis=-1))
            p = jnp.exp(logits - m_cur[..., None])
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = l_prev * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqhrk,bkhd->bqhrd", p, vc.astype(jnp.float32))
            return (m_cur, l_cur, acc), None

        init = (
            jnp.full((b, q_chunk, kh, rep), _NEG_INF, jnp.float32),
            jnp.zeros((b, q_chunk, kh, rep), jnp.float32),
            jnp.zeros((b, q_chunk, kh, rep, d), jnp.float32),
        )
        (m, l, acc), _ = lax.scan(
            body, init,
            (jnp.arange(nk), (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0))))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, q_chunk, h, d).astype(q.dtype)

    # checkpoint: backward recomputes each q-chunk's online-softmax pass
    # instead of saving per-chunk masks/probabilities as residuals (the
    # flash-attention backward; cuts train-time attention residency from
    # O(S^2 / nq) to O(chunk^2) transients)
    out = lax.map(jax.checkpoint(one_q_chunk), jnp.arange(nq))
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq_p, h, d)   # (nq,B,qc,H,D)
    return out[:, :sq]


def decode_mha(q, k_cache, v_cache, cache_len, scale=None):
    """Single-step decode: q (B, 1, H, D) against a (B, S, KH, D) cache.

    Positions >= cache_len are masked.  Small enough to run unchunked; the
    contraction is sharded by pjit (seq-sharded cache => psum combine, the
    flash-decode pattern, chosen automatically by SPMD).
    """
    return full_mha(q, k_cache, v_cache,
                    MaskSpec(causal=False), kv_valid_len=cache_len,
                    scale=scale)
