"""Production entry point for the flash-attention engine: fused Pallas
forward, recompute backward via `jax.custom_vjp`.

The forward never materializes the (Sq, Skv) score matrix (it runs
`flash_attn_pallas`); the backward recomputes attention with a
q-chunked differentiable masked softmax (`_attn_recompute`) and takes its
VJP, so the residuals are just (q, k, v) — no saved probabilities.
``kv_len`` / ``q_offset`` are integer runtime operands and receive float0
cotangents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import NEG_INF
from repro.kernels.flash_attn.flash_attn import flash_attn_pallas


def _masked_attn(q, k, v, kv_len, q_offset, causal: bool):
    """Differentiable masked-softmax attention, f32 math (backward only —
    the forward path is the fused kernel)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d).astype(jnp.float32) * (d ** -0.5)
    sc = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32))
    kpos = jnp.arange(skv)
    mask = kpos[None, :] < kv_len[:, None]            # (B, Skv)
    mask = mask[:, None, None, None, :]
    if causal:
        qpos = q_offset + jnp.arange(sq)
        mask = mask & (qpos[:, None] >= kpos[None, :])[None, None, None]
    sc = jnp.where(mask, sc, NEG_INF)
    p = jnp.exp(sc - jax.lax.stop_gradient(sc.max(-1, keepdims=True)))
    p = jnp.where(mask, p, 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, d).astype(q.dtype)


def _q_chunk(sq: int, cap: int = 512) -> int:
    """Largest divisor of sq that is <= cap (bounds the bwd score buffer)."""
    for c in range(min(sq, cap), 0, -1):
        if sq % c == 0:
            return c
    return sq


def _attn_recompute(causal: bool, q, k, v, kv_len, q_offset):
    """Masked attention recompute, chunked over the query axis so the
    backward's transient score buffer is (B, Hq, cq, Skv), not Sq x Skv."""
    b, sq, hq, d = q.shape
    cq = _q_chunk(sq)
    if cq == sq:
        return _masked_attn(q, k, v, kv_len, q_offset, causal)
    n = sq // cq
    qs = jnp.moveaxis(q.reshape(b, n, cq, hq, d), 1, 0)   # (n, B, cq, Hq, D)
    starts = jnp.arange(n, dtype=jnp.int32) * cq
    outs = jax.lax.map(
        lambda t: _masked_attn(t[0], k, v, kv_len, q_offset + t[1], causal),
        (qs, starts))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, hq, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attention_vjp(causal: bool, q, k, v, kv_len, q_offset):
    return flash_attn_pallas(q, k, v, kv_len, q_offset, causal=causal)


def _flash_attention_fwd(causal, q, k, v, kv_len, q_offset):
    y = _flash_attention_vjp(causal, q, k, v, kv_len, q_offset)
    return y, (q, k, v, kv_len, q_offset)


def _flash_attention_bwd(causal, res, g):
    q, k, v, kv_len, q_offset = res
    _, vjp = jax.vjp(
        lambda a, b, c: _attn_recompute(causal, a, b, c, kv_len, q_offset),
        q, k, v)
    gq, gk, gv = vjp(g.astype(q.dtype))
    return (gq, gk, gv, np.zeros(kv_len.shape, jax.dtypes.float0),
            np.zeros(jnp.shape(q_offset), jax.dtypes.float0))


_flash_attention_vjp.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    kv_len: jnp.ndarray | None = None,
                    q_offset: jnp.ndarray | None = None, *,
                    causal: bool = True) -> jnp.ndarray:
    """Fused flash attention with STE-free exact recompute gradients.

    q (B, Sq, Hq, D); k/v (B, Skv, Hkv, D) -> (B, Sq, Hq, D).  ``kv_len``
    (B,) int32 valid KV prefix per batch row (default full); ``q_offset``
    scalar int32 absolute position of query row 0 (default 0) for the
    causal mask on rectangular calls (cache prefill)."""
    b = q.shape[0]
    skv = k.shape[1]
    if kv_len is None:
        kv_len = jnp.full((b,), skv, jnp.int32)
    if q_offset is None:
        q_offset = jnp.zeros((), jnp.int32)
    return _flash_attention_vjp(causal, q, k, v,
                                jnp.asarray(kv_len, jnp.int32),
                                jnp.asarray(q_offset, jnp.int32))
