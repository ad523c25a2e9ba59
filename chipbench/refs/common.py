"""Pieces of the plain reference that any decoder reuses.  They follow the
arithmetic the configurations state: bf16 weights and activations,
matmuls accumulated in f32 and rounded to bf16, norms, RoPE and softmax
in f32.  `lowp` is the control: every matmul input, activation and
weight, rounded through float8 e4m3 first."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32


def _lowp(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def _linear(p, x, lowp: bool, out_f32: bool = False):
    """x (T, K) bf16 -> (T, N): bf16, or f32 before the last rounding."""
    w = p["w"]
    if lowp:
        x, w = _lowp(x), _lowp(w)
    y = jnp.dot(x, w, preferred_element_type=F32)
    if out_f32:
        return y
    y = y.astype(BF16)
    return y + p["b"] if "b" in p else y


def _rmsnorm(p, x, eps: float):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
            ).astype(x.dtype)


def _rope(x, pos, theta: float):
    """x (T, H, D); rotate-half RoPE at integer positions pos (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v):
    """Causal GQA softmax attention in f32; q (T, Hq, D), k/v (T, Hkv, D)."""
    t, hq, hd = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k.astype(F32), g, axis=1)
    v = jnp.repeat(v.astype(F32), g, axis=1)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("thd,uhd->htu", q.astype(F32) * hd ** -0.5, k)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("htu,uhd->thd", p, v).astype(q.dtype)


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best, per position."""
    best = ref_logits.max(-1)
    return best - np.take_along_axis(ref_logits, chosen[:, None], 1)[:, 0]
