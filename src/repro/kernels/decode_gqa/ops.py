"""Production entry point for the decode attention engine.

Decode is inference-only (no gradient path), so the wrapper is just the
fused Pallas kernel; the jnp oracle lives in `ref.py` for tests and the
`bench_attention` speed gate — it is not on any runtime path.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_gqa.decode_gqa import decode_gqa_pallas


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     length: jnp.ndarray) -> jnp.ndarray:
    """q (B, Hq, D); k/v (B, S, Hkv, D) or lane-dense (B, S, Hkv*Dp);
    length (B,) int32 -> (B, Hq, D)."""
    return decode_gqa_pallas(q, k, v, length)
