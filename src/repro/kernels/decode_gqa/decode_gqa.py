"""Pallas kernel: fused GQA decode attention (flash-decode style) — the
production attention engine for single-query-row decode steps.

Decode with a long KV cache is the memory-roofline hot spot of the decode_*
shapes: each step streams the whole KV cache from HBM once.  The kernel
tiles the cache along S; each grid step loads one KV head's (bs, D) block
into VMEM, scores the g = Hq/Hkv query heads of its group against it in
one (g, D) x (D, bs) matmul, updates the online-softmax running (m, l, acc)
held in VMEM scratch, and writes the normalized output on the last block.

Runtime operand: ``length`` — the (B,) int32 cache fill level — rides in as
an SMEM scalar operand, NOT a compile-time constant, and KV blocks past it
are skipped entirely at runtime via ``pl.when`` (the td_vmm bar: a decode
loop over growing fill levels reuses ONE compiled program and never touches
dead cache blocks).

Grid: (B, Hkv, S/bs).  The kernel reads the cache lane-dense, as
(B, S, Hkv*Dp) with Dp the head dim rounded up to whole 128-lane blocks,
and KV head h is lane block h, so no head axis sits in the last two block
dims; q and the output are viewed as (B, Hkv, g, Dp).  Scratch: m/l
(g, 1), acc (g, Dp) — 2-D tiles, persistent across the S axis for one
(batch row, KV head) (TPU grid is sequential over the last dim).

Two cache layouts come in.  The serving engine's per-row cache is stored
lane-dense already (`models.attention.init_cache(per_row_idx=True)`) and
is read as it is: no pad, no reshape, and a block that divides S.  A
(B, S, Hkv, D) cache is zero-padded to Dp and reshaped to the lane-dense
view on every call; on a TPU that reshape is NOT free: (Hkv, D) is the
minor tile of the 4-D layout, so it compiles to a re-tiling copy of the
whole cache.

Interpret policy (`kernels.common`): ``interpret=None`` compiles on a TPU
backend and runs in the Pallas interpreter elsewhere (CPU tests); both
modes use the same block, 512 or the cache rounded up to 16 rows when it
is shorter (a lane-dense cache: the largest such block that divides S).

Public surface
--------------
``decode_gqa_pallas(q, k, v, length, *, bs=None, interpret=None)
-> (B, Hq, D)``, k/v (B, S, Hkv, D) or lane-dense (B, S, Hkv*Dp)

Consumers: `kernels.decode_gqa.ops.decode_attention` (the production
wrapper `models.attention` routes s == 1 self-attention decode steps to).
The oracle is `kernels.decode_gqa.ref.decode_gqa_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (LANES, NEG_INF, SCALAR_SPACE,
                                  resolve_interpret, round_up)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bs: int, n_blocks: int, scale: float):
    i = pl.program_id(0)
    blk = pl.program_id(2)
    length = len_ref[0, i]                        # runtime scalar operand

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # runtime dead-block skip: blocks entirely past the cache fill level
    @pl.when(blk * bs < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale   # (g, D)
        k = k_ref[0].astype(jnp.float32)              # (bs, D)
        v = v_ref[0].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        pos = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) + blk * bs
        mask = pos < length
        sc = jnp.where(mask, sc, NEG_INF)             # (g, bs)

        m_prev = m_ref[...]                           # (g, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # NEG_INF - NEG_INF == 0 in f32: zero masked entries explicitly
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        l_ref[...] = l_prev * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # finalize reads the REFS (not compute-locals): the last cache block may
    # have been skipped as dead, so its locals never exist.
    @pl.when(blk == n_blocks - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)   # (g, D)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def decode_gqa_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      length: jnp.ndarray, *, bs: int | None = None,
                      interpret: bool | None = None) -> jnp.ndarray:
    """q (B, Hq, D); k/v (B, S, Hkv, D) or lane-dense (B, S, Hkv*Dp);
    length (B,) int32 RUNTIME operand.

    ``interpret`` resolves through `kernels.common.resolve_interpret` here,
    OUTSIDE the jit."""
    s = k.shape[1]
    interpret = resolve_interpret(interpret)
    bs = min(bs or 512, round_up(s, 16))
    if k.ndim == 3:
        bs = _dividing_block(s, bs)
    return _decode_gqa_call(q, k, v, length, bs=bs, interpret=interpret)


def _dividing_block(s: int, bs: int) -> int:
    """The largest multiple of 16 rows up to ``bs`` that divides ``s`` (the
    whole cache when it is that short), so a lane-dense cache is read
    without a padded copy; ``bs`` itself where none does."""
    if s <= bs:
        return s
    return next((c for c in range(bs - bs % 16, 0, -16) if s % c == 0), bs)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def _decode_gqa_call(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     length: jnp.ndarray, *, bs: int,
                     interpret: bool) -> jnp.ndarray:
    b, hq, d = q.shape
    s = k.shape[1]
    dp = round_up(d, LANES)
    if k.ndim == 4:
        # (B, S, Hkv, D): pad the head dim to whole lane blocks and view
        # lane-dense -- a re-tiling copy of the whole cache on a TPU
        hkv = k.shape[2]
        k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
                .reshape(b, s, hkv * dp) for x in (k, v))
    else:
        hkv = k.shape[2] // dp
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    s_pad = round_up(s, bs)
    n_blocks = s_pad // bs
    if s_pad != s:
        k, v = (jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0))) for x in (k, v))
    q = jnp.pad(q, ((0, 0), (0, 0), (0, dp - d))).reshape(b, hkv, g, dp)
    # clamp to the true cache length: padded tail positions are never valid
    # a 2-D scalar operand stays one whole SMEM block under vmap
    lens = jnp.minimum(jnp.asarray(length, jnp.int32).reshape(1, b), s)

    kern = functools.partial(_kernel, bs=bs, n_blocks=n_blocks,
                             scale=d ** -0.5)
    head_spec = pl.BlockSpec((1, 1, g, dp), lambda i, h, j: (i, h, 0, 0))
    kv_spec = pl.BlockSpec((1, bs, dp), lambda i, h, j: (i, j, h))
    out = pl.pallas_call(
        kern,
        grid=(b, hkv, n_blocks),
        in_specs=[pl.BlockSpec(memory_space=SCALAR_SPACE), head_spec,
                  kv_spec, kv_spec],
        out_specs=head_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dp), jnp.float32),
        ],
        interpret=interpret,
    )(lens, q, k, v)
    return out.reshape(b, hq, dp)[:, :, :d]
