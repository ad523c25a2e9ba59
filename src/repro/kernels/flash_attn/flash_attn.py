"""Pallas TPU kernel: fused GQA flash-attention forward — the production
attention engine for every multi-query-row attention call (train, prefill,
cross-attention).

This is the fused path that removes the S x S score traffic identified as
the dominant (and HLO-irreducible) memory-roofline term of every train/
prefill cell (EXPERIMENTS §Perf C4): scores and probabilities live only in
VMEM tiles; HBM sees q, k, v and o exactly once.

Grid: (B, Hq, S_q/bq, S_kv/bk) — the KV axis innermost so the online-
softmax running state (m, l, acc) persists in VMEM scratch across KV
blocks of one query tile.  Heads are lane blocks: q/o are viewed as
(B, S, Hq*D) and k/v as (B, S, Hkv*D) (free reshapes), and query head h
reads lane block h // (Hq/Hkv) of k/v, so no head axis sits in the last
two block dims.  A head dim that is not a multiple of 128 lanes is
zero-padded up to one (the scores and outputs are unchanged).  Both the
causal upper triangle and the KV tail past ``kv_len`` are skipped at
runtime via ``pl.when`` on the SMEM scalars (no MXU work issued for dead
blocks).

Runtime operands: ``kv_len`` — a (B,) int32 valid-prefix length per batch
row — and ``q_offset`` — the absolute position of query row 0 — ride in as
SMEM scalar operands, NOT compile-time constants, so a decode-cache prefill
sweep over fill levels reuses ONE compiled program (the td_vmm bar: zero
recompiles across runtime-value changes).

Rectangular attention: q (B, Sq, Hq, D) against k/v (B, Skv, Hkv, D) with
Sq != Skv is supported; under ``causal=True`` query row i attends to key
positions j <= q_offset + i (cache prefill: q_offset = idx,
kv_len = idx + Sq).  Sq/Skv are padded to tile multiples internally.

Interpret policy (`kernels.common`): ``interpret=None`` compiles on a TPU
backend and runs in the Pallas interpreter elsewhere (CPU tests); both
modes use the same tiles, 256 x 256 or the sequence rounded up to 16 rows
when it is shorter.

Public surface
--------------
``flash_attn_pallas(q, k, v, kv_len=None, q_offset=None, *, causal=True,
bq=None, bk=None, interpret=None) -> (B, Sq, Hq, D)``

Consumers: `kernels.flash_attn.ops.flash_attention` wraps this in the
`custom_vjp` production entry (recompute backward); `models.attention`
routes every non-decode attention call there.  The oracle is
`kernels.flash_attn.ref.flash_attn_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (LANES, NEG_INF, SCALAR_SPACE,
                                  resolve_interpret, round_up)


def _kernel(lens_ref, off_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref,
            *, bq: int, bk: int, n_kb: int, causal: bool, scale: float):
    bi = pl.program_id(0)
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    kv_len = lens_ref[0, bi]                    # runtime scalar operands
    q_off = off_ref[0, 0]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # runtime dead-block skip: KV blocks past the valid prefix, and (causal)
    # blocks fully above the diagonal — q_block_end < k_block_start
    live = kb * bk < kv_len
    if causal:
        live = live & (q_off + qb * bq + bq - 1 >= kb * bk)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale         # (bq, D)
        k = k_ref[0].astype(jnp.float32)                 # (bk, D)
        v = v_ref[0].astype(jnp.float32)                 # (bk, D)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        mask = kpos < kv_len
        if causal:
            qpos = q_off + qb * bq + jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 0)
            mask = mask & (qpos >= kpos)
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]                              # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # NEG_INF - NEG_INF == 0 in f32, so a fully-masked row would get
        # exp(0) == 1 garbage: zero masked entries explicitly.
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        l_ref[...] = l_prev * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # finalize reads the REFS (not compute-locals): the last KV block may
    # have been skipped as dead, so its locals never exist.
    @pl.when(kb == n_kb - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)   # (bq, D)
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attn_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      kv_len: jnp.ndarray | None = None,
                      q_offset: jnp.ndarray | None = None, *,
                      causal: bool = True, bq: int | None = None,
                      bk: int | None = None,
                      interpret: bool | None = None) -> jnp.ndarray:
    """q (B, Sq, Hq, D); k/v (B, Skv, Hkv, D) -> (B, Sq, Hq, D).

    ``kv_len`` (B,) int32 valid-prefix lengths (default: full Skv) and
    ``q_offset`` scalar int32 absolute position of query row 0 (default 0)
    are RUNTIME operands — sweeping them reuses one compiled program.
    ``interpret`` resolves through `kernels.common.resolve_interpret` here,
    OUTSIDE the jit."""
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    interpret = resolve_interpret(interpret)
    bq = min(bq or 256, round_up(sq, 16))
    bk = min(bk or 256, round_up(skv, 16))
    if kv_len is None:
        kv_len = jnp.full((b,), skv, jnp.int32)
    if q_offset is None:
        q_offset = jnp.zeros((), jnp.int32)
    return _flash_attn_call(q, k, v, kv_len, q_offset, causal=causal,
                            bq=bq, bk=bk, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def _flash_attn_call(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     kv_len: jnp.ndarray, q_offset: jnp.ndarray, *,
                     causal: bool, bq: int, bk: int,
                     interpret: bool) -> jnp.ndarray:
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    dp = round_up(d, LANES)
    sq_p, skv_p = round_up(sq, bq), round_up(skv, bk)
    n_qb, n_kb = sq_p // bq, skv_p // bk
    q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, dp - d)))
    k = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0), (0, dp - d)))
    v = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0), (0, dp - d)))
    # clamp to the true KV length: padded tail positions are never valid
    # 2-D scalar operands stay whole SMEM blocks under vmap
    lens = jnp.minimum(jnp.asarray(kv_len, jnp.int32).reshape(1, b), skv)
    off = jnp.asarray(q_offset, jnp.int32).reshape(1, 1)

    kern = functools.partial(_kernel, bq=bq, bk=bk, n_kb=n_kb,
                             causal=causal, scale=d ** -0.5)
    kv_spec = pl.BlockSpec((1, bk, dp),
                           lambda bi, hi, qi, ki: (bi, ki, hi // g))
    out = pl.pallas_call(
        kern,
        grid=(b, hq, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec(memory_space=SCALAR_SPACE),
            pl.BlockSpec(memory_space=SCALAR_SPACE),
            pl.BlockSpec((1, bq, dp), lambda bi, hi, qi, ki: (bi, qi, hi)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, bq, dp),
                               lambda bi, hi, qi, ki: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, sq_p, hq * dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dp), jnp.float32),
        ],
        interpret=interpret,
    )(lens, off, q.reshape(b, sq_p, hq * dp), k.reshape(b, skv_p, hkv * dp),
      v.reshape(b, skv_p, hkv * dp))
    return out.reshape(b, sq_p, hq, dp)[:, :sq, :, :d]
