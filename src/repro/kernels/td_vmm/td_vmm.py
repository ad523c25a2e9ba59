"""Pallas TPU kernel for the bit-serial noisy TD-VMM — the production TD
execution engine (every ``mode == "td"`` matmul runs here).

Hardware mapping (TPU adaptation of the paper's scheme — DESIGN.md §2):
one chain segment (length n_chain) of one output column is a "hardware
chain"; a grid step processes a (bm x n_chain) x (n_chain x bn) tile on the
MXU once per activation bit-plane, adds the per-chain Gaussian error from a
counter-based hash (no HBM RNG traffic), applies TDC rounding, and
accumulates 2^b-weighted partials into the fp32 output tile held in VMEM.

Fused wrapper semantics: the kernel takes *signed* LSQ codes and performs
offset encoding, contraction-tail masking (padding), bit-plane extraction
and the exact digital correction side-sums (popcount / static weight sum)
per tile — no offset tensor, no (Ba, ..., K) plane tensor and no
correction intermediates are ever materialized in HBM.

Runtime operands: ``sigma`` (chain noise std) and ``tdc_q`` (TDC LSB
coarsening) arrive as a (2,) float32 SMEM scalar operand, NOT as
compile-time constants — the noise and TDC branches are always traced
(sigma = 0 adds exactly 0; q <= 1 rounds to the unit LSB), so one compiled
program serves the whole noise-tolerance sweep with traced sigma under
vmap, with zero recompiles.  The per-bit plane loop is a
``lax.fori_loop``, keeping trace size constant up to bits_a = 8.

Grid: (M/bm, N/bn, n_seg) — segments innermost so the output tile is
revisited and accumulated in place.  A segment's stride in memory is
n_chain rounded up to the 128-lane width (576 -> 640): the wrapper lays
each segment out at that stride and the kernel masks the pad lanes exactly
like the contraction tail past ``k_true``, so every block is lane-aligned
while the noise, the tail scale sqrt(live / n_chain) and the result are
those of the unpadded chain.

Interpret policy (`kernels.common`): ``interpret=None`` compiles on a TPU
backend and runs in the Pallas interpreter elsewhere (CPU tests); both
modes use the same tiling: ``bm`` = M rounded up to 8 rows, at most 128,
and ``bn`` = N rounded up to 128 lanes, at most 512.

Public surface (API reference)
------------------------------
``td_vmm_pallas(x_int, w_int, params, seed, *, bits_a, bits_w, n_chain,
k_true=None, bm=None, bn=None, interpret=None) -> (M, N) float32``

  * ``x_int`` — (M, K) int32/float32 SIGNED activation codes in
    [-2^(bits_a-1), 2^(bits_a-1)-1] (LSQ levels, dimensionless).
  * ``w_int`` — (K, N) SIGNED weight codes, range per ``bits_w``.
  * ``params`` — (2,) float32 RUNTIME operand ``[sigma_chain, tdc_q]``:
    per-chain injected noise std in output-LSB units, and the TDC LSB
    coarsening factor (q <= 1 means unit-LSB rounding).  Traced, never a
    compile-time constant: may be a tracer under vmap/scan (the
    noise-tolerance sweep) with zero recompiles.
  * ``seed`` — uint32 scalar stream seed (`ref.derive_seed` folds a jax
    PRNG key into it; GOLDEN-salted counter hash in-kernel).
  * static (compile-keyed) arguments: ``bits_a``/``bits_w`` (bit widths),
    ``n_chain`` (hardware chain length; K must be a multiple — pad freely,
    positions >= ``k_true`` are masked in-kernel), tile sizes ``bm``/``bn``
    and ``interpret``.
  * returns the noisy TD product in output-LSB units, fp32 — bit-exact
    equal to the jnp simulator oracle at sigma=0, q<=1
    (`ref.td_vmm_signed_ref`, tests/test_td_vmm_engine.py).

Consumers: `tdsim.td_linear.td_matmul` routes EVERY ``mode == "td"``
matmul here (custom_vjp STE: Pallas forward, fake-quant backward);
`kernels.td_vmm.ops` holds the jit wrapper, `kernels.td_vmm.ref` the
oracles.  Hardware energy/latency of the modelled chain come from the
design engine (`core.design_grid` at a `core.techlib.TechLib`), not from
this kernel — the kernel only executes the (R, q, sigma_chain) policy the
engine solved.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (LANES, SCALAR_SPACE, resolve_interpret,
                                  round_up)

# GOLDEN salts the second Box-Muller hash stream and the seed derivation
# (ref.derive_seed) so one uint32 seed yields independent streams.
GOLDEN = 0x9E3779B9


def _hash32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _uniform(bits):
    # bits >> 8 < 2^24, so the int32 view converts to the same float the
    # oracle's uint32 -> float32 cast gives (Mosaic has no unsigned cast)
    top = jax.lax.bitcast_convert_type(bits >> 8, jnp.int32)
    return top.astype(jnp.float32) * (1.0 / (1 << 24)) + (0.5 / (1 << 24))


def _td_vmm_kernel(par_ref, seed_ref, x_ref, w_ref, o_ref, *, bits_a: int,
                   bits_w: int, n_chain: int, stride: int, n_seg: int,
                   m_total: int, n_total: int, k_true: int, bm: int,
                   bn: int):
    """One (bm, bn) output tile, one chain segment (k-step), signed codes.
    The segment arrives as ``stride`` lanes, the first ``n_chain`` real."""
    seg = pl.program_id(2)
    i = pl.program_id(0)
    j = pl.program_id(1)
    ox = 2 ** (bits_a - 1)
    ow = 2 ** (bits_w - 1)

    @pl.when(seg == 0)
    def _init():
        # the +K*ox*ow term of the offset-correction identity
        o_ref[...] = jnp.full(o_ref.shape, jnp.float32(k_true * ox * ow))

    sigma = par_ref[0, 0]                       # runtime scalar operands
    q = jnp.maximum(par_ref[0, 1], 1.0)
    seed = seed_ref[0, 0]

    # offset-encode in tile; stride pad lanes and contraction positions
    # past k_true encode 0 (zero offset weight) so they contribute nothing
    # to the dot or the side-sums
    def live(shape, dim):
        c = jax.lax.broadcasted_iota(jnp.int32, shape, dim)
        return (c < n_chain) & (seg * n_chain + c < k_true)

    x = jnp.where(live((1, stride), 1), x_ref[...] + ox, 0)  # (bm, stride)
    w = jnp.where(live((stride, 1), 0),
                  (w_ref[...] + ow).astype(jnp.float32), 0.0)  # (stride, bn)

    # tail segment holds k_true - (n_seg-1)*n_chain live cells: Eq. 5's
    # sigma ~ sqrt(N) scaling, identical to td_matmul_int / the ref oracle
    n_live = jnp.clip(k_true - seg * n_chain, 1, n_chain)
    n_live = jnp.full((1, 1), n_live, jnp.int32).astype(jnp.float32)
    sig_seg = sigma * jnp.sqrt(n_live / jnp.float32(n_chain))   # (1, 1)

    # noise indices use the TRUE (m, n): identical to the ref oracle; padded
    # rows/cols may collide but are sliced away by the wrapper.
    row = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + bm * i
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + bn * j
    cell = (jax.lax.bitcast_convert_type(row, jnp.uint32)
            * jnp.uint32(n_total)
            + jax.lax.bitcast_convert_type(col, jnp.uint32))

    def plane_step(b, acc):
        plane = ((x >> b) & 1).astype(jnp.float32)
        partial = jax.lax.dot(plane, w,
                              preferred_element_type=jnp.float32)
        base = jax.lax.bitcast_convert_type(
            jnp.full((1, 1), (b * n_seg + seg) * m_total, jnp.int32),
            jnp.uint32)
        idx = base * jnp.uint32(n_total) + cell
        h1 = _hash32(idx ^ seed)
        h2 = _hash32(idx ^ seed ^ jnp.uint32(GOLDEN))
        z = jnp.sqrt(-2.0 * jnp.log(_uniform(h1))) \
            * jnp.cos(2.0 * jnp.pi * _uniform(h2))
        partial = partial + sig_seg * z
        partial = q * jnp.round(partial / q)
        w2b = jax.lax.shift_left(jnp.int32(1), b).astype(jnp.float32)
        return acc + w2b * partial

    acc = jax.lax.fori_loop(0, bits_a, plane_step,
                            jnp.zeros(o_ref.shape, jnp.float32))

    # fused digital corrections: per-segment popcount / static weight sums
    # accumulate to the exact -ox*sum(w') - ow*sum(x') side terms
    corr = jnp.float32(ow) * x.astype(jnp.float32).sum(1, keepdims=True) \
        + jnp.float32(ox) * w.sum(0, keepdims=True)
    o_ref[...] += acc - corr


def td_vmm_pallas(x_int: jnp.ndarray, w_int: jnp.ndarray,
                  params: jnp.ndarray, seed: jnp.ndarray, *, bits_a: int,
                  bits_w: int, n_chain: int, k_true: int | None = None,
                  bm: int | None = None, bn: int | None = None,
                  interpret: bool | None = None) -> jnp.ndarray:
    """x_int (M, K) / w_int (K, N) SIGNED codes; K % n_chain == 0 (pad with
    anything — positions >= k_true are masked in-kernel).  ``params`` is the
    (2,) float32 runtime scalar operand [sigma_chain, tdc_q]; ``seed`` a
    uint32 scalar (see ref.derive_seed).  M, N are padded up to tile
    multiples internally.  ``interpret`` resolves through
    `kernels.common.resolve_interpret` here, OUTSIDE the jit."""
    m = x_int.shape[0]
    n = w_int.shape[1]
    interpret = resolve_interpret(interpret)
    if k_true is None:
        k_true = x_int.shape[1]
    if bm is None:
        bm = min(128, round_up(m, 8))
    if bn is None:
        bn = min(512, round_up(n, LANES))
    return _td_vmm_call(x_int, w_int, params, seed, bits_a=bits_a,
                        bits_w=bits_w, n_chain=n_chain, k_true=k_true,
                        bm=bm, bn=bn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits_a", "bits_w", "n_chain",
                                             "k_true", "bm", "bn",
                                             "interpret"))
def _td_vmm_call(x_int: jnp.ndarray, w_int: jnp.ndarray,
                 params: jnp.ndarray, seed: jnp.ndarray, *, bits_a: int,
                 bits_w: int, n_chain: int, k_true: int,
                 bm: int, bn: int, interpret: bool) -> jnp.ndarray:
    m, k = x_int.shape
    n = w_int.shape[1]
    assert k % n_chain == 0, "pad K to a multiple of n_chain first"
    n_seg = k // n_chain
    stride = round_up(n_chain, LANES)
    m_pad = round_up(m, bm)
    n_pad = round_up(n, bn)
    # lay each segment out at the lane-aligned stride (pad lanes masked)
    x_p = jnp.pad(x_int.reshape(m, n_seg, n_chain),
                  ((0, m_pad - m), (0, 0), (0, stride - n_chain)))
    w_p = jnp.pad(w_int.reshape(n_seg, n_chain, n),
                  ((0, 0), (0, stride - n_chain), (0, n_pad - n)))
    x_p = x_p.reshape(m_pad, n_seg * stride).astype(jnp.int32)
    w_p = w_p.reshape(n_seg * stride, n_pad).astype(jnp.int32)
    # 2-D scalar operands: under vmap (the noise-tolerance sweep batches
    # sigma and seed) each call still reads one whole (1, n) SMEM block
    params = jnp.asarray(params, jnp.float32).reshape(1, 2)
    seed_arr = jnp.asarray(seed, jnp.uint32).reshape(1, 1)

    kern = functools.partial(
        _td_vmm_kernel, bits_a=bits_a, bits_w=bits_w, n_chain=n_chain,
        stride=stride, n_seg=n_seg, m_total=m, n_total=n, k_true=k_true,
        bm=bm, bn=bn)
    out = pl.pallas_call(
        kern,
        grid=(m_pad // bm, n_pad // bn, n_seg),
        in_specs=[
            pl.BlockSpec(memory_space=SCALAR_SPACE),
            pl.BlockSpec(memory_space=SCALAR_SPACE),
            pl.BlockSpec((bm, stride), lambda i, j, s: (i, s)),
            pl.BlockSpec((stride, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(params, seed_arr, x_p, w_p)
    return out[:m, :n]
