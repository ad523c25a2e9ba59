"""TD-simulated attention: QK^T and PV routed through the td_vmm engine
under per-head policies — the paper's time-domain VMM applied to the one
workload class it never evaluates.

Pipeline (mode "td" / "quant"):
  1. Dynamically quantize q per (batch, q-head) at bits_a and k per
     (batch, kv-head) at bits_w (symmetric maxabs — attention operands are
     activations on both sides, so there are no learned LSQ steps).
  2. QK^T: one td_vmm engine call per (batch, q-head) lane via `jax.vmap`
     over `td_vmm_seeded` — each lane carries ITS head's (sigma_chain,
     tdc_q) as the runtime SMEM operand and a lane-salted noise seed, so a
     per-head heterogeneous policy sweep reuses ONE compiled kernel
     (exactly the td_linear contract).
  3. Dequantize, scale by D^-1/2, mask (valid-KV prefix + causal) and take
     the softmax in f32 — the softmax is small digital post-processing in
     the paper's architecture, not a VMM, so it stays exact.
  4. Quantize the probabilities per (batch, q-head) at bits_a and v per
     (batch, kv-head) at bits_w; PV runs the same per-lane engine with a
     GOLDEN-salted seed stream.
  5. Dequantize; straight-through gradients via `jax.custom_vjp` against
     the clean masked-softmax attention (the td_linear STE pattern: noisy
     Pallas forward, clean recompute backward; sigma/tdc_q operands get
     zero cotangents, integer operands float0).

With sigma_chain = 0 and tdc_q = 1 on every head (or mode "quant") the
engine is bit-exact integer arithmetic, so the result equals the pure
fake-quant attention — the accuracy floor of the comparison; per-head
(R, q, sigma) policies from the scenario grid then perturb it without any
recompile across sigma values.

All heads must share (mode, bits_a, bits_w, n_chain) — those are compile
constants of the engine; redundancy/sigma_chain/tdc_q are free per head.
The contraction lengths differ per call site (QK contracts over D, PV over
S_kv): the engine segments any K into n_chain-long chains with in-kernel
tail masking, matching Eq. 5's sqrt(N) noise scaling on both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import NEG_INF
from repro.kernels.td_vmm import ops as td_ops
from repro.kernels.td_vmm import ref as td_ref
from repro.tdsim.policy import TDPolicy

_PV_SALT = td_ref.GOLDEN


def _quant_dyn(x: jnp.ndarray, bits: int, axes) -> tuple[jnp.ndarray,
                                                         jnp.ndarray]:
    """Symmetric maxabs quantization to signed codes over ``axes``."""
    levels = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / levels
    s = jnp.maximum(s, 1e-8)
    xi = jnp.clip(jnp.round(x / s), -levels - 1, levels).astype(jnp.int32)
    return xi, s


def _lane_vmm(pol_static: TDPolicy, x_int, w_int, sigma_l, tdcq_l, seeds):
    """vmap one td_vmm engine call per lane; each lane's (sigma, tdc_q)
    rides in as the runtime operand of the SAME compiled kernel."""
    def lane(x_i, w_i, sg, qq, sd):
        pol_l = pol_static.replace(sigma_chain=sg, tdc_q=qq)
        return td_ops.td_vmm_seeded(x_i, w_i, pol_l, sd)
    return jax.vmap(lane)(x_int, w_int, sigma_l, tdcq_l, seeds)


def _clean_attention(q, k, v, kv_len, q_offset, causal: bool):
    """Clean f32 masked-softmax attention — the STE backward function."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d).astype(jnp.float32) * (d ** -0.5)
    sc = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32))
    mask = _mask(b, sq, skv, kv_len, q_offset, causal)[:, None, None]
    sc = jnp.where(mask, sc, NEG_INF)
    p = jnp.exp(sc - jax.lax.stop_gradient(sc.max(-1, keepdims=True)))
    p = jnp.where(mask, p, 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, d).astype(q.dtype)


def _mask(b, sq, skv, kv_len, q_offset, causal: bool) -> jnp.ndarray:
    """(B, Sq, Skv) bool: valid-KV prefix, optionally causal with query row
    i at absolute position q_offset + i."""
    kpos = jnp.arange(skv)
    mask = jnp.broadcast_to(kpos[None, None, :] < kv_len[:, None, None],
                            (b, sq, skv))
    if causal:
        qpos = q_offset + jnp.arange(sq)
        mask = mask & (qpos[:, None] >= kpos[None, :])[None]
    return mask


def _td_attention_impl(pol_static: TDPolicy, causal: bool, q, k, v,
                       sigma_vec, tdcq_vec, kv_len, q_offset, seed):
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qh = q.astype(jnp.float32).transpose(0, 2, 1, 3)    # (B, Hq, Sq, D)
    kh = k.astype(jnp.float32).transpose(0, 2, 1, 3)    # (B, Hkv, Skv, D)
    vh = v.astype(jnp.float32).transpose(0, 2, 1, 3)    # (B, Hkv, Skv, D)

    lanes = b * hq
    lane_idx = jnp.arange(lanes, dtype=jnp.uint32)
    sigma_l = jnp.tile(sigma_vec, b)                    # lane = bi*Hq + h
    tdcq_l = jnp.tile(tdcq_vec, b)

    # -- QK^T on the engine: x = q codes (Sq, D), w = k^T codes (D, Skv) --
    q_int, s_q = _quant_dyn(qh, pol_static.bits_a, (2, 3))
    k_int, s_k = _quant_dyn(kh, pol_static.bits_w, (2, 3))
    kt_rep = jnp.repeat(k_int.transpose(0, 1, 3, 2), g, axis=1)
    sc_int = _lane_vmm(pol_static, q_int.reshape(lanes, sq, d),
                       kt_rep.reshape(lanes, d, skv), sigma_l, tdcq_l,
                       td_ref.hash32(seed ^ lane_idx))
    s_k_rep = jnp.repeat(s_k, g, axis=1)                # (B, Hq, 1, 1)
    scores = sc_int.reshape(b, hq, sq, skv) * s_q * s_k_rep * (d ** -0.5)

    # -- digital f32 masked softmax (small post-processing, not a VMM) --
    mask = _mask(b, sq, skv, kv_len, q_offset, causal)[:, None]
    scores = jnp.where(mask, scores, NEG_INF)
    p = jnp.exp(scores - scores.max(-1, keepdims=True))
    p = jnp.where(mask, p, 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)

    # -- PV on the engine: x = prob codes (Sq, Skv), w = v codes (Skv, D) --
    p_int, s_p = _quant_dyn(p, pol_static.bits_a, (2, 3))
    v_int, s_v = _quant_dyn(vh, pol_static.bits_w, (2, 3))
    v_rep = jnp.repeat(v_int, g, axis=1)                # (B, Hq, Skv, D)
    o_int = _lane_vmm(pol_static, p_int.reshape(lanes, sq, skv),
                      v_rep.reshape(lanes, skv, d), sigma_l, tdcq_l,
                      td_ref.hash32(seed ^ lane_idx ^ _PV_SALT))
    s_v_rep = jnp.repeat(s_v, g, axis=1)
    o = o_int.reshape(b, hq, sq, d) * s_p * s_v_rep
    return o.transpose(0, 2, 1, 3).astype(q.dtype)      # (B, Sq, Hq, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _td_attention_ste(pol_static: TDPolicy, causal: bool, q, k, v,
                      sigma_vec, tdcq_vec, kv_len, q_offset, seed):
    return _td_attention_impl(pol_static, causal, q, k, v, sigma_vec,
                              tdcq_vec, kv_len, q_offset, seed)


def _td_attention_ste_fwd(pol_static, causal, q, k, v, sigma_vec, tdcq_vec,
                          kv_len, q_offset, seed):
    y = _td_attention_ste(pol_static, causal, q, k, v, sigma_vec, tdcq_vec,
                          kv_len, q_offset, seed)
    return y, (q, k, v, kv_len, q_offset)


def _td_attention_ste_bwd(pol_static, causal, res, g):
    q, k, v, kv_len, q_offset = res
    _, vjp = jax.vjp(
        lambda a, b, c: _clean_attention(a, b, c, kv_len, q_offset, causal),
        q, k, v)
    gq, gk, gv = vjp(g.astype(q.dtype))
    return (gq, gk, gv,
            jnp.zeros(jnp.shape(g)[2:3], jnp.float32),   # sigma_vec (Hq,)
            jnp.zeros(jnp.shape(g)[2:3], jnp.float32),   # tdcq_vec (Hq,)
            np.zeros(kv_len.shape, jax.dtypes.float0),
            np.zeros(jnp.shape(q_offset), jax.dtypes.float0),
            np.zeros((), jax.dtypes.float0))              # scalar seed


_td_attention_ste.defvjp(_td_attention_ste_fwd, _td_attention_ste_bwd)


def td_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 pols, key: jax.Array | None = None, *,
                 causal: bool = True,
                 kv_len: jnp.ndarray | None = None,
                 q_offset: jnp.ndarray | None = None) -> jnp.ndarray:
    """TD-simulated attention under per-head policies.

    q (B, Sq, Hq, D); k/v (B, Skv, Hkv, D) -> (B, Sq, Hq, D).  ``pols`` is
    one TDPolicy (broadcast to every head) or a length-Hq sequence; all
    entries must share (mode, bits_a, bits_w, n_chain) — redundancy /
    sigma_chain / tdc_q are free per head and ride into the engine as
    runtime operands (sigma may be traced; no recompile across values).
    ``kv_len`` (B,) int32 valid KV prefix (default full); ``q_offset``
    scalar int32 absolute position of query row 0 for the causal mask."""
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    if isinstance(pols, TDPolicy):
        pols = (pols,) * hq
    pols = tuple(pols)
    if len(pols) != hq:
        raise ValueError(f"{len(pols)} head policies for {hq} query heads")
    p0 = pols[0]
    if p0.mode not in ("quant", "td"):
        raise ValueError(f"td_attention needs mode 'quant'|'td', "
                         f"got {p0.mode!r}")
    for p in pols[1:]:
        if (p.mode, p.bits_a, p.bits_w, p.n_chain) != \
                (p0.mode, p0.bits_a, p0.bits_w, p0.n_chain):
            raise ValueError("attention head policies must share "
                             "(mode, bits_a, bits_w, n_chain)")
    if kv_len is None:
        kv_len = jnp.full((b,), skv, jnp.int32)
    if q_offset is None:
        q_offset = jnp.zeros((), jnp.int32)
    if key is None:
        key = jax.random.PRNGKey(0)
    sigma_vec = jnp.stack([jnp.asarray(p.sigma_chain, jnp.float32)
                           for p in pols])
    tdcq_vec = jnp.stack([jnp.asarray(p.tdc_q, jnp.float32) for p in pols])
    pol_static = p0.replace(mode="td", sigma_chain=0.0, tdc_q=1)
    return _td_attention_ste(pol_static, causal, q, k, v, sigma_vec,
                             tdcq_vec, jnp.asarray(kv_len, jnp.int32),
                             jnp.asarray(q_offset, jnp.int32),
                             td_ref.derive_seed(key))
