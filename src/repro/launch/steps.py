"""Step builders: train_step (grad-accum microbatching + AdamW/ZeRO),
prefill_step, serve_step (single-token decode).

Every step is a pure function suitable for jax.jit with explicit
in/out_shardings; the builders close over static config only.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCfg
from repro.models import attention, common, get_api
from repro.optim import adamw

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
          "float16": jnp.float16}


def build_train_step(arch: ArchConfig, shape: ShapeCfg):
    cfg = arch.model
    pol = common.resolve_arch_policy(arch)
    api = get_api(cfg)
    n_micro = arch.microbatches_for(shape.name)
    compute_dt = DTYPES[arch.train.compute_dtype]
    ar_dt = DTYPES[arch.train.grad_allreduce_dtype]

    def loss_fn(params, mb, key):
        p_c = common.cast_tree(params, compute_dt)
        loss, metrics = api["train_loss"](p_c, mb, cfg, pol, key,
                                          remat=arch.train.remat)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch, seed):
        key = jax.random.key(seed)
        if n_micro == 1:
            (loss, metrics), grads = grad_fn(params, batch, key)
        else:
            def resh(a):
                return a.reshape(n_micro, a.shape[0] // n_micro,
                                 *a.shape[1:])
            mbs = jax.tree_util.tree_map(resh, batch)

            def body(carry, xs):
                gacc, i = carry
                mb = xs
                (l, mets), g = grad_fn(params, mb,
                                       jax.random.fold_in(key, i))
                g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(ar_dt), gacc, g)
                return (g, i + 1), (l, mets)

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, ar_dt), params)
            (gsum, _), (losses, metric_seq) = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.int32)), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, gsum)
            loss = losses.mean()
            metrics = jax.tree_util.tree_map(lambda m: m.mean(), metric_seq)

        new_params, new_opt, om = adamw.apply_updates(
            params, grads, opt_state, arch.train)
        metrics = {**metrics, **om}
        return new_params, new_opt, metrics

    return train_step


def build_prefill_step(arch: ArchConfig, shape: ShapeCfg):
    cfg = arch.model
    pol = common.resolve_arch_policy(arch)
    api = get_api(cfg)
    compute_dt = DTYPES[arch.train.compute_dtype]

    def prefill_step(params, batch):
        p_c = common.cast_tree(params, compute_dt)
        b = {k: v for k, v in batch.items() if k != "labels"}
        logits, state = api["prefill"](p_c, b, cfg, pol,
                                       s_cache=shape.seq_len)
        return logits, state

    return prefill_step


def build_serve_step(arch: ArchConfig, shape: ShapeCfg):
    """One decode step: new token against a seq_len KV cache/SSM state."""
    cfg = arch.model
    pol = common.resolve_arch_policy(arch)
    api = get_api(cfg)
    compute_dt = DTYPES[arch.train.compute_dtype]

    def serve_step(params, tok, state):
        p_c = common.cast_tree(params, compute_dt)
        logits, new_state = api["decode_step"](p_c, tok, state, cfg, pol)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, new_state

    return serve_step


def build_adaptive_serve_step(arch: ArchConfig, shape: ShapeCfg):
    """Drift-adaptive decode step: `build_serve_step` plus (a) the policy's
    (sigma_chain, tdc_q) rebound to a runtime ``ops`` operand
    (`common.runtime_td_policy` — hot-swappable with zero recompiles) and
    (b) a fused running estimate of the activation bit density
    (`ft.drift.measure_p_x_one` over this step's token embeddings), the
    operating-point statistic the drift detector watches.  ``active`` is
    the (B,) occupancy mask of the continuous batch: free slots carry a
    stale last token, and letting it into the measurement would bias the
    statistic toward dead traffic.  Another runtime operand — any fill mix
    reuses the one compiled program.  Returns
    ``(next_tok, new_state, p_x_one)``."""
    from repro.ft import drift as ft_drift

    cfg = arch.model
    pol = common.resolve_arch_policy(arch)
    api = get_api(cfg)
    compute_dt = DTYPES[arch.train.compute_dtype]
    bits_a = common.pol_at(pol, 0).bits_a

    def serve_step(params, tok, state, ops, active):
        p_c = common.cast_tree(params, compute_dt)
        pol_rt = common.runtime_td_policy(pol, ops)
        logits, new_state = api["decode_step"](p_c, tok, state, cfg, pol_rt)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        px = ft_drift.measure_p_x_one(
            common.embed(params["embed"], tok[:, 0]).astype(jnp.float32),
            bits_a, mask=active)
        return next_tok, new_state, px

    return serve_step


def build_ragged_prefill_step(arch: ArchConfig, prompt_pad: int):
    """Bucketed prefill for the continuous-batching serve engine.

    Prompts are right-padded to the `prompt_pad` bucket and the TRUE
    length rides in as a runtime int32, so every admission reuses ONE
    compiled program regardless of prompt length; the causal mask keeps
    all rows below the true length clean of the pad junk, and the
    next-token logits are gathered at the true last position.  Returns
    ``(next_tok (B, 1), state)`` with caches sized at `prompt_pad` — the
    insert step copies them into a decode-cache slot.
    """
    cfg = arch.model
    if cfg.family != "decoder":
        raise ValueError("ragged prefill requires a decoder-family model, "
                         f"got {cfg.family!r}")
    pol = common.resolve_arch_policy(arch)
    api = get_api(cfg)
    compute_dt = DTYPES[arch.train.compute_dtype]

    def prefill_step(params, toks, true_len):
        p_c = common.cast_tree(params, compute_dt)
        logits, state = api["prefill"](p_c, {"tokens": toks}, cfg, pol,
                                       s_cache=prompt_pad,
                                       true_len=true_len)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        return tok, state

    return prefill_step


def build_insert_step():
    """Copy a b=1 prefilled state into slot `i` of the batched decode
    state (the slot-recycle primitive of the continuous-batching engine).

    Generic over the cache pytree: leaves with a leading batch dim (KV
    tensors, SSM/RWKV state) are written at the slot row — a prefill
    cache shorter than the decode cache writes its prefix, and a
    (1, P, Hkv, D) prefill KV cache is laid out lane-dense first, as the
    per-row decode cache stores it (the small source is reshaped, never
    the destination) — while the attention fill-index leaf (``idx``: dst
    ``(B,)`` per-row, src scalar) is set to the TRUE prompt length, which
    is exactly what masks the pad junk the bucketed prefill wrote past it.
    """

    def insert_step(dst_state, src_state, slot, length):
        def ins(path, dst, src):
            if path[-1] == jax.tree_util.DictKey("idx"):
                # scalar fill idx -> per-row idx[slot]
                return jax.lax.dynamic_update_slice(
                    dst, jnp.asarray(length, dst.dtype)[None], (slot,))
            if src.ndim > dst.ndim:
                src = attention.lane_dense(src)
            return jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype),
                (slot,) + (0,) * (src.ndim - 1))

        return jax.tree_util.tree_map_with_path(ins, dst_state, src_state)

    return insert_step


def build_forward_eval(arch: ArchConfig):
    """Forward-only loss eval (used by noise-tolerance runs on LMs)."""
    cfg = arch.model
    api = get_api(cfg)

    def eval_step(params, batch, pol, key):
        loss, metrics = api["train_loss"](params, batch, cfg, pol, key)
        return metrics

    return eval_step
