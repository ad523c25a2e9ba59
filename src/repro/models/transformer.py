"""Unified decoder-only stack covering the dense, MoE, hybrid (zamba2) and
attention-free (rwkv6) assigned architectures via per-layer mixer dispatch.

Layer anatomy (pre-norm residual):
    x += mixer(ln1(x))      mixer in {attn, shared_attn, mamba2, rwkv6}
    x += ffn(ln2(x))        ffn   in {swiglu, moe, rwkv_chanmix}
Granite-style multipliers (`ModelCfg`): the embedding is scaled by
`embedding_multiplier`, each branch by `residual_multiplier` before it is
added, and the logits divided by `logits_scaling`; at their defaults
(1.0) none of them is applied.

"shared_attn" (zamba2) applies one weight-tied attention block at several
depths (per-site norms are private, block weights shared — stored once at
the top level).  VLM/audio frontends are stubs: `embeds` (precomputed
patch/frame embeddings) are adapter-projected and prepended to the token
embeddings, matching the assignment's "modality frontend is a STUB" rule.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg
from repro.models import attention, common, ffn, mamba2, rwkv6
from repro.tdsim.policy import NetworkPolicy


def _is_homogeneous(cfg: ModelCfg) -> bool:
    """scan-over-layers requires identical layer structure (no shared-attn
    hybrids, single mixer/ffn kind)."""
    mixers = {cfg.mixer_at(i) for i in range(cfg.n_layers)}
    ffns = {_ffn_kind(cfg, i) for i in range(cfg.n_layers)}
    return len(mixers) == 1 and len(ffns) == 1 and \
        "shared_attn" not in mixers


def _can_scan(cfg: ModelCfg, pol) -> bool:
    """Heterogeneous per-layer policies are static per layer, so the layer
    bodies differ and must unroll (a homogeneous NetworkPolicy still
    scans)."""
    if not (cfg.scan_layers and _is_homogeneous(cfg)):
        return False
    return not (isinstance(pol, NetworkPolicy) and not pol.homogeneous)


def _ffn_kind(cfg: ModelCfg, layer: int) -> str:
    if cfg.ffn_pattern is not None:
        return cfg.ffn_pattern[layer]
    if cfg.rwkv is not None:
        return "rwkv_cm"
    if cfg.moe is not None:
        return "moe"
    return "swiglu"


def init_params(key: jax.Array, cfg: ModelCfg, pol,
                dtype=jnp.float32) -> dict:
    top = common.pol_top(pol)
    keys = jax.random.split(key, cfg.n_layers + 4)
    params: dict = {"embed": common.embed_init(keys[0], cfg.vocab,
                                               cfg.d_model, dtype)}
    if cfg.frontend is not None:
        d_in = cfg.d_frontend or cfg.d_model
        params["adapter"] = common.dense_init(keys[1], d_in, cfg.d_model,
                                              top, dtype=dtype)
    if any(cfg.mixer_at(i) == "shared_attn" for i in range(cfg.n_layers)):
        params["shared_attn"] = attention.attn_init(keys[2], cfg, top, dtype)

    layers = []
    for i in range(cfg.n_layers):
        pol_i = common.pol_at(pol, i)
        lk = jax.random.split(keys[3 + i], 4)
        mix = cfg.mixer_at(i)
        lp: dict = {"ln1": common.rmsnorm_init(cfg.d_model, dtype),
                    "ln2": common.rmsnorm_init(cfg.d_model, dtype)}
        if mix == "attn":
            lp["attn"] = attention.attn_init(lk[0], cfg, pol_i, dtype)
        elif mix == "mamba2":
            lp["mamba"] = mamba2.mamba2_init(lk[0], cfg, pol_i, dtype)
        elif mix == "rwkv6":
            lp["timemix"] = rwkv6.timemix_init(lk[0], cfg, pol_i, dtype)
        elif mix == "shared_attn":
            pass  # weights live at params["shared_attn"]
        else:
            raise ValueError(mix)
        fk = _ffn_kind(cfg, i)
        if fk == "swiglu":
            lp["mlp"] = ffn.swiglu_init(lk[1], cfg.d_model, cfg.d_ff, pol_i,
                                        dtype)
        elif fk == "moe":
            lp["moe"] = ffn.moe_init(lk[1], cfg.d_model, cfg.moe, pol_i,
                                     dtype)
        elif fk == "rwkv_cm":
            lp["chanmix"] = rwkv6.chanmix_init(lk[1], cfg, pol_i, dtype)
        # fk == "none": mixer-only layer (zamba2 mamba blocks)
        layers.append(lp)
    if _can_scan(cfg, pol):
        layers = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *layers)
    params["layers"] = layers
    params["final_norm"] = common.rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(
            keys[-1], cfg.d_model, cfg.vocab, top, dtype=dtype,
            scale=1.0 / cfg.d_model ** 0.5)
    return params


def _layer_apply(lp: dict, shared: dict | None, x: jnp.ndarray,
                 cfg: ModelCfg, pol, i: int,
                 positions: jnp.ndarray,
                 cache: dict | None,
                 key: jax.Array | None,
                 shared_pol=None,
                 attn_pols=None,
                 true_len=None) -> tuple[jnp.ndarray, dict | None, dict]:
    mix = cfg.mixer_at(i)
    aux: dict = {}
    kmix = common.fold_key(key, 2 * i)
    kffn = common.fold_key(key, 2 * i + 1)
    h = common.rmsnorm(lp["ln1"], x, cfg.rms_eps)
    new_cache = None
    if mix == "attn":
        y, new_cache = attention.attention(lp["attn"], h, cfg, pol,
                                           positions, cache=cache, key=kmix,
                                           attn_pols=attn_pols)
    elif mix == "shared_attn":
        # weight-tied shared block: its params were initialized with the
        # top-level policy, so it must run under that policy too
        y, new_cache = attention.attention(
            shared, h, cfg, pol if shared_pol is None else shared_pol,
            positions, cache=cache, key=kmix, attn_pols=attn_pols)
    elif mix == "mamba2":
        y, new_cache = mamba2.mamba2(lp["mamba"], h, cfg, pol,
                                     state=cache, key=kmix,
                                     true_len=true_len)
    elif mix == "rwkv6":
        y, new_cache = rwkv6.timemix(lp["timemix"], h, cfg, pol,
                                     state=cache, key=kmix)
    else:
        raise ValueError(mix)
    x = x + _residual(y, cfg)

    fk = _ffn_kind(cfg, i)
    if fk == "none":
        return x, new_cache, aux
    h = common.rmsnorm(lp["ln2"], x, cfg.rms_eps)
    if fk == "swiglu":
        y = ffn.swiglu(lp["mlp"], h, pol, kffn)
    elif fk == "moe":
        y, aux = ffn.moe_ffn(lp["moe"], h, cfg.moe, pol, kffn)
    else:
        cm_state = (cache if (cache is not None and "shift_c" in
                              (cache or {})) else None)
        y, cm_new = rwkv6.chanmix(lp["chanmix"], h, cfg, pol,
                                  state=cm_state, key=kffn)
        if new_cache is not None and cm_new is not None:
            new_cache = {**new_cache, **cm_new}
    return x + _residual(y, cfg), new_cache, aux


def _residual(y: jnp.ndarray, cfg: ModelCfg) -> jnp.ndarray:
    m = cfg.residual_multiplier
    return y if m == 1.0 else y * jnp.asarray(m, y.dtype)


def forward(params: dict, batch: dict, cfg: ModelCfg, pol,
            caches: list | None = None,
            positions: jnp.ndarray | None = None,
            key: jax.Array | None = None,
            remat: str = "none",
            true_len=None
            ) -> tuple[jnp.ndarray, list | None, dict]:
    """Returns (logits, new_caches, aux).  batch: {"tokens": (B,S)} plus
    optional {"embeds": (B,Nv,d_f)} for stub frontends.  `true_len` (B,)
    marks right-padded prompts filling `caches`: recurrent mixers stop
    their state at each row's true length."""
    tokens = batch["tokens"]
    x = common.embed(params["embed"], tokens)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if cfg.frontend is not None and "embeds" in batch:
        emb = common.dense(params["adapter"], batch["embeds"],
                           common.pol_top(pol))
        x = jnp.concatenate([emb.astype(x.dtype), x], axis=1)
    x = common.maybe_constrain(x, common.batch_sharding_axes(), None, None)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)

    shared = params.get("shared_attn")
    new_caches: list = [None] * cfg.n_layers
    aux_all: dict = {}

    attn_pols = common.pol_attn(pol)

    def run_layer(lp, xx, cache, i, lkey):
        return _layer_apply(lp, shared, xx, cfg, common.pol_at(pol, i), i,
                            positions, cache, lkey,
                            shared_pol=common.pol_top(pol),
                            attn_pols=attn_pols, true_len=true_len)

    if remat == "full":
        run_layer = jax.checkpoint(run_layer, static_argnums=(3,))
    elif remat == "dots":
        run_layer = jax.checkpoint(
            run_layer, static_argnums=(3,),
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    if _can_scan(cfg, pol):
        stacked = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *params["layers"]) \
            if isinstance(params["layers"], list) else params["layers"]
        stacked_caches = None
        if caches is not None:
            stacked_caches = (jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *caches)
                if isinstance(caches, list) else caches)

        def scan_body(carry, xs):
            xx, kk = carry
            lp, cache_i, idx = xs
            xx, nc, aux = _layer_apply(lp, shared, xx, cfg,
                                       common.pol_at(pol, 0), 0,
                                       positions, cache_i,
                                       common.fold_key(kk, idx),
                                       shared_pol=common.pol_top(pol),
                                       attn_pols=attn_pols,
                                       true_len=true_len)
            return (xx, kk), (nc, aux)

        body = scan_body
        if remat in ("full", "dots"):
            pol_fn = (None if remat == "full" else
                      jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
            body = jax.checkpoint(scan_body, policy=pol_fn) \
                if pol_fn else jax.checkpoint(scan_body)
        (x, _), (nc_stack, aux_stack) = jax.lax.scan(
            body, (x, key), (stacked, stacked_caches,
                             jnp.arange(cfg.n_layers)))
        if caches is not None:
            new_caches = nc_stack          # stacked pytree, same as input
        aux_all = {k: v.sum() for k, v in aux_stack.items()}
    else:
        for i, lp in enumerate(params["layers"]):
            cache = caches[i] if caches is not None else None
            x, nc, aux = run_layer(lp, x, cache, i, key)
            new_caches[i] = nc
            for k2, v2 in aux.items():
                aux_all[k2] = aux_all.get(k2, 0.0) + v2

    x = common.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].T
    else:
        logits = common.dense(params["lm_head"], x, common.pol_top(pol),
                              common.fold_key(key, 10_000))
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    # keep the (huge) logits vocab-sharded; CE's logsumexp reduces over it
    logits = common.maybe_constrain(
        logits, common.batch_sharding_axes(), None, "model")
    return logits, (new_caches if caches is not None else None), aux_all


def init_caches(b: int, s_cache: int, cfg: ModelCfg,
                dtype=jnp.bfloat16, pol=None, per_row_idx: bool = False):
    """`pol` must be the policy the forward pass will run under: a
    heterogeneous NetworkPolicy unrolls layers, so its caches must stay a
    per-layer list even when cfg.scan_layers is set (pol=None keeps the
    config-only behavior).  `per_row_idx` builds the serving engine's
    ragged-slot attention caches (one fill index per batch row).  Mamba2
    layers hold their conv window in `dtype` and their SSM state in f32."""
    caches = []
    for i in range(cfg.n_layers):
        mix = cfg.mixer_at(i)
        if mix in ("attn", "shared_attn"):
            caches.append(attention.init_cache(b, s_cache, cfg, dtype,
                                               per_row_idx=per_row_idx))
        elif mix == "mamba2":
            caches.append(mamba2.init_state(b, cfg, dtype))
        elif mix == "rwkv6":
            caches.append(rwkv6.init_state(b, cfg, jnp.float32))
    if _can_scan(cfg, pol):
        return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *caches)
    return caches
