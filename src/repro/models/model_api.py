"""Uniform model API over the two families (decoder-only, enc-dec):

  init(key, cfg, pol)                      -> params
  train_loss(params, batch, cfg, pol, key) -> (loss, metrics)
  prefill(params, batch, cfg, pol, s_cache)-> (last_logits, state)
  decode_step(params, tok, state, cfg, pol)-> (logits, state)
  matmul_shapes(cfg)                       -> energy-meter ledger

`state` is {"layers": [...per-layer cache...], "enc_out": (B,S,d)|None}.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg
from repro.models import common, encdec, transformer
from repro.tdsim.energy_meter import MatmulShape


# ---------------------------------------------------------------------------
# decoder-only family
# ---------------------------------------------------------------------------
def _dec_init(key, cfg: ModelCfg, pol, dtype=jnp.float32):
    return transformer.init_params(key, cfg, pol, dtype)


def _dec_train_loss(params, batch, cfg: ModelCfg, pol, key=None,
                    remat: str = "none"):
    logits, _, aux = transformer.forward(params, batch, cfg, pol,
                                         key=key, remat=remat)
    labels = batch["labels"]
    if cfg.frontend is not None and "embeds" in batch:
        n_vis = batch["embeds"].shape[1]
        logits = logits[:, n_vis:]
    loss = common.cross_entropy(logits, labels, batch.get("mask"))
    metrics = {"ce": loss}
    for k, v in aux.items():
        if k.startswith("moe_") and k != "moe_dropped":
            loss = loss + v
        metrics[k] = v
    metrics["loss"] = loss
    return loss, metrics


def _dec_prefill(params, batch, cfg: ModelCfg, pol, s_cache: int,
                 key=None, cache_dtype=jnp.bfloat16, true_len=None):
    b = batch["tokens"].shape[0]
    caches = transformer.init_caches(b, s_cache, cfg, cache_dtype, pol=pol)
    tl = None
    if true_len is not None:
        tl = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (b,))
    logits, caches, _ = transformer.forward(params, batch, cfg, pol,
                                            caches=caches, key=key,
                                            true_len=tl)
    if true_len is not None:
        # bucket-padded prompts (serving engine): causal masking keeps every
        # row < true_len clean of the pad junk, and recurrent mixers stop
        # their state at true_len, so the next-token logits live at the
        # TRUE last prompt position, not the padded one
        last = jnp.take_along_axis(logits, tl[:, None, None] - 1, axis=1)
        return last, {"layers": caches, "enc_out": None}
    return logits[:, -1:], {"layers": caches, "enc_out": None}


def _dec_decode(params, tok, state, cfg: ModelCfg, pol, key=None):
    caches = state["layers"]
    # positions = current fill index of the first attn cache (all equal
    # across layers; a per-row (B,) vector for ragged serving slots)
    pos = None
    if isinstance(caches, dict):          # stacked caches (scan_layers)
        if "idx" in caches:
            pos = caches["idx"][0]
    else:
        for c in caches:
            if c is not None and "idx" in c:
                pos = c["idx"]
                break
    if pos is None:  # pure-SSM model: position is irrelevant (no RoPE)
        pos = jnp.zeros((1,), jnp.int32)
    elif pos.ndim:   # per-slot ragged caches: one query position per row
        pos = pos[:, None]
    else:
        pos = pos[None]
    logits, new_caches, _ = transformer.forward(
        params, {"tokens": tok}, cfg, pol, caches=caches,
        positions=pos, key=key)
    return logits[:, -1], {"layers": new_caches, "enc_out": None}


# ---------------------------------------------------------------------------
# enc-dec family
# ---------------------------------------------------------------------------
def _ed_init(key, cfg: ModelCfg, pol, dtype=jnp.float32):
    return encdec.init_params(key, cfg, pol, dtype)


def _ed_train_loss(params, batch, cfg: ModelCfg, pol, key=None,
                   remat: str = "none"):
    enc_out = encdec.encode(params, batch["embeds"], cfg, pol, key=key,
                            remat=remat)
    logits, _ = encdec.decode(params, batch["tokens"], enc_out, cfg, pol,
                              key=key, remat=remat)
    loss = common.cross_entropy(logits, batch["labels"], batch.get("mask"))
    return loss, {"ce": loss, "loss": loss}


def _ed_prefill(params, batch, cfg: ModelCfg, pol, s_cache: int,
                key=None, cache_dtype=jnp.bfloat16):
    enc_out = encdec.encode(params, batch["embeds"], cfg, pol, key=key)
    b = batch["tokens"].shape[0]
    caches = encdec.init_caches(b, s_cache, cfg, cache_dtype)
    logits, caches = encdec.decode(params, batch["tokens"], enc_out, cfg,
                                   pol, caches=caches, key=key)
    return logits[:, -1:], {"layers": caches, "enc_out": enc_out}


def _ed_decode(params, tok, state, cfg: ModelCfg, pol, key=None):
    caches = state["layers"]
    pos = caches[0]["self"]["idx"][None]
    logits, new_caches = encdec.decode(params, tok, state["enc_out"], cfg,
                                       pol, caches=caches, positions=pos,
                                       key=key)
    return logits[:, -1], {"layers": new_caches, "enc_out": state["enc_out"]}


# ---------------------------------------------------------------------------
# energy-meter ledger: every matmul per token, layer counts folded in
# ---------------------------------------------------------------------------
def matmul_shapes(cfg: ModelCfg) -> list[MatmulShape]:
    d, hd = cfg.d_model, cfg.hd
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    out = []
    n_attn = sum(1 for i in range(cfg.n_layers)
                 if cfg.mixer_at(i) in ("attn", "shared_attn"))
    n_mamba = sum(1 for i in range(cfg.n_layers)
                  if cfg.mixer_at(i) == "mamba2")
    n_rwkv = sum(1 for i in range(cfg.n_layers)
                 if cfg.mixer_at(i) == "rwkv6")
    if n_attn:
        out += [MatmulShape("attn.q", d, hq * hd, n_attn),
                MatmulShape("attn.k", d, hkv * hd, n_attn),
                MatmulShape("attn.v", d, hkv * hd, n_attn),
                MatmulShape("attn.o", hq * hd, d, n_attn)]
    if n_mamba and cfg.ssm:
        di = cfg.ssm.expand * d
        nh = di // cfg.ssm.head_dim
        out += [MatmulShape("mamba.in", d,
                            2 * di + 2 * cfg.ssm.d_state + nh, n_mamba),
                MatmulShape("mamba.out", di, d, n_mamba)]
    if n_rwkv:
        out += [MatmulShape(f"rwkv.{nm}", d, d, n_rwkv)
                for nm in ("r", "k", "v", "g", "o")]
    if cfg.rwkv is not None:
        out += [MatmulShape("cm.k", d, cfg.d_ff, cfg.n_layers),
                MatmulShape("cm.v", cfg.d_ff, d, cfg.n_layers),
                MatmulShape("cm.r", d, d, cfg.n_layers)]
    elif cfg.moe is not None:
        f = cfg.moe.d_ff_expert
        act = cfg.moe.top_k
        out += [MatmulShape("moe.wi", d, f, cfg.n_layers * act),
                MatmulShape("moe.wg", d, f, cfg.n_layers * act),
                MatmulShape("moe.wo", f, d, cfg.n_layers * act),
                MatmulShape("moe.router", d, cfg.moe.num_experts,
                            cfg.n_layers)]
    else:
        out += [MatmulShape("mlp.wi", d, cfg.d_ff, cfg.n_layers),
                MatmulShape("mlp.wg", d, cfg.d_ff, cfg.n_layers),
                MatmulShape("mlp.wo", cfg.d_ff, d, cfg.n_layers)]
    if cfg.family == "encdec":
        n_enc = cfg.n_enc_layers or cfg.n_layers
        out += [MatmulShape("enc.attn", d, hq * hd, 4 * n_enc),
                MatmulShape("enc.mlp", d, cfg.d_ff, 3 * n_enc),
                MatmulShape("dec.xattn", d, hq * hd, 4 * cfg.n_layers)]
    out.append(MatmulShape("lm_head", d, cfg.vocab, 1.0))
    return out


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
_API = {
    "decoder": dict(init=_dec_init, train_loss=_dec_train_loss,
                    prefill=_dec_prefill, decode_step=_dec_decode),
    "encdec": dict(init=_ed_init, train_loss=_ed_train_loss,
                   prefill=_ed_prefill, decode_step=_ed_decode),
}


def get_api(cfg: ModelCfg) -> dict:
    return _API[cfg.family]
