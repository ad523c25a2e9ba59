"""The qwen2 family (`model_type` "qwen2"): weights from the seed, and
the plain reference the served tokens are checked against.

The benchmark makes the weights itself, in one jitted call on the device,
in the tree the serving engine takes, and hands the same arrays to the
engine and to the reference.  The reference imports nothing of the
program: it is a straightforward qwen2 decoder written here (RMSNorm,
RoPE, GQA causal attention with q/k/v biases over the whole sequence,
SwiGLU), run once per request over its prompt and served tokens, with
the arithmetic of `refs/common.py`.  `lowp=True` is its control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import Dims
from .common import BF16, _attention, _linear, _rmsnorm, _rope


def stated(m) -> dict:
    """The program's value of each published key, from its model config."""
    return {"num_hidden_layers": m.n_layers, "hidden_size": m.d_model,
            "num_attention_heads": m.n_heads,
            "num_key_value_heads": m.n_kv_heads,
            "intermediate_size": m.d_ff, "vocab_size": m.vocab,
            "rope_theta": m.rope_theta, "rms_norm_eps": m.rms_eps,
            "tie_word_embeddings": m.tie_embeddings}


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def dims(cfg: dict) -> Dims:
    """Sizes for the work counts; every linear of every layer and the LM
    head multiply each token (the embedding lookup multiplies nothing)."""
    per_layer = sum(k * n for k, n, _, _ in _linears(cfg).values())
    return Dims(layers=cfg["num_hidden_layers"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], head_dim=_head_dim(cfg),
                matmul_params=cfg["num_hidden_layers"] * per_layer
                + cfg["hidden_size"] * cfg["vocab_size"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _linears(cfg: dict) -> dict:
    """name -> (K, N, bias, std) of one layer's matmuls."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = _head_dim(cfg)
    return {"attn.wq": (d, hq * hd, True, d ** -0.5),
            "attn.wk": (d, hkv * hd, True, d ** -0.5),
            "attn.wv": (d, hkv * hd, True, d ** -0.5),
            "attn.wo": (hq * hd, d, False, (hq * hd) ** -0.5),
            "mlp.wg": (d, f, False, d ** -0.5),
            "mlp.wi": (d, f, False, d ** -0.5),
            "mlp.wo": (f, d, False, f ** -0.5)}


def make_weights(cfg: dict, seed_words):
    """The whole model's bf16 weights from two 32-bit seed words, in one
    jitted call.  Linear weights are N(0, std^2) with the fan-in std of
    the program's own initialiser, biases N(0, 0.02^2), the embedding
    N(0, 0.02^2), norm scales 1.  Each kind of linear is drawn for all
    layers at once and cut into the per-layer tree the engine takes.  (A
    draw per layer made the set-up ~11 s longer on a v5e and the peak no
    lower: the serving state, not this call, sets the peak.)"""
    return jax.jit(functools.partial(_make_weights, cfg=cfg))(
        jnp.asarray(seed_words, jnp.uint32))


def _make_weights(words, cfg):
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    n_layers = cfg["num_hidden_layers"]
    lins = _linears(cfg)
    keys = dict(zip([*lins, "embed", "lm_head"],
                    jax.random.split(key, len(lins) + 2)))

    def linear(k, lead, k_in, n_out, bias, std):
        """One kind of linear for all `lead` layers at once."""
        kw, kb = jax.random.split(k)
        p = {"w": jax.random.normal(kw, (*lead, k_in, n_out), BF16) * std}
        if bias:
            p["b"] = jax.random.normal(kb, (*lead, n_out), BF16) * 0.02
        return p

    stacked = {name: linear(keys[name], (n_layers,), *spec)
               for name, spec in lins.items()}
    layers = []
    for i in range(n_layers):
        layer = {"ln1": {"scale": jnp.ones((d,), BF16)},
                 "ln2": {"scale": jnp.ones((d,), BF16)},
                 "attn": {}, "mlp": {}}
        for name, p in stacked.items():
            group, leaf = name.split(".")
            layer[group][leaf] = {k: a[i] for k, a in p.items()}
        layers.append(layer)
    return {"embed": {"table": jax.random.normal(keys["embed"], (v, d), BF16)
                      * 0.02},
            "layers": layers,
            "final_norm": {"scale": jnp.ones((d,), BF16)},
            "lm_head": linear(keys["lm_head"], (), d, v, False, d ** -0.5)}


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def _layer(lp, x, pos, *, cfg, lowp):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t = x.shape[0]
    hd = _head_dim(cfg)
    lin = functools.partial(_linear, lowp=lowp)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rmsnorm(lp["ln1"], x, eps)
    a = lp["attn"]
    q = _rope(lin(a["wq"], h).reshape(t, hq, hd), pos, theta)
    k = _rope(lin(a["wk"], h).reshape(t, hkv, hd), pos, theta)
    v = lin(a["wv"], h).reshape(t, hkv, hd)
    x = x + lin(a["wo"], _attention(q, k, v).reshape(t, hq * hd))
    h = _rmsnorm(lp["ln2"], x, eps)
    m = lp["mlp"]
    return x + lin(m["wo"], jax.nn.silu(lin(m["wg"], h)) * lin(m["wi"], h))


def _forward(params, toks, lo, *, cfg, lowp, n_rows):
    """Logits (n_rows, V), f32, of positions lo .. lo + n_rows - 1 of a
    padded token sequence, through every layer in one program."""
    x = params["embed"]["table"][toks]
    pos = jnp.arange(toks.shape[0], dtype=jnp.int32)
    for lp in params["layers"]:
        x = _layer(lp, x, pos, cfg=cfg, lowp=lowp)
    h = _rmsnorm(params["final_norm"], x, cfg["rms_norm_eps"])
    h = jax.lax.dynamic_slice_in_dim(h, lo, n_rows)
    return _linear(params["lm_head"], h, lowp, out_f32=True)


class Reference:
    """The plain reference of one configuration for sequences padded to
    `t_pad` tokens, reading the logits of `n_rows` positions.  The whole
    model is one program, as the served prefill and decode are."""

    def __init__(self, cfg: dict, t_pad: int, n_rows: int):
        self.cfg, self.t_pad, self.n_rows = cfg, t_pad, n_rows
        self._forward = {lp: jax.jit(functools.partial(
            _forward, cfg=cfg, lowp=lp, n_rows=n_rows))
            for lp in (False, True)}

    def logits(self, params, toks, lo: int, hi: int,
               lowp: bool = False) -> np.ndarray:
        """f32 logits of positions [lo, hi)."""
        if hi - lo > self.n_rows:
            raise ValueError(f"{hi - lo} positions, the reference reads "
                             f"{self.n_rows}")
        start = min(lo, self.t_pad - self.n_rows)
        lg = self._forward[lowp](params, jnp.asarray(toks),
                                 jnp.asarray(start, jnp.int32))
        return np.asarray(lg)[lo - start:hi - start]

