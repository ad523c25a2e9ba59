"""Weights from the seed, and the plain reference the served tokens are
checked against.

The benchmark makes the weights itself, in one jitted call on the device,
in the tree the serving engine takes, and hands the same arrays to the
engine and to the reference.  The reference imports nothing of the
program: it is a straightforward qwen2-style decoder written here
(RMSNorm, RoPE, GQA causal attention over the whole sequence, SwiGLU),
run once per request over its prompt and served tokens.  It follows the
arithmetic the configuration states: bf16 weights and activations,
matmuls accumulated in f32 and rounded to bf16, norms, RoPE and softmax
in f32.

`lowp=True` is the control: the same reference with every matmul input,
activation and weight, rounded through float8 e4m3 first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _linears(cfg: dict) -> dict:
    """name -> (K, N, bias, std) of one layer's matmuls."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
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


def _layer(lp, x, pos, *, cfg, lowp):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t, d = x.shape
    hd = d // hq
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


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best, per position."""
    best = ref_logits.max(-1)
    return best - np.take_along_axis(ref_logits, chosen[:, None], 1)[:, 0]
