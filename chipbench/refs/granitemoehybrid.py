"""The granitemoehybrid family (`model_type` "granitemoehybrid", Granite
4.0-H without experts): weights from the seed, and the plain reference
the served tokens are checked against.

The reference imports nothing of the program.  It is the published
layer written out once more, run over a request's prompt and served
tokens:

    x0 = embedding_multiplier * embed[tok]
    each layer:  x += residual_multiplier * mixer(RMSNorm(x))
                 x += residual_multiplier * W_out(silu(h W_gate) * h W_up),
                      h = RMSNorm(x)
    logits = (RMSNorm(x) @ embed^T) / logits_scaling      (tied head)

with the mixer of `layer_types`: causal GQA softmax attention without
positional encoding at scale `attention_multiplier`, or Mamba2 with one
B/C group: `in_proj` splits into [z | xBC | dt]; xBC = silu(causal
depthwise conv(xBC) + b), split into x, B, C; dt = softplus(dt +
dt_bias), A = -exp(A_log); per head S_t = exp(dt A) S_{t-1} + dt x_t B_t^T
and y_t = S_t C_t + D x_t, a sequential per-token scan in f32; output
out_proj(RMSNorm(y * silu(z))).  Arithmetic as in `refs/common.py`: bf16
weights and activations, f32 accumulation, norms, softmax and the scan
in f32.  `lowp=True` is its control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import Dims
from .common import BF16, F32, _linear, _rmsnorm


def _mixers(m) -> list:
    names = {"attn": "attention", "mamba2": "mamba"}
    return [names.get(m.mixer_at(i), m.mixer_at(i))
            for i in range(m.n_layers)]


def stated(m) -> dict:
    """The program's value of each published key, from its model config.
    The program's Mamba2 has one B/C group, a conv bias and no bias on its
    projections; its heads are d_inner / head_dim."""
    s = m.ssm
    return {"num_hidden_layers": m.n_layers, "hidden_size": m.d_model,
            "num_attention_heads": m.n_heads,
            "num_key_value_heads": m.n_kv_heads,
            "shared_intermediate_size": m.d_ff, "vocab_size": m.vocab,
            "layer_types": _mixers(m),
            "mamba_d_state": s.d_state, "mamba_d_conv": s.d_conv,
            "mamba_expand": s.expand, "mamba_d_head": s.head_dim,
            "mamba_n_heads": s.expand * m.d_model // s.head_dim,
            "mamba_chunk_size": s.chunk, "mamba_n_groups": 1,
            "mamba_conv_bias": True, "mamba_proj_bias": False,
            "attention_bias": m.qkv_bias,
            "num_local_experts": 0 if m.moe is None else m.moe.num_experts,
            "embedding_multiplier": m.embedding_multiplier,
            "residual_multiplier": m.residual_multiplier,
            "attention_multiplier": m.attention_multiplier,
            "logits_scaling": m.logits_scaling,
            "position_embedding_type": "rope" if m.rope else "nope",
            "tie_word_embeddings": m.tie_embeddings,
            "rms_norm_eps": m.rms_eps}


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _count(cfg: dict, kind: str) -> int:
    return cfg["layer_types"].count(kind)


def _sizes(cfg: dict) -> tuple:
    """(d_inner, heads, head dim, d_state, d_conv) of the Mamba2 mixer."""
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    return (di, cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"])


def _linears(cfg: dict) -> dict:
    """name -> (layers of that kind, K, N) of each matmul."""
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = _head_dim(cfg)
    di, nh, _, ns, _ = _sizes(cfg)
    n_attn, n_mamba = _count(cfg, "attention"), _count(cfg, "mamba")
    return {"attn.wq": (n_attn, d, hq * hd), "attn.wk": (n_attn, d, hkv * hd),
            "attn.wv": (n_attn, d, hkv * hd),
            "attn.wo": (n_attn, hq * hd, d),
            "mamba.in_proj": (n_mamba, d, 2 * di + 2 * ns + nh),
            "mamba.out_proj": (n_mamba, di, d),
            "mlp.wg": (n_attn + n_mamba, d, f),
            "mlp.wi": (n_attn + n_mamba, d, f),
            "mlp.wo": (n_attn + n_mamba, f, d)}


def dims(cfg: dict) -> Dims:
    """Sizes for the work counts: the attention layers' heads, and every
    projection of every layer with the tied head as the matmul weights
    per token."""
    return Dims(layers=_count(cfg, "attention"),
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], head_dim=_head_dim(cfg),
                matmul_params=sum(n * k * m for n, k, m in
                                  _linears(cfg).values())
                + cfg["hidden_size"] * cfg["vocab_size"])


def state_bytes(cfg: dict) -> int:
    """The recurrent state one slot holds: per Mamba2 layer the conv
    window, the last d_conv - 1 xBC inputs in bf16 as the activations are,
    and the f32 SSM state of every head."""
    di, nh, hp, ns, dc = _sizes(cfg)
    conv = (dc - 1) * (di + 2 * ns) * 2
    return _count(cfg, "mamba") * (conv + nh * hp * ns * 4)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def make_weights(cfg: dict, seed_words):
    """The whole model's bf16 weights from two 32-bit seed words, in one
    jitted call.  Linear weights N(0, 1/fan_in); the conv N(0, 1/d_conv)
    with bias N(0, 0.02^2); A_log = log(1..heads) and D 1, as the
    published initialisation sets them, and dt_bias as Mamba2's own
    initialisation draws it (dt log-uniform in [0.001, 0.1], dt_bias its
    inverse softplus), not the constant 1 of the Granite port: at 1 every
    head forgets within a token or two, and the random model's greedy
    tokens then follow bf16 rounding (program and reference each sit 7.5 %
    of the logit spread from an f32 forward; on a v5e the float8
    control's widest gaps were only 1.4 times the program's), so no check
    could tell a sound program from a float8 one; norm scales 1.  The tied
    embedding is N(0, 1/(m^2 hidden_size)), m the embedding multiplier, so
    that the scaled input m * e has unit norm: at the head's fan-in std the
    tied head scores each position's own input token ~m times above its
    rivals, and the random model only repeats its last token, whatever its
    mixers compute (at smoke size every served token, and every token of
    the float8 control, was that token).  Each kind of linear is drawn for
    all its layers at once and cut into the per-layer tree the engine
    takes."""
    return jax.jit(functools.partial(_make_weights, cfg=cfg))(
        jnp.asarray(seed_words, jnp.uint32))


def _make_weights(words, cfg):
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    di, nh, _, ns, dc = _sizes(cfg)
    lins = _linears(cfg)
    keys = dict(zip([*lins, "embed", "conv_w", "conv_b", "dt"],
                    jax.random.split(key, len(lins) + 4)))
    stacked = {name: jax.random.normal(keys[name], (n, k, m), BF16)
               * k ** -0.5 for name, (n, k, m) in lins.items()}
    n_mamba = _count(cfg, "mamba")
    conv_w = jax.random.normal(keys["conv_w"], (n_mamba, dc, di + 2 * ns),
                               BF16) * dc ** -0.5
    conv_b = jax.random.normal(keys["conv_b"], (n_mamba, di + 2 * ns),
                               BF16) * 0.02
    # Mamba2's dt init: dt log-uniform in [1e-3, 0.1] (floor 1e-4), and
    # dt_bias its inverse softplus
    dt = jnp.exp(jax.random.uniform(keys["dt"], (n_mamba, nh), F32)
                 * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    dt_bias = (dt + jnp.log(-jnp.expm1(-dt))).astype(BF16)
    ones = lambda n: jnp.ones((n,), BF16)  # noqa: E731
    seen = {"attention": 0, "mamba": 0}
    layers = []
    for kind in cfg["layer_types"]:
        j = seen[kind]
        seen[kind] += 1
        group = "attn" if kind == "attention" else "mamba"
        layer = {"ln1": {"scale": ones(d)}, "ln2": {"scale": ones(d)},
                 group: {}, "mlp": {}}
        for name, w in stacked.items():
            g, leaf = name.split(".")
            if g == group:
                layer[g][leaf] = {"w": w[j]}
            elif g == "mlp":
                layer[g][leaf] = {"w": w[len(layers)]}
        if kind == "mamba":
            layer["mamba"].update(
                conv_w=conv_w[j], conv_b=conv_b[j], dt_bias=dt_bias[j],
                a_log=jnp.log(jnp.arange(1, nh + 1, dtype=F32)).astype(BF16),
                d_skip=ones(nh), norm={"scale": ones(di)})
        layers.append(layer)
    emb_std = 1.0 / (cfg["embedding_multiplier"] * d ** 0.5)
    return {"embed": {"table": jax.random.normal(keys["embed"], (v, d), BF16)
                      * emb_std},
            "layers": layers,
            "final_norm": {"scale": ones(d)}}


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def _attention(q, k, v, scale: float):
    """Causal GQA softmax attention in f32 at the stated scale, no
    positional encoding; q (T, Hq, D), k/v (T, Hkv, D)."""
    t, hq, _ = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k.astype(F32), g, axis=1)
    v = jnp.repeat(v.astype(F32), g, axis=1)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("thd,uhd->htu", q.astype(F32), k) * scale
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("htu,uhd->thd", p, v).astype(q.dtype)


def _mamba(p, h, *, cfg, lowp):
    """The Mamba2 mixer over a whole sequence h (T, d), token by token."""
    di, nh, hp, ns, dc = _sizes(cfg)
    t = h.shape[0]
    zxbcdt = _linear(p["in_proj"], h, lowp)
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * ns], axis=-1)
    # causal depthwise conv: output t sees inputs t - dc + 1 .. t
    xp = jnp.pad(xbc.astype(F32), ((dc - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    conv = sum(xp[i:i + t] * w[i] for i in range(dc)) + p["conv_b"].astype(F32)
    xbc = jax.nn.silu(conv.astype(BF16).astype(F32))
    x, b, c = jnp.split(xbc, [di, di + ns], axis=-1)
    x = x.astype(BF16).astype(F32).reshape(t, nh, hp)
    b, c = b.astype(BF16).astype(F32), c.astype(BF16).astype(F32)
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32))

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp            # (H, P), (N,), (N,), (H,)
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[..., None] * b_t[None, None, :])
        return s, jnp.einsum("hpn,n->hp", s, c_t,
                             precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((nh, hp, ns), F32), (x, b, c, dt))
    y = y + p["d_skip"].astype(F32)[None, :, None] * x
    g = y.reshape(t, di) * jax.nn.silu(z.astype(F32))
    g = _rmsnorm(p["norm"], g, cfg["rms_norm_eps"]).astype(BF16)
    return _linear(p["out_proj"], g, lowp)


def _layer(lp, x, kind, *, cfg, lowp):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t, hd = x.shape[0], _head_dim(cfg)
    lin = functools.partial(_linear, lowp=lowp)
    eps = cfg["rms_norm_eps"]
    res = jnp.asarray(cfg["residual_multiplier"], BF16)
    h = _rmsnorm(lp["ln1"], x, eps)
    if kind == "attention":
        a = lp["attn"]
        q = lin(a["wq"], h).reshape(t, hq, hd)
        k = lin(a["wk"], h).reshape(t, hkv, hd)
        v = lin(a["wv"], h).reshape(t, hkv, hd)
        o = _attention(q, k, v, cfg["attention_multiplier"])
        y = lin(a["wo"], o.reshape(t, hq * hd))
    else:
        y = _mamba(lp["mamba"], h, cfg=cfg, lowp=lowp)
    x = x + y * res
    h = _rmsnorm(lp["ln2"], x, eps)
    m = lp["mlp"]
    y = lin(m["wo"], jax.nn.silu(lin(m["wg"], h)) * lin(m["wi"], h))
    return x + y * res


def _forward(params, toks, lo, *, cfg, lowp, n_rows):
    """Logits (n_rows, V), f32, of positions lo .. lo + n_rows - 1 of a
    padded token sequence, through every layer in one program."""
    table = params["embed"]["table"]
    x = table[toks] * jnp.asarray(cfg["embedding_multiplier"], BF16)
    for lp, kind in zip(params["layers"], cfg["layer_types"]):
        x = _layer(lp, x, kind, cfg=cfg, lowp=lowp)
    h = _rmsnorm(params["final_norm"], x, cfg["rms_norm_eps"])
    h = jax.lax.dynamic_slice_in_dim(h, lo, n_rows)
    logits = _linear({"w": table.T}, h, lowp, out_f32=True)
    return logits / cfg["logits_scaling"]


class Reference:
    """The plain reference of one configuration for sequences padded to
    `t_pad` tokens, reading the logits of `n_rows` positions."""

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
