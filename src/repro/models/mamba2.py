"""Mamba-2 mixer (SSD — state space duality, arXiv:2405.21060) for the
zamba2 hybrid architecture.

Training path: chunked SSD algorithm (block-diagonal intra-chunk attention
via segment-sums + inter-chunk state recurrence with a lax.scan over
chunks) — O(S * chunk) instead of O(S^2).
Decode path: single-step recurrent update of the (H, P, N) SSM state plus a
rolling causal-conv window, O(1) per token.
Bucketed prefill (the serving engine's right-padded prompts, `true_len`):
dt is 0 at every pad position, so the SSM state passes through the pads
unchanged (exp(0 * A) = 1, increment 0), and the conv window is the last
d_conv - 1 TRUE inputs; the state returned is the state at the true length.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg, SSMCfg
from repro.models import common

NEG_INF = -1e30


def dims(cfg: ModelCfg) -> tuple[int, int, int, int, int]:
    ssm = cfg.ssm
    d_inner = ssm.expand * cfg.d_model
    n_heads = d_inner // ssm.head_dim
    return d_inner, n_heads, ssm.head_dim, ssm.d_state, ssm.d_conv


def mamba2_init(key: jax.Array, cfg: ModelCfg, pol,
                dtype=jnp.float32) -> dict:
    d = cfg.d_model
    di, nh, hp, ns, dc = dims(cfg)
    d_xbc = di + 2 * ns                       # x + B + C (n_groups = 1)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "in_proj": common.dense_init(k1, d, 2 * di + 2 * ns + nh, pol,
                                     dtype=dtype),
        "conv_w": jax.random.normal(k2, (dc, d_xbc), dtype) * 0.2,
        "conv_b": jnp.zeros((d_xbc,), dtype),
        "dt_bias": jnp.log(jnp.exp(jnp.linspace(1e-3, 0.1, nh)) - 1.0
                           ).astype(dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(dtype),
        "d_skip": jnp.ones((nh,), dtype),
        "norm": common.rmsnorm_init(di, dtype),
        "out_proj": common.dense_init(k3, di, d, pol, dtype=dtype,
                                      scale=1.0 / di ** 0.5),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: jnp.ndarray | None = None,
                 true_len: jnp.ndarray | None = None
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv along S.  x (B,S,C), w (K,C).  Returns output
    (in x's dtype, accumulated in f32) and the K-1 inputs before position
    `true_len` (B,) (default S): the decode carry."""
    k = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    out = sum(xp[:, i:i + x.shape[1], :].astype(jnp.float32) * wf[i]
              for i in range(k)) + b.astype(jnp.float32)
    if true_len is None:
        new_state = xp[:, xp.shape[1] - (k - 1):, :]
    else:
        # xp[j] is input j - (k-1): inputs true_len-k+1 .. true_len-1
        idx = true_len[:, None] + jnp.arange(k - 1)[None]
        new_state = jnp.take_along_axis(xp, idx[..., None], axis=1)
    return out.astype(x.dtype), new_state


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """a (..., L) -> (..., L, L): sum_{j<i..} with -inf above diagonal."""
    cs = jnp.cumsum(a, axis=-1)
    ss = cs[..., :, None] - cs[..., None, :]
    l = a.shape[-1]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, ss, NEG_INF)


def ssd_chunked(x, dt, a, b_mat, c_mat, chunk: int, s0=None):
    """Chunked SSD.  x (B,S,H,P); dt (B,S,H); a (H,) negative;
    b_mat/c_mat (B,S,N); s0 optional initial state (B,H,P,N).
    Returns y (B,S,H,P) and final state (B,H,P,N)."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    l = chunk
    xc = x.reshape(bsz, nc, l, h, p)
    dtc = dt.reshape(bsz, nc, l, h)
    bc = b_mat.reshape(bsz, nc, l, n)
    cc = c_mat.reshape(bsz, nc, l, n)

    da = dtc * a[None, None, None, :]                 # (B,C,L,H)  log-decay
    da_h = da.transpose(0, 3, 1, 2)                   # (B,H,C,L)
    da_cum = jnp.cumsum(da_h, axis=-1)                # (B,H,C,L)

    # intra-chunk (diagonal blocks)
    lmat = jnp.exp(_segsum(da_h))                     # (B,H,C,L,L)
    xdt = xc * dtc[..., None]                         # input scaled by dt
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp",
                        cc, bc, lmat, xdt)

    # chunk states
    decay_states = jnp.exp(da_cum[..., -1:] - da_cum)  # (B,H,C,L)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", bc, decay_states, xdt)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(da_cum[..., -1])             # (B,H,C)

    def scan_fn(s_prev, inp):
        st, dec = inp                                  # (B,H,P,N), (B,H)
        s_new = s_prev * dec[..., None, None] + st
        return s_new, s_prev                           # emit state BEFORE chunk

    states_t = states.transpose(1, 0, 2, 3, 4)         # (C,B,H,P,N)
    decay_t = chunk_decay.transpose(2, 0, 1)           # (C,B,H)
    if s0 is None:
        s0 = jnp.zeros((bsz, h, p, n), x.dtype)
    s_final, s_before = jax.lax.scan(scan_fn, s0, (states_t, decay_t))
    s_before = s_before.transpose(1, 0, 2, 3, 4)       # (B,C,H,P,N)

    # contribution of the carried-in state to each position
    state_decay = jnp.exp(da_cum)                      # (B,H,C,L)
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", cc, s_before, state_decay)

    y = (y_diag + y_off).reshape(bsz, nc * l, h, p)
    return y[:, :s], s_final


def mamba2(params: dict, u: jnp.ndarray, cfg: ModelCfg, pol,
           state: dict | None = None,
           key: jax.Array | None = None,
           true_len: jnp.ndarray | None = None
           ) -> tuple[jnp.ndarray, dict | None]:
    """u (B,S,d) -> (y, new_state).  state={'conv':..., 'ssm':...} enables
    O(1)-per-token decode (S must be 1 in that case), or, with S > 1, a
    prefill into it; `true_len` (B,) then stops the state at each row's
    true length (the rows are right-padded)."""
    di, nh, hp, ns, dc = dims(cfg)
    b, s, _ = u.shape
    k1, k2 = (common.fold_key(key, i) for i in range(2))

    zxbcdt = common.dense(params["in_proj"], u, pol, k1)
    z, xbc, dt_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * ns], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    if true_len is not None:
        dt = jnp.where((jnp.arange(s)[None] < true_len[:, None])[..., None],
                       dt, 0.0)

    conv_state = state["conv"] if state is not None else None
    xbc_c, new_conv = _causal_conv(xbc, params["conv_w"], params["conv_b"],
                                   conv_state, true_len)
    xbc_c = jax.nn.silu(xbc_c)
    x, b_mat, c_mat = jnp.split(xbc_c, [di, di + ns], axis=-1)
    xh = x.reshape(b, s, nh, hp).astype(jnp.float32)
    b_f = b_mat.astype(jnp.float32)
    c_f = c_mat.astype(jnp.float32)

    if state is None:
        y, s_final = ssd_chunked(xh, dt, a, b_f, c_f, cfg.ssm.chunk)
        new_state = None
    elif s > 1:
        # prefill into a decode state: chunked SSD seeded with the carry
        y, s_final = ssd_chunked(xh, dt, a, b_f, c_f, cfg.ssm.chunk,
                                 s0=state["ssm"].astype(jnp.float32))
        new_state = {"conv": new_conv.astype(state["conv"].dtype),
                     "ssm": s_final.astype(state["ssm"].dtype)}
    else:
        # single-step recurrence
        s_prev = state["ssm"].astype(jnp.float32)          # (B,H,P,N)
        dt1 = dt[:, 0]                                     # (B,H)
        dec = jnp.exp(dt1 * a[None, :])                    # (B,H)
        xdt = xh[:, 0] * dt1[..., None]                    # (B,H,P)
        s_new = (s_prev * dec[..., None, None]
                 + jnp.einsum("bhp,bn->bhpn", xdt, b_f[:, 0]))
        y = jnp.einsum("bn,bhpn->bhp", c_f[:, 0], s_new)[:, None]
        y = y.reshape(b, 1, nh, hp)
        s_final = s_new
        new_state = {"conv": new_conv.astype(state["conv"].dtype),
                     "ssm": s_final.astype(state["ssm"].dtype)}

    y = y + params["d_skip"].astype(jnp.float32)[None, None, :, None] * xh
    # the gated norm in f32, as the SSM output is: rounding y to the
    # activations' dtype first is amplified where the gated rows are small
    g = y.reshape(b, s, di) * jax.nn.silu(z.astype(jnp.float32))
    y = common.rmsnorm(params["norm"], g, cfg.rms_eps).astype(u.dtype)
    out = common.dense(params["out_proj"], y, pol, k2)
    if state is None:
        new_state = None
    return out, new_state


def init_state(b: int, cfg: ModelCfg, conv_dtype=jnp.float32) -> dict:
    """Zero state of b rows: the conv window in `conv_dtype` (that of the
    activations it holds), the SSM state in f32."""
    di, nh, hp, ns, dc = dims(cfg)
    return {"conv": jnp.zeros((b, dc - 1, di + 2 * ns), conv_dtype),
            "ssm": jnp.zeros((b, nh, hp, ns), jnp.float32)}
