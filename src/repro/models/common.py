"""Shared model building blocks: norms, RoPE, embeddings, losses, dtype and
TD-policy plumbing.

Parameters are plain nested dicts of jnp arrays.  Every matmul goes through
`dense(...)`, which routes to the TD execution simulator according to the
arch's TDExecCfg — this is how the paper's technique is a first-class
feature of every architecture rather than a bolt-on.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelCfg, TDExecCfg
from repro.tdsim import policy as td_policy
from repro.tdsim import td_linear


# ---------------------------------------------------------------------------
# TD policy resolution (host-side, hashable -> safe as jit constant)
# ---------------------------------------------------------------------------
pol_at = td_policy.pol_at
pol_top = td_policy.pol_top
pol_attn = td_policy.pol_attn


def resolve_policy(td: TDExecCfg) -> td_policy.TDPolicy:
    return resolve_policies([td])[0]


def resolve_policies(tds, scenario=None, corner=None
                     ) -> list[td_policy.TDPolicy]:
    """Resolve many layer configs at once: all "td"-mode entries are solved
    by one batched (R, q, sigma) call per weight bit width instead of a
    per-layer scalar solve.  A named `scenario`/`corner` (core.scenario)
    resolves each "td" entry's operating point first: corner-derated error
    budget, grid-argmin supply (`tdsim.policy.apply_scenario`).  A corner
    without a scenario resolves against the default 'vdd-opt' supply grid
    (same rule as the CLI) rather than being silently ignored."""
    if corner is not None and scenario is None:
        scenario = "vdd-opt"
    out: list[td_policy.TDPolicy | None] = [None] * len(tds)
    td_specs, td_idx = [], []
    for i, td in enumerate(tds):
        if td.mode == "precise":
            out[i] = td_policy.PRECISE
        elif td.mode == "quant":
            out[i] = td_policy.quant_policy(td.bits_a, td.bits_w)
        elif td.mode == "td":
            td_specs.append(td_policy.TDLayerSpec(
                td.bits_a, td.bits_w, td.n_chain, td.sigma_max))
            td_idx.append(i)
        else:
            raise ValueError(f"unknown td mode {td.mode!r}")
    if scenario is not None and td_specs:
        td_specs = td_policy.apply_scenario(td_specs, scenario, corner)
    for i, pol in zip(td_idx, td_policy.solve_td_policies(td_specs)):
        out[i] = pol
    return out  # type: ignore[return-value]


def resolve_arch_policy(arch) -> td_policy.TDPolicy | td_policy.NetworkPolicy:
    """Resolve an ArchConfig's execution policy in one shot.

    Homogeneous (`td_per_layer is None`) -> a single TDPolicy as before.
    Heterogeneous -> every per-layer TDExecCfg plus the top-level `td` go
    through ONE `resolve_policies` call (batched (R, q, sigma) solve per
    distinct weight bit width) and come back as a NetworkPolicy.
    `arch.scenario`/`arch.corner` resolve every "td"-mode matmul's
    operating point for that named scenario/corner.

    `arch.td_attn` (when set to a non-precise TDExecCfg) additionally
    resolves one policy PER QUERY HEAD for the attention engine — the
    chain length clamps to the head dim (the QK contraction) and the
    per-head (R, q, sigma) solve goes through the same batched call and
    scenario/corner resolution as the layer policies — and attaches them
    as `NetworkPolicy.attn` (promoting a homogeneous policy to a
    NetworkPolicy if needed).  Decoder-family only, like `td_per_layer`.
    """
    sc, co = getattr(arch, "scenario", None), getattr(arch, "corner", None)
    if arch.td_per_layer is None:
        base = resolve_policies([arch.td], scenario=sc, corner=co)[0]
    else:
        if arch.model.family != "decoder":
            raise ValueError("per-layer TD policies require a decoder-family "
                             f"model, got {arch.model.family!r}")
        n_layers = arch.model.n_layers
        if len(arch.td_per_layer) != n_layers:
            raise ValueError(
                f"td_per_layer has {len(arch.td_per_layer)} entries for "
                f"{n_layers}-layer model {arch.model.name!r}")
        pols = resolve_policies(list(arch.td_per_layer) + [arch.td],
                                scenario=sc, corner=co)
        base = td_policy.NetworkPolicy(layers=tuple(pols[:-1]), top=pols[-1])

    td_attn = getattr(arch, "td_attn", None)
    if td_attn is not None and td_attn.mode != "precise":
        if arch.model.family != "decoder":
            raise ValueError("td_attn requires a decoder-family model, "
                             f"got {arch.model.family!r}")
        spec = dataclasses.replace(
            td_attn, n_chain=min(td_attn.n_chain, arch.model.hd))
        attn_pols = tuple(resolve_policies([spec] * arch.model.n_heads,
                                           scenario=sc, corner=co))
        if isinstance(base, td_policy.NetworkPolicy):
            base = dataclasses.replace(base, attn=attn_pols)
        else:
            base = td_policy.NetworkPolicy(
                layers=(base,) * arch.model.n_layers, top=base,
                attn=attn_pols)
    return base


def runtime_td_policy(pol, ops: jnp.ndarray):
    """Rebind every "td"-mode layer policy's (sigma_chain, tdc_q) to the
    runtime operand array ``ops`` — the zero-recompile hot-swap hook of the
    drift-adaptive serve step.

    ``ops`` is ``(2,)`` f32 ``[sigma, q]`` applied to every TD layer, or
    ``(L, 2)`` for per-layer operating points.  Both ride into the Pallas
    kernel as traced SMEM operands (`tdsim.td_linear`), so feeding a new
    ``ops`` value re-runs the SAME compiled program at the new operating
    point.  Non-"td" policies (precise/quant) pass through untouched; a
    NetworkPolicy's `top`/`attn` are left as solved (the hot path the
    drift loop re-resolves is the per-layer matmuls)."""
    ops = jnp.asarray(ops, jnp.float32)

    def bind(p: td_policy.TDPolicy, row) -> td_policy.TDPolicy:
        if p.mode != "td":
            return p
        return p.replace(sigma_chain=row[0], tdc_q=row[1])

    if isinstance(pol, td_policy.NetworkPolicy):
        rows = [ops[i] if ops.ndim == 2 else ops for i in range(len(pol))]
        return dataclasses.replace(
            pol, layers=tuple(bind(p, r)
                              for p, r in zip(pol.layers, rows)))
    return bind(pol, ops[0] if ops.ndim == 2 else ops)


def td_policy_ops(pol) -> jnp.ndarray:
    """The ``(L, 2)`` (or ``(2,)`` for a plain policy) runtime operand
    array of a SOLVED policy — the value `runtime_td_policy` rebinds."""
    if isinstance(pol, td_policy.NetworkPolicy):
        return jnp.asarray([[p.sigma_chain, p.tdc_q] for p in pol.layers],
                           jnp.float32)
    return jnp.asarray([pol.sigma_chain, pol.tdc_q], jnp.float32)


def td_layer_indices(pol) -> list[int]:
    """Indices of the "td"-mode layer policies of ``pol`` (all the layers
    the drift loop re-resolves; a plain TDPolicy is layer 0 or nothing)."""
    if isinstance(pol, td_policy.NetworkPolicy):
        return [i for i, p in enumerate(pol.layers) if p.mode == "td"]
    return [0] if pol.mode == "td" else []


def replace_td_layers(pol, solved):
    """Rebuild ``pol`` with its "td"-mode layers replaced by ``solved``
    (one new TDPolicy per `td_layer_indices` entry, in order); `top`/
    `attn` and non-td layers pass through untouched.  The drift loop's
    policy-set rebuild — used by both the synchronous (sigma, q) hot-swap
    and the staged supply swap."""
    idx = td_layer_indices(pol)
    solved = list(solved)
    if len(solved) != len(idx):
        raise ValueError(f"need {len(idx)} solved td layers, "
                         f"got {len(solved)}")
    if not idx:
        return pol
    if isinstance(pol, td_policy.NetworkPolicy):
        layers = list(pol.layers)
        for i, p in zip(idx, solved):
            layers[i] = p
        return dataclasses.replace(pol, layers=tuple(layers))
    return solved[0]


# ---------------------------------------------------------------------------
# Sharding constraints (no-ops outside a mesh context)
# ---------------------------------------------------------------------------
def _auto_axes(env) -> set:
    """Axis names of the ambient mesh that the compiler partitions (inside
    a `shard_map` body the mesh axes are Manual and take no constraint)."""
    return {n for n, t in zip(env.axis_names, env.axis_types)
            if t == jax.sharding.AxisType.Auto}


def maybe_constrain(x: jnp.ndarray, *axes) -> jnp.ndarray:
    """with_sharding_constraint(x, P(*axes)) if a global mesh providing all
    referenced axis names is active; otherwise identity.  Lets model code
    carry distribution hints without coupling tests to a mesh."""
    env = jax.sharding.get_abstract_mesh()
    names = _auto_axes(env)
    if not names:
        return x

    def ok(a):
        if a is None:
            return True
        if isinstance(a, (tuple, list)):
            return all(n in names for n in a)
        return a in names

    if not all(ok(a) for a in axes):
        return x
    # drop axes that do not divide the dim
    fixed = []
    for dim, a in zip(x.shape, axes):
        if a is None:
            fixed.append(None)
            continue
        ax = (a,) if isinstance(a, str) else tuple(a)
        n = 1
        for nm in ax:
            n *= env.shape[nm]
        fixed.append(a if dim % n == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*fixed))


def batch_sharding_axes(env=None):
    names = _auto_axes(env or jax.sharding.get_abstract_mesh())
    if "data" not in names:
        return None
    return ("pod", "data") if "pod" in names else "data"


# ---------------------------------------------------------------------------
# Initializers / dense layer
# ---------------------------------------------------------------------------
def dense_init(key: jax.Array, d_in: int, d_out: int, pol,
               bias: bool = False, dtype=jnp.float32,
               scale: float | None = None) -> dict:
    return td_linear.init_linear(key, d_in, d_out, pol, bias, dtype, scale)


def dense(params: dict, x: jnp.ndarray, pol, key=None) -> jnp.ndarray:
    return td_linear.linear(params, x, pol, key)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                         # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, Dh/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., S, 1, Dh/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head / losses
# ---------------------------------------------------------------------------
def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> dict:
    return {"table": jax.random.normal(key, (vocab, d), dtype) * 0.02}


def embed(params: dict, ids: jnp.ndarray) -> jnp.ndarray:
    return params["table"][ids]


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: jnp.ndarray | None = None,
                  z_coef: float = 1e-4) -> jnp.ndarray:
    """Mean next-token CE with z-loss; logits (..., V), labels (...)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    z = z_coef * lse ** 2
    loss = nll + z
    if mask is not None:
        loss = loss * mask
        return loss.sum() / jnp.maximum(mask.sum(), 1.0)
    return loss.mean()


def fold_key(key: jax.Array | None, *idx: int) -> jax.Array | None:
    if key is None:
        return None
    for i in idx:
        key = jax.random.fold_in(key, i)
    return key


def cast_tree(tree, dtype):
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)
