"""Declarative sharding rules: parameter-path regex -> PartitionSpec.

2D strategy (MaxText-style): the contraction/model-width dim of every large
matrix is sharded over 'data' (FSDP storage sharding, ZeRO-3 dataflow under
pjit) and the parallel dim over 'model' (tensor parallelism).  Experts shard
over 'model' (EP).  Vectors/norms/scalars replicate.

All rules are validated against divisibility at spec-construction time; a
dim that does not divide its mesh axes falls back to replication on that
dim (correct, just less sharded) — this keeps every (arch x mesh) cell
compilable by construction.
"""
from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import dp_axes


# (regex on "/"-joined path, spec template)
# DP = FSDP/storage axis, TP = tensor axis; templates use the strings and
# are resolved per-mesh.
_RULES: list[tuple[str, tuple]] = [
    # embeddings / heads
    (r"embed/table$",                       ("TP", "DP")),
    (r"lm_head/w$",                         ("DP", "TP")),
    (r"adapter/w$",                         (None, "TP")),
    # attention (order matters: chanmix/timemix wv|wk|wr before attn generic)
    (r"chanmix/wk/w$",                      ("DP", "TP")),
    (r"chanmix/wv/w$",                      ("TP", "DP")),
    (r"chanmix/wr/w$",                      ("DP", "TP")),
    (r"timemix/w[rkvg]/w$",                 ("DP", "TP")),
    (r"timemix/wo/w$",                      ("TP", "DP")),
    (r"(attn|xattn|shared_attn)/w[qkv]/w$", ("DP", "TP")),
    (r"(attn|xattn|shared_attn)/w[qkv]/b$", ("TP",)),
    (r"(attn|xattn|shared_attn)/wo/w$",     ("TP", "DP")),
    # dense mlp
    (r"mlp/w[ig]/w$",                       ("DP", "TP")),
    (r"mlp/wo/w$",                          ("TP", "DP")),
    # MoE: experts over TP (EP), contraction over DP
    (r"moe/w[ig]$",                         ("TP", "DP", None)),
    (r"moe/wo$",                            ("TP", None, "DP")),
    (r"moe/router/w$",                      (None, None)),
    # mamba2
    (r"mamba/in_proj/w$",                   ("DP", None)),
    (r"mamba/out_proj/w$",                  ("TP", "DP")),
]


def _resolve(template: tuple, shape: tuple, mesh) -> P:
    """Template -> PartitionSpec with divisibility fallback.  Right-aligned:
    stacked (scan-over-layers) params carry an extra leading layer dim that
    stays unsharded."""
    dp = dp_axes(mesh)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    tp_n = mesh.shape["model"]
    extra = len(shape) - len(template)
    parts = [None] * extra
    for dim, t in zip(shape[extra:], template):
        if t == "DP" and dim % dp_n == 0:
            parts.append(dp if len(dp) > 1 else dp[0])
        elif t == "TP" and dim % tp_n == 0:
            parts.append("model")
        else:
            parts.append(None)
    return P(*parts)


def param_specs(params, mesh, serving: bool = False) -> object:
    """Pytree of PartitionSpec matching `params`.

    serving=True drops the FSDP ('data') storage sharding so weights are
    not re-all-gathered every decode step (§Perf A3): inference has no
    optimizer state, so the capacity pressure that motivates FSDP is gone
    and the per-step gather traffic dominates instead.
    """
    def spec_of(path_keys, leaf):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path_keys)
        for rx, template in _RULES:
            if re.search(rx, path):
                if leaf.ndim not in (len(template), len(template) + 1):
                    return P()
                t = tuple(None if (serving and x == "DP") else x
                          for x in template)
                return _resolve(t, leaf.shape, mesh)
        return P()          # replicate (norms, scalars, small vectors)

    return jax.tree_util.tree_map_with_path(spec_of, params)


def named(specs, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh, batch_size: int, rank: int) -> P:
    """Shard the leading batch dim over (pod, data) when divisible."""
    dp = dp_axes(mesh)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    lead = (dp if len(dp) > 1 else dp[0]) if batch_size % dp_n == 0 else None
    return P(lead, *([None] * (rank - 1)))


def cache_specs(state_shapes, mesh) -> object:
    """PartitionSpecs for a decode-state pytree (KV caches, SSM states).

    KV caches (B, S, H, D): batch over DP when divisible, else the sequence
    dim takes DP (flash-decode style split-K); heads over TP when divisible.
    SSM/wkv states (B, H, ...): heads over TP.
    """
    dp = dp_axes(mesh)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    tp_n = mesh.shape["model"]
    dp_part = dp if len(dp) > 1 else dp[0]

    def spec_of(path_keys, leaf):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path_keys)
        shape = leaf.shape
        if leaf.ndim == 0 or path.endswith("idx"):
            return P()
        if re.search(r"(^|/)(k|v)$", path) and leaf.ndim in (4, 5):
            lead = (None,) if leaf.ndim == 5 else ()   # stacked layer dim
            b, s, h, d = shape[-4:]
            # Heads over TP when they divide; otherwise split-K: sequence
            # over TP (flash-decode style) — a cache replicated across the
            # model axis dominated the decode memory roofline (§Perf A2).
            b_ax = dp_part if b % dp_n == 0 else None
            seq_axes: list = []
            seq_div = 1
            if b_ax is None and s % dp_n == 0:
                seq_axes += list(dp)
                seq_div *= dp_n
            h_ax = "model" if h % tp_n == 0 else None
            if h_ax is None and s % (seq_div * tp_n) == 0:
                seq_axes.append("model")
            s_ax = (None if not seq_axes
                    else seq_axes[0] if len(seq_axes) == 1
                    else tuple(seq_axes))
            return P(*lead, b_ax, s_ax, h_ax, None)
        if re.search(r"(ssm|wkv)$", path):
            lead = (None,) if leaf.ndim in (5,) else ()
            b, h = shape[-4], shape[-3]
            return P(*lead, dp_part if b % dp_n == 0 else None,
                     "model" if h % tp_n == 0 else None)
        if re.search(r"conv$", path) and leaf.ndim in (3, 4):
            lead = (None,) if leaf.ndim == 4 else ()
            b, _, c = shape[-3:]
            return P(*lead, dp_part if b % dp_n == 0 else None, None,
                     "model" if c % tp_n == 0 else None)
        if re.search(r"enc_out$", path) and leaf.ndim == 3:
            b, _, d = shape
            return P(dp_part if b % dp_n == 0 else None, None,
                     "model" if d % tp_n == 0 else None)
        if leaf.ndim >= 1 and shape[0] % dp_n == 0:
            return P(dp_part, *([None] * (leaf.ndim - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(spec_of, state_shapes)
