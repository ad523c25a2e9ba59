"""A family the harness has never seen, for the CPU tests: qwen2's weights,
reference and sizes under DBRX-style published keys (`d_model`,
`n_layers`, `attn_config.kv_n_heads`, ...).  The tests put it in as
`refs.qwen2_renamed`.  A configuration in its keys (`rename`) holds no
published key of qwen2's but `vocab_size`, so the harness raises
`KeyError` wherever it reads another outside the family."""
from __future__ import annotations

import refs
import smoke
from refs import qwen2

MODEL_TYPE = "qwen2_renamed"

# qwen2's published key -> this family's
KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
        "num_attention_heads": "n_heads",
        "num_key_value_heads": "attn_config.kv_n_heads",
        "rope_theta": "attn_config.rope_theta",
        "intermediate_size": "ffn_config.ffn_hidden_size",
        "rms_norm_eps": "norm_config.eps",
        "tie_word_embeddings": "tie_embeddings",
        "vocab_size": "vocab_size"}

# the harness's own keys, which every configuration carries
HARNESS = ("name", "registry", "mode", "check")


def rename(cfg: dict) -> dict:
    """The qwen2 configuration `cfg` in this family's keys, with the
    harness's own keys and nothing else of qwen2's."""
    out = {k: cfg[k] for k in HARNESS}
    out["model_type"] = MODEL_TYPE
    return smoke.assign(out, {new: cfg[old] for old, new in KEYS.items()})


def _qwen2(cfg: dict) -> dict:
    return {"model_type": "qwen2",
            **{old: refs.at(cfg, new) for old, new in KEYS.items()}}


def make_weights(cfg, seed_words):
    return qwen2.make_weights(_qwen2(cfg), seed_words)


def Reference(cfg, t_pad, n_rows):  # noqa: N802 - the interface's name
    return qwen2.Reference(_qwen2(cfg), t_pad, n_rows)


def stated(m) -> dict:
    return {KEYS[k]: v for k, v in qwen2.stated(m).items()}


def dims(cfg):
    return qwen2.dims(_qwen2(cfg))
