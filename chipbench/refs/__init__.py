"""Each configuration's family: one module per published `model_type`,
`chipbench/refs/<model_type>.py`, found by the `model_type` key that the
published config already carries.  A family module brings what the
harness would otherwise have to know of one architecture:

    make_weights(cfg, seed_words)
        the whole model's weights from two 32-bit seed words, on the
        device, in the tree the serving engine takes
    Reference(cfg, t_pad, n_rows).logits(params, toks, lo, hi, lowp=False)
        the plain reference the served tokens are checked against, and
        with `lowp` its control
    stated(model_cfg) -> {published key: the program's value}
        what the harness holds the program to before a run
    dims(cfg) -> Dims
        the sizes the work counts (`chipbench/work/`) read

A new architecture is added as new files only: its family module here,
its configuration, its traffic and its metric readers.  Pieces that any
decoder reuses are in `refs/common.py`.
"""
from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration that the work counts read.
    `matmul_params` are the weights that multiply each token: every
    layer's projections and the LM head; for a mixture of experts the
    routed top-k, not all of them."""
    layers: int          # attention layers
    heads: int           # query heads
    kv_heads: int
    head_dim: int
    matmul_params: int


def load(cfg: dict):
    """The family module of `cfg["model_type"]`; an unknown type is an
    error that names the missing file."""
    name = cfg["model_type"]
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ModuleNotFoundError(
            f"no family for model_type {name!r}: chipbench/refs/{name}.py "
            f"is missing", name=module) from None


def at(cfg: dict, key: str):
    """The configuration's value of a published key; a dotted key names
    one inside a nested group (`attn_config.kv_n_heads`)."""
    for part in key.split("."):
        cfg = cfg[part]
    return cfg
