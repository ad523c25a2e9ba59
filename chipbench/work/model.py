"""Model FLOPs of a decode step (the serving formula of the program's
`roofline.model.model_flops_serve`, 2 x parameters x tokens, plus the
attention over each row's true KV length), with the sizes the
configuration's family gives (`refs.<model_type>.dims`)."""
from __future__ import annotations

import refs


def model_flops_serve(n_params_active: float, tokens: float) -> float:
    return 2.0 * n_params_active * tokens


def matmul_params(cfg: dict) -> int:
    """Weights that multiply each token."""
    return refs.load(cfg).dims(cfg).matmul_params


def attention_flops(cfg: dict, kv_len: int) -> int:
    """QK^T and PV of one query token against kv_len keys, all layers."""
    d = refs.load(cfg).dims(cfg)
    return d.layers * 4 * d.heads * d.head_dim * kv_len


def decode_flops(cfg: dict, kv_lens) -> float:
    """One decode step over the occupied rows, each with its KV length."""
    return sum(model_flops_serve(matmul_params(cfg), 1)
               + attention_flops(cfg, kv) for kv in kv_lens)
