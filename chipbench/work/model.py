"""Model FLOPs of a decode step (the serving formula of the program's
`roofline.model.model_flops_serve`, 2 x parameters x tokens, plus the
attention over each row's true KV length)."""
from __future__ import annotations


def model_flops_serve(n_params_active: float, tokens: float) -> float:
    return 2.0 * n_params_active * tokens


def matmul_params(cfg: dict) -> int:
    """Weights that multiply each token: every layer's projections and
    the LM head (the embedding lookup multiplies nothing)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def attention_flops(cfg: dict, kv_len: int) -> int:
    """QK^T and PV of one query token against kv_len keys, all layers."""
    hq = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // hq
    return cfg["num_hidden_layers"] * 4 * hq * hd * kv_len


def decode_flops(cfg: dict, kv_lens) -> float:
    """One decode step over the occupied rows, each with its KV length."""
    return sum(model_flops_serve(matmul_params(cfg), 1)
               + attention_flops(cfg, kv) for kv in kv_lens)
