"""granite-4.0-h-micro [hybrid]: Mamba2 + GQA attention, no positional
encoding (hf:ibm-granite/granite-4.0-h-micro config.json).

40L d_model=2048: 36 Mamba2 mixers (64 heads of 64, d_state 128, one B/C
group, conv 4 with bias, expand 2, chunk 256) and 4 GQA attention layers
(32 query / 8 KV heads of 64) at layer_types indices 5, 15, 25, 35; a
SwiGLU MLP of width 8192 in every layer; vocab 100352, tied embeddings.
Multipliers: embedding x12, residual x0.22, softmax scale 1/64, logits
/8.  The served model the Granite-4.0-H family builds on.
"""
from repro.configs.base import ArchConfig, ModelCfg, SSMCfg

_ATTN_AT = (5, 15, 25, 35)
_PATTERN = tuple("attn" if i in _ATTN_AT else "mamba2" for i in range(40))

CONFIG = ArchConfig(model=ModelCfg(
    name="granite-4.0-h-micro", n_layers=40, d_model=2048, n_heads=32,
    n_kv_heads=8, d_ff=8192, vocab=100352, rope=False, rms_eps=1e-5,
    tie_embeddings=True, embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attention_multiplier=0.015625,
    ssm=SSMCfg(d_state=128, d_conv=4, expand=2, head_dim=64, chunk=256),
    layer_pattern=_PATTERN))


def smoke() -> ArchConfig:
    """4 layers (mamba, attn, mamba, mamba) at width 64, the published
    multipliers."""
    pat = ("mamba2", "attn", "mamba2", "mamba2")
    return ArchConfig(model=ModelCfg(
        name="granite-4.0-h-micro-smoke", n_layers=4, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=128, rope=False, tie_embeddings=True,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, attention_multiplier=0.015625,
        ssm=SSMCfg(d_state=16, d_conv=4, expand=2, head_dim=16, chunk=16),
        layer_pattern=pat))
