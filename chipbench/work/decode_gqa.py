"""Work of the decode_gqa kernel: one query token per row against that
row's true KV length, every query head.  Bytes: the row's K and V
(bf16) and its query and output; operations: QK^T and PV, 4 * Hq * D
per cached token.  One call per attention layer; the sizes are the
configuration's family's (`refs.<model_type>.dims`)."""
from __future__ import annotations

import refs


def work(cfg: dict, kv_lens) -> tuple:
    """(operations, bytes) of one call (one layer) over the rows."""
    d = refs.load(cfg).dims(cfg)
    hq, hkv, hd = d.heads, d.kv_heads, d.head_dim
    ops = sum(4 * hq * hd * kv for kv in kv_lens)
    nbytes = sum(2 * kv * hkv * hd * 2 + 2 * hq * hd * 2 for kv in kv_lens)
    return ops, nbytes


def least_time(cfg: dict, kv_lens, peaks: dict) -> tuple:
    """(seconds, share set by bytes) of one step's calls, all layers."""
    ops, nbytes = work(cfg, kv_lens)
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t = refs.load(cfg).dims(cfg).layers * max(t_ops, t_bytes)
    return t, 1.0 if t_bytes >= t_ops else 0.0
