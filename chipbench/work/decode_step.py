"""Least bytes of one decode step of the serving engine: every matmul
weight once (bf16), each occupied row's true K and V in every attention
layer (bf16), and each occupied row's recurrent state read and written
(the family's `state_bytes`, where it has one).  The sizes are the
configuration's family's (`refs.<model_type>`)."""
from __future__ import annotations

import refs


def state_bytes(cfg: dict) -> int:
    """One slot's recurrent state; 0 for a family without one."""
    fam = refs.load(cfg)
    return fam.state_bytes(cfg) if hasattr(fam, "state_bytes") else 0


def least_bytes(cfg: dict, kv_lens) -> int:
    """Bytes one step over the occupied rows (each with its KV length)
    must move at the least."""
    d = refs.load(cfg).dims(cfg)
    kv = sum(2 * d.layers * n * d.kv_heads * d.head_dim * 2 for n in kv_lens)
    return 2 * d.matmul_params + kv + 2 * len(kv_lens) * state_bytes(cfg)


def least_time(cfg: dict, kv_lens, peaks: dict) -> float:
    """Seconds of that step at the chip's HBM peak."""
    return least_bytes(cfg, kv_lens) / peaks["hbm_bytes_per_s"]
