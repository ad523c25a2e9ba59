"""Three-term roofline from the compiled dry-run artifact.

TPU v5e-class hardware constants (per chip):
  peak 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

  compute_s    = HLO_FLOPs   / (chips * PEAK_FLOPS)
  memory_s     = HLO_bytes   / (chips * HBM_BW)
  collective_s = coll_bytes  / (chips * LINK_BW)

cost_analysis() on the SPMD-partitioned module is per-device; we detect
which convention we got by comparing against the analytic MODEL_FLOPS and
normalize to PER-CHIP seconds.
"""
from __future__ import annotations

import dataclasses

import jax

PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # B/s per chip
LINK_BW = 50e9             # B/s per ICI link
N_LINKS = 4                # usable links per chip on the 2D torus


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float           # per chip
    hlo_bytes: float           # per chip
    coll_bytes: float          # per chip (link-model)
    model_flops: float         # 6*N*D (global, fwd+bwd) or serve analogue
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs * chips): remat/dispatch waste shows up
        as a ratio below 1."""
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs utilization at the roofline step time."""
        denom = self.step_s * self.chips * PEAK_FLOPS
        return self.model_flops / denom if denom else 0.0


def make_roofline(arch: str, shape: str, mesh: str, chips: int,
                  flops_total: float, bytes_total: float,
                  coll_link_bytes_total: float,
                  model_flops: float) -> Roofline:
    """totals are whole-program (all chips); divide down to per-chip."""
    f = flops_total / chips
    b = bytes_total / chips
    c = coll_link_bytes_total / chips
    return Roofline(
        arch=arch, shape=shape, mesh=mesh, chips=chips,
        hlo_flops=f, hlo_bytes=b, coll_bytes=c, model_flops=model_flops,
        compute_s=f / PEAK_FLOPS,
        memory_s=b / HBM_BW,
        collective_s=c / (LINK_BW * N_LINKS),
    )


@dataclasses.dataclass(frozen=True)
class KVCachePlan:
    """Block-granular KV-cache sizing for the slot-batched serve engine.

    Slots are contiguous per request but sized in `block`-token blocks
    against a memory budget (a fraction of the device's limit net of the
    resident weights), so the engine's fixed capacity is derived rather
    than guessed.  `max_slots` is how many slots of `s_cache` tokens the
    budget admits; `fits` says whether the REQUESTED capacity does.
    """
    capacity: int              # requested concurrent slots
    s_cache: int               # tokens per slot, rounded up to blocks
    block: int                 # allocation granularity (tokens)
    bytes_per_slot: int
    bytes_total: int           # capacity * bytes_per_slot
    budget_bytes: int | None   # None: the device reports no limit
    max_slots: int

    @property
    def fits(self) -> bool:
        return self.capacity <= self.max_slots


def device_bytes_limit(device=None) -> int | None:
    """The device's own memory limit (`memory_stats()["bytes_limit"]`);
    None where the backend reports none (the CPU)."""
    device = device or jax.local_devices()[0]
    stats = device.memory_stats()
    return int(stats["bytes_limit"]) if stats and "bytes_limit" in stats \
        else None


def tree_bytes(tree) -> int:
    """Bytes held by a pytree of arrays (the weights' real footprint)."""
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree))


def plan_kv_cache(cfg, capacity: int, s_cache: int, *, block: int = 128,
                  dtype_bytes: int = 2, weight_bytes: float = 0.0,
                  budget_frac: float = 0.9,
                  hbm_bytes: float | None = None) -> KVCachePlan:
    """Size the serve engine's KV slots against the device memory.

    cfg: a ModelCfg (uses n_layers/mixer pattern/n_kv_heads/hd).  The
    budget is `budget_frac` of (hbm_bytes - weight_bytes), with
    `hbm_bytes` the device's limit (`device_bytes_limit`); None (no limit
    reported) leaves the requested capacity uncapped.  Per-slot bytes are
    K+V per attention layer at `dtype_bytes` per element, with the
    sequence rounded up to `block`-token blocks.
    """
    n_attn = sum(1 for i in range(cfg.n_layers)
                 if cfg.mixer_at(i) in ("attn", "shared_attn"))
    blocks = max(1, -(-s_cache // block))
    s_pad = blocks * block
    per_slot = 2 * n_attn * s_pad * cfg.n_kv_heads * cfg.hd * dtype_bytes
    if hbm_bytes is None:
        budget, max_slots = None, capacity
    else:
        budget = int(max(0.0, hbm_bytes - weight_bytes) * budget_frac)
        max_slots = budget // per_slot if per_slot else 0
    return KVCachePlan(capacity=capacity, s_cache=s_pad, block=block,
                       bytes_per_slot=per_slot,
                       bytes_total=capacity * per_slot,
                       budget_bytes=budget, max_slots=max_slots)


def model_flops_train(n_params: float, tokens: float) -> float:
    return 6.0 * n_params * tokens


def model_flops_serve(n_params_active: float, tokens: float) -> float:
    return 2.0 * n_params_active * tokens
