"""Shared plumbing for the Pallas kernels (td_vmm, flash_attn, decode_gqa,
lsq_quant): the one interpret policy, the scalar-operand memory space and
the TPU lane width.

Interpret policy: a kernel compiles on a TPU backend and runs in the Pallas
interpreter everywhere else (CPU tests, `JAX_PLATFORMS=cpu`).  There is no
override: ``interpret=None`` resolves to the backend's mode, an explicit
``interpret=False`` compiles (the AOT compile tests lower for a described
TPU from a CPU process), and an explicit ``interpret=True`` on a TPU
backend is refused, so no path can silently run a kernel interpreted on
the chip.  Each kernel uses the same tiling in both modes.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

SCALAR_SPACE = pltpu.SMEM
NEG_INF = -1e30
LANES = 128                      # TPU vector lane width (last-dim tile)


def default_interpret() -> bool:
    """True off a TPU backend (the kernels run in the interpreter there)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> the backend's mode; ``True`` on a TPU backend raises."""
    if interpret is None:
        return default_interpret()
    if interpret and not default_interpret():
        raise ValueError("Pallas interpret mode is refused on a TPU backend:"
                         " kernels compile there")
    return bool(interpret)


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m
