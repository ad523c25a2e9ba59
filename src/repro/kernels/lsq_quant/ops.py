"""Jit'd wrapper for the fused LSQ fake-quant kernel (forward only; the
training path attaches the LSQ custom_vjp from repro.quant.lsq)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.lsq_quant.lsq_quant import lsq_quant_pallas
from repro.quant.lsq import qrange


def lsq_quant(x: jnp.ndarray, s: jnp.ndarray, bits: int, signed: bool,
              interpret: bool | None = None) -> jnp.ndarray:
    qn, qp = qrange(bits, signed)
    return lsq_quant_pallas(x, s, qn=float(qn), qp=float(qp),
                            interpret=interpret)
