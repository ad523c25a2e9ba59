"""Pallas kernel: fused LSQ fake-quantization (scale-div / round / clip /
rescale) — one VMEM pass instead of four HLO elementwise ops; used on the
activation path where the TD simulator quantizes every matmul input.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _kernel(x_ref, s_ref, o_ref, *, qn: float, qp: float):
    s = jnp.maximum(s_ref[0], 1e-8)
    x = x_ref[...]
    o_ref[...] = jnp.clip(jnp.round(x / s), qn, qp) * s


def lsq_quant_pallas(x: jnp.ndarray, s: jnp.ndarray, *, qn: float, qp: float,
                     bm: int = 1024,
                     interpret: bool | None = None) -> jnp.ndarray:
    return _lsq_quant_call(x, s, qn=qn, qp=qp, bm=bm,
                           interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("qn", "qp", "bm", "interpret"))
def _lsq_quant_call(x: jnp.ndarray, s: jnp.ndarray, *, qn: float, qp: float,
                    bm: int, interpret: bool) -> jnp.ndarray:
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    n_pad = -(-n // bm) * bm
    flat = jnp.pad(flat, (0, n_pad - n))
    out = pl.pallas_call(
        functools.partial(_kernel, qn=qn, qp=qp),
        grid=(n_pad // bm,),
        in_specs=[pl.BlockSpec((bm,), lambda i: (i,)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((bm,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), x.dtype),
        interpret=interpret,
    )(flat, jnp.reshape(s, (1,)))
    return out[:n].reshape(shape)
