"""Digital adder-tree VMM reference model (paper Section IV).

The paper obtains digital numbers from post-layout simulation of single-cycle
VMM arrays synthesized at 1 GHz in the same 22 nm technology (TT corner),
dividing total array energy by array length N to get the per-MAC average.
Weights are fully (bit-)serialized like the TD implementation.

We model the same structure analytically: a 1xB AND-stage feeding a binary
adder tree with N leaves.  Level k of the tree has N/2^k adders of width
~ B + k, so the per-MAC adder-bit count is sum_k (B + k)/2^k ~ B + 2 + o(1).
Digital computation is exact: no R, no SNR dependence (its energy is flat in
the accuracy-relaxation axis -- which is exactly why TD/analog overtake it
once the error budget is relaxed, Fig. 11).

Entry points are array-polymorphic: python scalars keep the original float
math, arrays broadcast elementwise (closed-form partial sums replace the
per-point tree-depth loop).  Synthesis energies/areas come from a
`core.techlib.TechLib` (``lib=`` keyword, default bit-identical to the
historical constants).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.core import constants as C
from repro.core import tdc
from repro.core.techlib import DEFAULT_LIB, TechLib


def _is_scalar(*xs) -> bool:
    return all(isinstance(x, (int, float)) for x in xs)


def _adder_bits_per_mac(n, bits: int):
    """sum_{k=1..d} (B + k) / 2^k with d = ceil(log2 N), exact partial sum:
    B (1 - 2^-d) + 2 - (d + 2) 2^-d."""
    if _is_scalar(n):
        depth = max(1, int(math.ceil(math.log2(max(2.0, n)))))
        total = 0.0
        for k in range(1, depth + 1):
            total += (bits + k) / 2.0 ** k
        return total
    nf = jnp.maximum(2.0, jnp.asarray(n, jnp.float32))
    depth = jnp.maximum(1.0, tdc.ceil_log2(nf))
    inv = 2.0 ** (-depth)
    return bits * (1.0 - inv) + 2.0 - (depth + 2.0) * inv


def digital_energy_per_mac(n, bits: int, vdd=C.VDD_NOM,
                           p_x_one=C.P_X_ONE,
                           w_bit_sparsity=C.W_BIT_SPARSITY,
                           lib: TechLib = DEFAULT_LIB):
    """Per-MAC energy of the single-cycle N-long 1xB VMM array.

    `lib.alpha_sw_digital` was synthesized at the paper's Section IV input
    statistics (p_x_one = 0.5, 70 % weight-bit sparsity); other statistics
    rescale the switching activity proportionally to the active-bit
    probability p_x_one * (1 - w_bit_sparsity), so the defaults reproduce
    the constant exactly."""
    act = p_x_one * (1.0 - w_bit_sparsity)
    act_base = C.P_X_ONE * (1.0 - C.W_BIT_SPARSITY)
    alpha_sw = lib.alpha_sw_digital * act / act_base
    scale = (vdd / C.VDD_NOM) ** 2
    e_adder = _adder_bits_per_mac(n, bits) * lib.e_fa_bit * alpha_sw
    e_and = bits * lib.e_and_gate_bit * alpha_sw          # AND gating stage
    if _is_scalar(n):
        log2n = math.log2(max(2.0, n))
    else:
        log2n = jnp.log2(jnp.maximum(2.0, jnp.asarray(n, jnp.float32)))
    e_wire = log2n * lib.e_wire_per_log2n
    e = (e_adder + e_and + e_wire) * scale + lib.e_seq_mac * scale
    return e * (1.0 + lib.leakage_fraction)


def digital_throughput(n, bits: int, m=C.M_DEFAULT,
                       lib: TechLib = DEFAULT_LIB):
    """Single-cycle array at f_dig: N*M MACs retire per cycle."""
    return n * m * lib.f_dig


def digital_area(n, bits: int, lib: TechLib = DEFAULT_LIB):
    """Per-MAC area after P&R: AND stage + amortized adder tree + seq."""
    a_adder = _adder_bits_per_mac(n, bits) * lib.a_fa_bit
    a_and = bits * 0.30e-12
    return a_adder + a_and + lib.a_seq_mac
