"""Pallas TPU kernels for the perf-critical compute layers.

td_vmm       bit-serial noisy TD-VMM — the production TD execution engine:
             fused offset/plane/TDC/correction tiles, runtime sigma & tdc_q
             scalar operands (traced-sigma sweeps run one compiled program)
lsq_quant    fused LSQ fake-quantization (VPU)
decode_gqa   fused flash-decode GQA attention: block-tiled online softmax,
             runtime SMEM lengths (one compiled program per shape)
flash_attn   fused online-softmax flash forward (no materialized (Sq, Skv)
             scores), runtime kv_len/q_offset SMEM operands, custom_vjp
             recompute backward
common       the one interpret policy (`resolve_interpret`), the SMEM
             scalar space and the lane width shared by all four

Each package ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper) and ref.py (pure-jnp oracle).  On a TPU backend every kernel
compiles, and interpret mode is refused there; on any other backend (CPU
tests) every kernel runs in the Pallas interpreter with the same tiling.
tests/test_tpu_compile.py compiles the main-path kernels at real widths
for a described v5e.  The model path has no unfused fallback (CI greps it
stays that way).
"""
