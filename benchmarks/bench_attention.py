"""Attention engine benchmark: unfused jnp reference vs the fused Pallas
kernels (flash forward + flash-decode), wall-clock and bytes-materialized,
plus a TD-attention accuracy-vs-sigma smoke.

The jnp reference materializes the full (B, Hq, Sq, Skv) score AND
probability tensors in f32 per call; the fused kernels stream (bq, bk)
tiles with online softmax and never write them — the bytes column
quantifies exactly the traffic the fusion removes.

Timing policy (same as bench_td_vmm): the wall-clock gate — compiled
kernels beating the reference — is only *asserted* on a TPU backend where
they actually compile; interpret-mode CPU runs (CI) record the ratio in
the artifact and assert correctness only (kernel/ref parity per shape, and
TD attention reproducing clean attention at sigma=0).

Artifacts under ``artifacts/attention/``:

  * ``bench_attention.csv``   per-shape wall-clock + bytes table
  * ``bench_attention.json``  the same plus the TD-attention sigma sweep
                              and the gate disposition

``REPRO_ATTN_SMOKE=1`` shrinks the sweep for CI.
"""
import csv
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import default_interpret
from repro.kernels.decode_gqa.ops import decode_attention
from repro.kernels.decode_gqa.ref import decode_gqa_ref
from repro.kernels.flash_attn.ops import flash_attention
from repro.kernels.flash_attn.ref import flash_attn_ref
from repro.tdsim import TDPolicy
from repro.tdsim.td_attention import td_attention

OUT_DIR = os.path.join("artifacts", "attention")

#                 B  Hq  Hkv    T    D
FLASH_SHAPES = [(4,  8,   2,  512, 64),
                (2, 16,   4, 1024, 64),
                (1, 32,   8, 2048, 128),
                (8,  8,   8,  256, 64)]    # MHA
FLASH_SHAPES_SMOKE = [(2, 4, 2, 128, 32), (1, 8, 1, 96, 64)]

#                  B  Hq  Hkv     S    D
DECODE_SHAPES = [(16,  8,   2, 2048, 64),
                 (64, 16,   4, 1024, 64),
                 (8,  32,   8, 4096, 128)]
DECODE_SHAPES_SMOKE = [(4, 4, 2, 256, 32)]

TD_SIGMAS = [0.0, 1.0, 4.0]


def _smoke() -> bool:
    return os.environ.get("REPRO_ATTN_SMOKE", "").strip() in ("1", "true")


def _timed(fn, *args, iters: int = 10) -> float:
    """Median wall-clock seconds of a jitted call (post-warmup)."""
    fn(*args).block_until_ready()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _bytes_ref(b, hq, hkv, sq, skv, d) -> int:
    """HBM bytes the unfused reference materializes: f32 q/k/v/o plus the
    full (B, Hq, Sq, Skv) scores and probabilities."""
    io = 4 * b * (2 * sq * hq * d + 2 * skv * hkv * d)
    return io + 2 * 4 * b * hq * sq * skv


def _bytes_kernel(b, hq, hkv, sq, skv, d) -> int:
    """HBM bytes the fused kernel touches: q/k/v/o only — scores and
    probabilities live in (bq, bk) VMEM tiles, never written back."""
    return 4 * b * (2 * sq * hq * d + 2 * skv * hkv * d)


def _flash_rows(shapes, iters):
    rows = []
    key = jax.random.PRNGKey(0)
    for b, hq, hkv, t, d in shapes:
        kq, kk, kv = jax.random.split(jax.random.fold_in(key, t + hq), 3)
        q = jax.random.normal(kq, (b, t, hq, d), jnp.float32)
        k = jax.random.normal(kk, (b, t, hkv, d), jnp.float32)
        v = jax.random.normal(kv, (b, t, hkv, d), jnp.float32)

        # correctness before timing
        r = flash_attn_ref(q, k, v, True)
        p = flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   atol=2e-5, rtol=2e-5)

        t_ref = _timed(jax.jit(lambda a, b_, c: flash_attn_ref(a, b_, c,
                                                               True)),
                       q, k, v, iters=iters)
        t_ker = _timed(jax.jit(lambda a, b_, c: flash_attention(
            a, b_, c, causal=True)), q, k, v, iters=iters)
        rows.append({
            "kind": "flash", "b": b, "hq": hq, "hkv": hkv, "t": t, "d": d,
            "t_ref_ms": t_ref * 1e3, "t_kernel_ms": t_ker * 1e3,
            "speedup": t_ref / t_ker,
            "bytes_ref": _bytes_ref(b, hq, hkv, t, t, d),
            "bytes_kernel": _bytes_kernel(b, hq, hkv, t, t, d),
        })
    return rows


def _decode_rows(shapes, iters):
    rows = []
    key = jax.random.PRNGKey(1)
    for b, hq, hkv, s, d in shapes:
        kq, kk, kv = jax.random.split(jax.random.fold_in(key, s + hq), 3)
        q = jax.random.normal(kq, (b, hq, d), jnp.float32)
        k = jax.random.normal(kk, (b, s, hkv, d), jnp.float32)
        v = jax.random.normal(kv, (b, s, hkv, d), jnp.float32)
        length = jnp.asarray([max(1, s - 17 * i) for i in range(b)],
                             jnp.int32)

        r = decode_gqa_ref(q, k, v, length)
        p = decode_attention(q, k, v, length)
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   atol=2e-5, rtol=2e-5)

        t_ref = _timed(jax.jit(decode_gqa_ref), q, k, v, length,
                       iters=iters)
        t_ker = _timed(jax.jit(decode_attention), q, k, v, length,
                       iters=iters)
        rows.append({
            "kind": "decode", "b": b, "hq": hq, "hkv": hkv, "t": s, "d": d,
            "t_ref_ms": t_ref * 1e3, "t_kernel_ms": t_ker * 1e3,
            "speedup": t_ref / t_ker,
            "bytes_ref": _bytes_ref(b, hq, hkv, 1, s, d),
            "bytes_kernel": _bytes_kernel(b, hq, hkv, 1, s, d),
        })
    return rows


def _td_sigma_smoke():
    """TD-attention accuracy-vs-sigma: per-head engine attention against
    the clean fused kernel.  sigma=0 at 8 bits must reproduce it to the
    quantization floor; noise must then degrade it monotonically-ish."""
    key = jax.random.PRNGKey(2)
    kq, kk, kv, kn = jax.random.split(key, 4)
    b, t, hq, hkv, d = 2, 64, 4, 2, 32
    q = jax.random.normal(kq, (b, t, hq, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, hkv, d), jnp.float32)
    clean = np.asarray(flash_attention(q, k, v, causal=True))
    base = TDPolicy(mode="td", bits_a=8, bits_w=8, n_chain=d)
    errs = []
    for sg in TD_SIGMAS:
        o = td_attention(q, k, v, base.replace(sigma_chain=float(sg)), kn,
                         causal=True)
        errs.append(float(np.mean(np.abs(np.asarray(o) - clean))))
    assert errs[0] < 0.05, \
        f"8-bit sigma=0 TD attention off the clean path: err={errs[0]:.4f}"
    assert errs[-1] >= errs[0], "noise did not degrade TD attention"
    return {"sigmas": TD_SIGMAS, "mean_abs_err": errs}


def write_artifacts(rows, td_smoke, compiled: bool) -> list[str]:
    os.makedirs(OUT_DIR, exist_ok=True)
    paths = []
    p = os.path.join(OUT_DIR, "bench_attention.csv")
    with open(p, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(rows[0]))
        w.writeheader()
        w.writerows(rows)
    paths.append(p)
    p = os.path.join(OUT_DIR, "bench_attention.json")
    with open(p, "w") as f:
        json.dump({"compiled": compiled,
                   "timing_gate": "enforced" if compiled else
                   "recorded_only (interpret-mode CPU: correctness gate)",
                   "shapes": rows, "td_sigma_smoke": td_smoke}, f, indent=1)
    paths.append(p)
    return paths


def run() -> list[str]:
    compiled = not default_interpret()
    iters = 3 if _smoke() else 10
    rows = _flash_rows(FLASH_SHAPES_SMOKE if _smoke() else FLASH_SHAPES,
                       iters)
    rows += _decode_rows(DECODE_SHAPES_SMOKE if _smoke() else DECODE_SHAPES,
                         iters)
    out = []
    for r in rows:
        out.append(
            f"attention,kind={r['kind']},b={r['b']},hq={r['hq']},"
            f"hkv={r['hkv']},t={r['t']},d={r['d']},"
            f"t_ref_ms={r['t_ref_ms']:.2f},"
            f"t_kernel_ms={r['t_kernel_ms']:.2f},"
            f"speedup={r['speedup']:.2f}x,"
            f"bytes_ratio={r['bytes_ref'] / r['bytes_kernel']:.1f}x")
    td_smoke = _td_sigma_smoke()
    out.append("attention,td_sigma_smoke=" + ",".join(
        f"err@{s}={e:.4f}" for s, e in zip(td_smoke["sigmas"],
                                           td_smoke["mean_abs_err"])))
    if compiled:
        # the headline acceptance gate: every fused kernel shape beats the
        # score-materializing reference on wall-clock
        worst = min(rows, key=lambda r: r["speedup"])
        assert worst["speedup"] > 1.0, \
            f"compiled kernel not faster on {worst['kind']} " \
            f"(b={worst['b']},t={worst['t']}): {worst['speedup']:.2f}x"
    paths = write_artifacts(rows, td_smoke, compiled)
    for p in paths:
        out.append(f"attention,artifact={p}")
    out.append(f"attention,compiled={compiled},correctness_ok=True,"
               f"derived=fused_attention_engine=True")
    return out
