"""TD-VMM engine benchmark: jnp reference simulator vs the fused Pallas
kernel, wall-clock and bytes-materialized, across (M, K, N, Ba, n_chain)
shapes plus a fig10-smoke end-to-end noise sweep.

The jnp simulator (`td_matmul_int`) materializes the full
(Ba, ..., n_seg, n_chain) bit-plane tensor and an equally large threefry
noise tensor per matmul; the kernel streams (bm, n_chain) tiles and hashes
its noise in-register — the bytes column quantifies exactly the traffic
the fusion removes.

Timing policy (ISSUE 4 acceptance): the wall-clock gate — compiled Pallas
beating the simulator — is only *asserted* on a TPU backend, where the
kernel actually compiles; interpret-mode CPU runs (CI) record the ratio in
the artifact and assert correctness only (bit-exactness at sigma=0 and
oracle parity at sigma>0).

Artifacts under ``artifacts/td_vmm/``:

  * ``bench_td_vmm.csv``   per-shape wall-clock + bytes-materialized table
  * ``bench_td_vmm.json``  the same plus the fig10-smoke end-to-end timings,
                           speedup ratios and the gate disposition

``REPRO_TD_VMM_SMOKE=1`` shrinks the sweep for CI.
"""
import csv
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import noise_tolerance
from repro.kernels.td_vmm import ops as td_ops
from repro.kernels.td_vmm import ref as td_ref
from repro.kernels.common import default_interpret
from repro.tdsim import TDPolicy
from repro.tdsim.td_linear import td_matmul_int

OUT_DIR = os.path.join("artifacts", "td_vmm")

#            M     K     N  Ba  n_chain
SHAPES = [(256,  576, 256, 4, 576),    # paper-baseline chain
          (512, 1152, 512, 4, 576),
          (256, 1024, 256, 8, 256),    # 8-bit activations
          (1024,  576, 128, 4, 288)]
SHAPES_SMOKE = [(64, 70, 32, 4, 32), (32, 576, 16, 4, 576)]
SIGMA, TDC_Q = 1.5, 2


def _smoke() -> bool:
    return os.environ.get("REPRO_TD_VMM_SMOKE", "").strip() in ("1", "true")


def _timed(fn, *args, iters: int = 10) -> float:
    """Median wall-clock seconds of a jitted call (post-warmup)."""
    fn(*args).block_until_ready()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _bytes_sim(m, k, n, ba, n_chain) -> int:
    """HBM bytes the jnp simulator materializes per matmul: the f32 plane
    tensor, the partials and the same-shape threefry noise tensor."""
    n_seg = -(-k // n_chain)
    k_pad = n_seg * n_chain
    return 4 * (ba * m * k_pad + 2 * ba * m * n_seg * n)


def _bytes_pallas(m, k, n, ba, n_chain) -> int:
    """HBM bytes the fused kernel touches: int32 operands + f32 out — no
    plane/noise/offset intermediates (noise is hashed in-register)."""
    n_seg = -(-k // n_chain)
    k_pad = n_seg * n_chain
    return 4 * (m * k_pad + k_pad * n + m * n)


def _shape_rows(shapes, iters):
    rows = []
    key = jax.random.PRNGKey(0)
    for m, k, n, ba, n_chain in shapes:
        kx, kw, kn = jax.random.split(jax.random.fold_in(key, m + k), 3)
        lo = -(2 ** (ba - 1))
        xi = jax.random.randint(kx, (m, k), lo, -lo, jnp.int32)
        wi = jax.random.randint(kw, (k, n), -8, 8, jnp.int32)
        pol = TDPolicy(mode="td", bits_a=ba, bits_w=4, n_chain=n_chain,
                       sigma_chain=SIGMA, tdc_q=TDC_Q)

        # correctness before timing: sigma=0 bit-exact, sigma>0 == oracle
        pol0 = pol.replace(sigma_chain=0.0, tdc_q=1)
        y0 = td_ops.td_vmm(xi, wi, pol0, kn)
        np.testing.assert_array_equal(
            np.asarray(y0), np.asarray((xi @ wi).astype(jnp.float32)))
        seed = td_ref.derive_seed(kn)
        yn = td_ops.td_vmm_seeded(xi, wi, pol, seed)
        rn = td_ref.td_vmm_signed_ref(xi, wi, bits_a=ba, bits_w=4,
                                      n_chain=n_chain, sigma=SIGMA,
                                      tdc_q=TDC_Q, seed=seed)
        np.testing.assert_array_equal(np.asarray(yn), np.asarray(rn))

        t_sim = _timed(jax.jit(lambda a, b: td_matmul_int(a, b, pol, kn)),
                       xi, wi, iters=iters)
        t_pal = _timed(jax.jit(lambda a, b: td_ops.td_vmm(a, b, pol, kn)),
                       xi, wi, iters=iters)
        rows.append({
            "m": m, "k": k, "n": n, "bits_a": ba, "n_chain": n_chain,
            "t_sim_ms": t_sim * 1e3, "t_pallas_ms": t_pal * 1e3,
            "speedup": t_sim / t_pal,
            "bytes_sim": _bytes_sim(m, k, n, ba, n_chain),
            "bytes_pallas": _bytes_pallas(m, k, n, ba, n_chain),
        })
    return rows


def _fig10_smoke_eval(engine: str):
    """Tiny 2-layer MLP accuracy eval (fig10-shaped: one-hot per-layer sigma
    probes) on the chosen engine."""
    key = jax.random.PRNGKey(7)
    kx, k1, k2, kl = jax.random.split(key, 4)
    x_int = jax.random.randint(kx, (64, 64), -8, 8, jnp.int32)
    w1 = jax.random.randint(k1, (64, 64), -8, 8, jnp.int32)
    w2 = jax.random.randint(k2, (64, 10), -8, 8, jnp.int32)
    labels = jax.random.randint(kl, (64,), 0, 10)
    base = TDPolicy(mode="td", bits_a=4, bits_w=4, n_chain=64, tdc_q=1)
    mm = td_ops.td_vmm if engine == "pallas" else td_matmul_int

    def eval_fn(sigma_vec, k):
        ka, kb = jax.random.split(k)
        h = mm(x_int, w1, base.replace(sigma_chain=sigma_vec[0]), ka)
        h = jnp.clip(jnp.round(h / 64.0), -8, 7).astype(jnp.int32)
        logits = mm(h, w2, base.replace(sigma_chain=sigma_vec[1]), kb)
        return noise_tolerance.top1_accuracy(logits, labels)

    return eval_fn


def _fig10_smoke_times():
    key = jax.random.PRNGKey(0)
    sigmas = [0.25, 1.0, 4.0, 16.0]
    out = {}
    for engine in ("sim", "pallas"):
        eval_fn = _fig10_smoke_eval(engine)
        # warm the jit cache, then time the full batched sweep
        noise_tolerance.find_sigma_max_batched(eval_fn, sigmas, key,
                                               n_layers=2, n_repeats=2)
        t0 = time.perf_counter()
        res = noise_tolerance.find_sigma_max_batched(eval_fn, sigmas, key,
                                                     n_layers=2, n_repeats=2)
        out[engine] = {"t_s": time.perf_counter() - t0,
                       "n_evals": res.n_evals,
                       "sigma_max": [float(s) for s in res.sigma_max]}
    out["speedup"] = out["sim"]["t_s"] / out["pallas"]["t_s"]
    return out


def write_artifacts(rows, fig10, compiled: bool) -> list[str]:
    os.makedirs(OUT_DIR, exist_ok=True)
    paths = []
    p = os.path.join(OUT_DIR, "bench_td_vmm.csv")
    with open(p, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(rows[0]))
        w.writeheader()
        w.writerows(rows)
    paths.append(p)
    p = os.path.join(OUT_DIR, "bench_td_vmm.json")
    with open(p, "w") as f:
        json.dump({"compiled": compiled,
                   "timing_gate": "enforced" if compiled else
                   "recorded_only (interpret-mode CPU: correctness gate)",
                   "shapes": rows, "fig10_smoke": fig10}, f, indent=1)
    paths.append(p)
    return paths


def run() -> list[str]:
    compiled = not default_interpret()
    shapes = SHAPES_SMOKE if _smoke() else SHAPES
    iters = 3 if _smoke() else 10
    out = []
    rows = _shape_rows(shapes, iters)
    for r in rows:
        out.append(
            f"td_vmm,m={r['m']},k={r['k']},n={r['n']},ba={r['bits_a']},"
            f"n_chain={r['n_chain']},t_sim_ms={r['t_sim_ms']:.2f},"
            f"t_pallas_ms={r['t_pallas_ms']:.2f},"
            f"speedup={r['speedup']:.2f}x,"
            f"bytes_ratio={r['bytes_sim'] / r['bytes_pallas']:.1f}x")
    fig10 = _fig10_smoke_times()
    out.append(
        f"td_vmm,fig10_smoke_sim_s={fig10['sim']['t_s']:.3f},"
        f"fig10_smoke_pallas_s={fig10['pallas']['t_s']:.3f},"
        f"fig10_smoke_speedup={fig10['speedup']:.2f}x,"
        f"n_evals={fig10['pallas']['n_evals']}")
    if compiled:
        # the headline acceptance gate: fused/compiled kernel beats the
        # plane-materializing simulator on the end-to-end sweep
        assert fig10["speedup"] > 1.0, \
            f"compiled kernel not faster: {fig10['speedup']:.2f}x"
    paths = write_artifacts(rows, fig10, compiled)
    for p in paths:
        out.append(f"td_vmm,artifact={p}")
    out.append(f"td_vmm,compiled={compiled},correctness_ok=True,"
               f"derived=pallas_only_td_engine=True")
    return out
