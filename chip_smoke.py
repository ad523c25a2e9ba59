#!/usr/bin/env python3
"""Chip smoke: the serving path end to end on one TPU, at full width.

    python chip_smoke.py [--seed N]        # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4         # only the two multi-device paths

One process drives every phase.  It exits non-zero, and prints no result
line, when JAX finds no TPU or when any phase raises or differs from its
reference.

(a) device: platform, kind and count; the weights' bytes; the device's
    ``peak_bytes_in_use`` after each phase.
(b) precise serving: qwen2.5-3b at its published widths (bf16 weights made
    from ``--seed``) through `ContinuousBatchingEngine`, the engine behind
    ``python -m repro.launch.serve --scheduler``: 8 ragged requests
    (prompts of 240-480 tokens, 16-32 new tokens) at capacity 8.  Every
    request must be answered with every token in the vocabulary, and the
    first-token logits of request 0 from the serving prefill program must
    agree with a plain `transformer.forward` of the same prompt in float32
    under ``jax.default_matmul_precision("highest")``.
(c) the same through ``--td td`` (every matmul on the td_vmm kernel at
    n_chain=576), with the logits' distance to the plain forward in
    fake-quant mode reported (see TOL_PRECISE for why it is not gated),
    plus one full-width kernel call checked bit for bit against
    `ref.td_vmm_signed_ref` and the integer product at sigma=0, q=1.
(d) one design-space sweep through a fresh `ExplorerService` (no disk
    cache) compared with tests/fixtures/design_space_golden.json.

``--chips 4`` runs only (e) the Fig. 10 probe sweep in td mode on the
compiled kernel, sharded on a (4, 1) mesh, against the unsharded sweep,
bit for bit, and (f) the explorer's corner fan-out over the local devices
against the serial sweep, with each device's peak bytes.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The compile cache follows `repro.launch.compile_cache`.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2.5-3b"
N_REQUESTS, PROMPT_LEN, GEN, CAPACITY = 8, 480, 32, 8
# Logit agreement with the float32 reference, as relative L2 error
# ||served - ref|| / ||ref|| over the vocabulary.  Precise: the serving
# path holds bf16 weights and computes in bf16 (8-bit mantissa, 4e-3
# relative per rounding) through 36 layers, so a few 1e-2 is expected and
# 5e-2 bounds it; fp8-class compute (4e-2 to 6e-2 per rounding) would not
# stay under it.  TD mode is not held to a logit tolerance: with random
# weights the 4-bit model is ill-conditioned, and its bf16 fake-quant run
# is already 0.8-1.0 (relative L2) from its own float32 run, because codes
# near a level boundary flip and the flips grow through the layers.  The
# TD kernel is held bit-exact to its oracle instead (`phase_kernel`), and
# the TD logits' distance to the fake-quant reference is reported.
TOL_PRECISE = 5e-2
GOLDEN = ROOT / "tests" / "fixtures" / "design_space_golden.json"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def memory(phase: str) -> None:
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    log(phase, peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
        bytes_in_use=[s.get("bytes_in_use") for s in stats])


def rel_l2(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------------------
# (b), (c): serving through the scheduler, logits against the plain forward
# ---------------------------------------------------------------------------
def serving_logits(eng, prompt):
    """First-token logits of the engine's own bucketed prefill (the program
    `build_ragged_prefill_step` compiles, before its argmax)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import steps as steps_lib
    from repro.models import common, get_api
    api = get_api(eng.cfg)
    dt = steps_lib.DTYPES[eng.arch.train.compute_dtype]

    @jax.jit
    def prefill(params, toks, true_len):
        p_c = common.cast_tree(params, dt)
        last, _ = api["prefill"](p_c, {"tokens": toks}, eng.cfg, eng.pol,
                                 s_cache=eng.prompt_pad, true_len=true_len)
        return last[0, 0].astype(jnp.float32)

    padded = np.zeros((1, eng.prompt_pad), np.int32)
    padded[0, :len(prompt)] = prompt
    return np.asarray(prefill(eng.params, jnp.asarray(padded),
                              jnp.asarray(len(prompt), jnp.int32)))


def reference_logits(arch, params, prompt, ref_mode: str):
    """Plain float32 forward of the true-length prompt, no cache, no
    batching, under "highest" matmul precision; ``ref_mode`` "quant" runs
    the fake-quant matmuls in place of the TD kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import td_cli
    from repro.models import common, transformer
    ref_arch = td_cli.apply_td_args(arch, ref_mode, None)
    pol = common.resolve_arch_policy(ref_arch)

    @jax.jit
    def forward(p, toks):
        logits, _, _ = transformer.forward(common.cast_tree(p, jnp.float32),
                                           {"tokens": toks}, arch.model, pol)
        return logits[0, -1]

    with jax.default_matmul_precision("highest"):
        return np.asarray(forward(params, jnp.asarray(prompt)[None]))


def phase_serve(phase: str, arch, ref_mode: str, tol: float | None,
                seed: int,
                n_requests: int = N_REQUESTS, prompt_len: int = PROMPT_LEN,
                gen: int = GEN, capacity: int = CAPACITY) -> None:
    """Serve ragged requests through the scheduler, then check logits."""
    import numpy as np
    from repro.launch.scheduler import ContinuousBatchingEngine
    from repro.launch.serve import synthetic_requests
    from repro.roofline.model import tree_bytes

    t0 = time.monotonic()
    eng = ContinuousBatchingEngine(arch, capacity=capacity,
                                   s_cache=prompt_len + gen, seed=seed)
    log(phase, arch=arch.model.name, layers=arch.model.n_layers,
        d_model=arch.model.d_model, vocab=arch.model.vocab,
        weight_bytes=tree_bytes(eng.params),
        init_s=round(time.monotonic() - t0, 3),
        capacity=eng.capacity, s_cache=eng.s_cache,
        kv_budget_bytes=eng.kv_plan.budget_bytes)
    t0 = time.monotonic()
    eng.warmup()                       # compiles prefill / insert / decode
    compile_s = time.monotonic() - t0
    reqs = synthetic_requests(n_requests, prompt_len, gen, arch.model.vocab,
                              seed=seed + 1)
    for r in reqs:
        r.arrival_s = eng.clock()
    out = eng.run(reqs)
    log(phase, requests=out["requests"], new_tokens=out["new_tokens"],
        decode_steps=out["steps"], compile_s=round(compile_s, 3),
        serve_wall_s=round(out["wall_s"], 3),
        prompt_lens=[len(r.prompt) for r in reqs])
    check(out["requests"] == n_requests, f"{phase}: requests unanswered")
    for r in reqs:
        got = eng.done[r.rid].generated
        check(len(got) == r.max_new_tokens,
              f"{phase}: request {r.rid} gave {len(got)} tokens")
        check(all(0 <= t < arch.model.vocab for t in got),
              f"{phase}: request {r.rid} gave a token outside the vocab")

    prompt = reqs[0].prompt
    served = serving_logits(eng, prompt)
    check(bool(np.isfinite(served).all()), f"{phase}: non-finite logits")
    params = eng.params
    del eng
    gc.collect()
    ref = reference_logits(arch, params, prompt, ref_mode)
    err = rel_l2(served, ref)
    log(phase, logits_rel_l2=err, tol=tol, reference=f"{ref_mode} f32",
        served_argmax=int(served.argmax()), ref_argmax=int(ref.argmax()),
        first_token=reqs[0].generated[0] if reqs[0].generated else None)
    if tol is not None:
        check(err <= tol, f"{phase}: logits rel L2 {err} > {tol}")


def phase_kernel(phase: str, seed: int, m: int = 8, k: int = 2048,
                 n: int = 11008, n_chain: int = 576) -> None:
    """One td_vmm call on the chip against the jnp oracle at sigma=0."""
    import jax
    import numpy as np
    from repro.kernels.td_vmm import ops as td_ops
    from repro.kernels.td_vmm import ref as td_ref
    from repro.tdsim import TDPolicy
    kx, kw, kn = jax.random.split(jax.random.key(seed), 3)
    xi = jax.random.randint(kx, (m, k), -8, 8)
    wi = jax.random.randint(kw, (k, n), -8, 8)
    pol = TDPolicy(mode="td", bits_a=4, bits_w=4, n_chain=n_chain,
                   sigma_chain=0.0, tdc_q=1)
    got = np.asarray(td_ops.td_vmm(xi, wi, pol, kn))
    want = np.asarray(td_ref.td_vmm_signed_ref(
        xi, wi, bits_a=4, bits_w=4, n_chain=n_chain, sigma=0.0, tdc_q=1,
        seed=td_ref.derive_seed(kn)))
    exact = np.asarray(xi, np.int64) @ np.asarray(wi, np.int64)
    log(phase, shape=(m, k, n), n_chain=n_chain,
        mismatches_vs_ref=int((got != want).sum()),
        mismatches_vs_int=int((got != exact).sum()))
    check(np.array_equal(got, want), f"{phase}: kernel != oracle at sigma=0")
    check(np.array_equal(got, exact), f"{phase}: kernel != integer product")


# ---------------------------------------------------------------------------
# (d): design-space sweep against the golden fixture
# ---------------------------------------------------------------------------
def phase_design(phase: str) -> None:
    import numpy as np
    from repro.core import constants as C
    from repro.core import design_space as ds
    from repro.core.explorer import ExplorerService

    doc = json.loads(GOLDEN.read_text())
    ns, bits = tuple(doc["ns"]), tuple(doc["bits"])
    svc = ExplorerService()
    regimes = {"exact": None, "relaxed": doc["sigma_relaxed"]}
    decisions = fields = winners = points = 0
    off = []                           # (regime, domain, n, bits, field, got, want)
    t0 = time.monotonic()
    for regime, sigma in regimes.items():
        g = svc.sweep_axes(ns=ns, bit_widths=bits, sigma_maxes=sigma,
                           vdds=C.VDD_NOM, p_x_ones=C.P_X_ONE,
                           w_bit_sparsities=C.W_BIT_SPARSITY,
                           ms=C.M_DEFAULT, tdc_archs="hybrid")
        names = g.winner_names()
        recs = [r for r in doc["records"] if r["regime"] == regime]
        for r in recs:
            bi, ni = bits.index(r["bits"]), ns.index(r["n"])
            if r["domain"] == "__winner__":
                winners += names[bi, ni, 0, 0, 0, 0, 0, 0] != r["winner"]
                continue
            ix = (ds.DOMAINS.index(r["domain"]), bi, ni, 0, 0, 0, 0, 0, 0)
            points += 1
            decisions += (int(g.redundancy[ix]) != r["redundancy"]) \
                + (int(g.tdc_q[ix]) != r["tdc_q"])
            for f in ("e_mac", "throughput", "area_per_mac"):
                got = float(getattr(g, f)[ix])
                if not np.isclose(got, r[f], rtol=1e-4, atol=0.0):
                    fields += 1
                    off.append((regime, r["domain"], r["n"], r["bits"], f,
                                got, r[f]))
    log(phase, points=points, sweep_s=round(time.monotonic() - t0, 3),
        r_q_mismatches=decisions, winner_mismatches=winners,
        field_mismatches=fields, field_rtol=1e-4, fields_off=off)
    check(decisions == 0 and winners == 0,
          f"{phase}: {decisions} R/q and {winners} winner mismatches")
    check(fields == 0, f"{phase}: {fields} fields off the fixture by >1e-4")


# ---------------------------------------------------------------------------
# (e), (f): the two multi-device paths
# ---------------------------------------------------------------------------
def phase_probe_mesh(phase: str, n_dev: int) -> None:
    """The Fig. 10 per-layer probe sweep of an LM in td mode, sharded over
    a (n_dev, 1) mesh, against the unsharded sweep (the comparison of
    tests/test_td_vmm_engine.py's multi-device parity test)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.configs as cfgs
    from repro.configs.base import TDExecCfg
    from repro.core import noise_tolerance as nt
    from repro.launch.mesh import make_mesh
    from repro.models import get_api
    from repro.models import transformer as tr
    from repro.tdsim import NetworkPolicy, TDPolicy, quant_policy

    cfg = cfgs.get_smoke("granite-8b").replace(
        td=TDExecCfg(mode="quant")).model
    key = jax.random.PRNGKey(0)
    params = get_api(cfg)["init"](key, cfg, quant_policy(4, 4))
    toks = jax.random.randint(key, (8, 64), 3, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    base = TDPolicy(mode="td", bits_a=4, bits_w=4, n_chain=cfg.d_model)

    def eval_fn(sigma_vec, k):
        pol = NetworkPolicy(layers=tuple(
            base.replace(sigma_chain=sigma_vec[i])
            for i in range(cfg.n_layers)), top=quant_policy(4, 4))
        logits, _, _ = tr.forward(params, batch, cfg, pol, key=k)
        return nt.top1_accuracy(logits, batch["labels"])

    kw = dict(sigmas=[0.25, 0.5, 1.0, 2.0, 4.0, 8.0], key=key,
              n_layers=cfg.n_layers, n_repeats=2)
    t0 = time.monotonic()
    plain = nt.find_sigma_max_batched(eval_fn, **kw)
    t_plain = time.monotonic() - t0
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    t0 = time.monotonic()
    meshed = nt.find_sigma_max_batched(eval_fn, **kw, mesh=mesh)
    t_mesh = time.monotonic() - t0
    chunked = nt.find_sigma_max_batched(eval_fn, **kw, mesh=mesh,
                                        chunk_size=2 * n_dev)
    same = all(np.array_equal(plain.rel_drop, got.rel_drop)
               and np.array_equal(plain.sigma_max, got.sigma_max)
               and np.array_equal(plain.acc_clean, got.acc_clean)
               for got in (meshed, chunked))
    log(phase, probes=plain.n_evals, mesh=dict(mesh.shape),
        sigma_max=plain.sigma_max.tolist(), bitwise_equal=same,
        unsharded_s=round(t_plain, 3), sharded_s=round(t_mesh, 3))
    check(same, f"{phase}: sharded sweep differs from unsharded")


def phase_fanout(phase: str, scenario: str = "edge") -> None:
    """Corner fan-out over the local devices against the serial sweep."""
    import numpy as np
    from repro.core import design_grid
    from repro.core.explorer import ExplorerService

    svc = ExplorerService()
    t0 = time.monotonic()
    par = svc.sweep_scenarios(scenario, parallel=True, use_cache=False)
    t_par = time.monotonic() - t0
    t0 = time.monotonic()
    ser = svc.sweep_scenarios(scenario, parallel=False, use_cache=False)
    t_ser = time.monotonic() - t0
    same = sorted(par) == sorted(ser) and all(
        np.array_equal(getattr(par[c], f), getattr(ser[c], f))
        for c in par for f in design_grid._FIELDS)
    log(phase, scenario=scenario, corners=sorted(par),
        points_per_corner=next(iter(par.values())).n_points,
        bitwise_equal=same, parallel_s=round(t_par, 3),
        serial_s=round(t_ser, 3))
    check(same, f"{phase}: fan-out differs from the serial sweep")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-device paths")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found ({device}); nothing was run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    log("a", platform=device["platform"], kind=device["kind"],
        count=device["count"], compile_cache=enable_compile_cache())

    if args.chips == 4:
        phase_probe_mesh("e", args.chips)
        memory("e")
        phase_fanout("f")
        memory("f")
    else:
        import repro.configs as cfgs
        from repro.launch import td_cli
        arch = cfgs.get(ARCH)
        phase_serve("b", td_cli.apply_td_args(arch, "precise", None),
                    "precise", TOL_PRECISE, args.seed)
        memory("b")
        gc.collect()
        phase_serve("c", td_cli.apply_td_args(arch, "td", None), "quant",
                    None, args.seed)
        phase_kernel("c", args.seed)
        memory("c")
        phase_design("d")
        memory("d")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
