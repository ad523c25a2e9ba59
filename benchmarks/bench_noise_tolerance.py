"""Fig. 10: relative accuracy drop vs injected chain noise for LSQ-4bit
networks; sigma_array_max at <= 1% relative drop — now on the batched
search: the whole (layers x sigma-grid x repeats [+ clean]) product runs as
ONE vmapped+jitted eval call (`core.noise_tolerance.find_sigma_max_batched`)
instead of a python double loop that recompiled per sigma.  Every td matmul
inside the sweep runs the Pallas kernel (sigma is a runtime operand), and on
multi-device hosts the probe batch shards over the mesh data axis
(bit-identical results; see `_probe_mesh`).

Paper setup: ResNet20/CIFAR10 + ResNet18/ImageNet.  Here: the paper's
ResNet20-family CNN on synthetic CIFAR-shaped data (trained to high
accuracy first) PLUS — beyond the paper — a small LM from the assigned-arch
zoo evaluated on next-token top-1.  Noise is injected per bit-plane with TDC
rounding via the TD execution simulator (exactly the paper's "necessary bit
sequencing" procedure).

Artifacts (closing the Fig. 10 -> Fig. 11 loop) under
``artifacts/noise_tolerance/``:

  * ``fig10b_rel_drop.csv``             network-level drop curves (Fig. 10b)
  * ``per_layer_sigma_max.csv``         per-layer/site sigma_array_max table
  * ``per_layer_policies_<model>.json`` per-layer (R, q, sigma_chain)
                                        solution via
                                        `tdsim.policy.solve_network_policies`,
                                        consumable by
                                        ``launch/{train,serve,dryrun}
                                        --td-per-layer @file``
"""
import csv
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as cfgs
from repro.configs.resnet20_cifar import smoke as resnet_smoke
from repro.core import noise_tolerance
from repro.models import get_api, resnet
from repro.tdsim import NetworkPolicy, TDPolicy, quant_policy
from repro.tdsim.policy import solve_network_policies
from repro.configs.base import TDExecCfg

SIGMAS = [0.25, 0.5, 1.0, 2.0, 4.0, 8.0]
N_REPEATS = 2
OUT_DIR = os.path.join("artifacts", "noise_tolerance")


def _probe_mesh():
    """Mesh for the probe-batch data sharding: all local devices on the
    data axis when there is more than one (the big-LM per-layer sweep is
    mesh-parallel); None on a single device (CI) — results are
    bit-identical either way (tests/test_td_vmm_engine.py)."""
    if jax.device_count() <= 1:
        return None
    from repro.launch.mesh import make_mesh
    return make_mesh((jax.device_count(), 1), ("data", "model"))


def _train_resnet(cfg, key, steps=150):
    pol = quant_policy(4, 4)   # LSQ-4bit as in the paper
    params = resnet.init_params(key, cfg, pol)
    imgs, labels = resnet.make_synthetic_cifar(key, 512, cfg)

    def loss_fn(p, k):
        logits = resnet.forward(p, imgs, cfg, pol, k)
        onehot = jax.nn.one_hot(labels, cfg.classes)
        return -(jax.nn.log_softmax(logits) * onehot).sum(-1).mean()

    @jax.jit
    def step(p, k):
        l, g = jax.value_and_grad(loss_fn)(p, k)
        return jax.tree_util.tree_map(lambda a, b: a - 0.05 * b, p, g), l

    for i in range(steps):
        params, l = step(params, jax.random.fold_in(key, i))
    return params, pol


def _resnet_eval_fns(params, cfg, key):
    """(per_site_eval, network_eval, n_sites): traceable accuracy functions
    taking a per-site / length-1 sigma vector (traced -> one compile for the
    whole sweep)."""
    # 64 eval images: the per-site sweep vmaps/chunks ~sites*(S*R+1)
    # forwards into one program, so the eval batch sets the per-probe cost
    # (every conv now runs the Pallas kernel, interpret-mode on CPU CI)
    imgs, labels = resnet.make_synthetic_cifar(
        jax.random.fold_in(key, 999), 64, cfg)
    sites = resnet.noise_sites(cfg)
    base = TDPolicy(mode="td", bits_a=4, bits_w=4,
                    n_chain=9 * max(cfg.stages), sigma_chain=0.0, tdc_q=1)

    def acc(pols, k):
        logits = resnet.forward(params, imgs, cfg, pols, k)
        return noise_tolerance.top1_accuracy(logits, labels)

    def per_site_eval(sigma_vec, k):
        return acc([base.replace(sigma_chain=sigma_vec[i])
                    for i in range(len(sites))], k)

    def network_eval(sigma_vec, k):
        return acc([base.replace(sigma_chain=sigma_vec[0])
                    for _ in sites], k)

    return per_site_eval, network_eval, len(sites), sites, base


def _lm_eval_fns(arch_name, key):
    """Batched per-layer eval for a smoke LM: sigma_vec entry i drives layer
    i's matmuls through a trace-local NetworkPolicy."""
    ac = cfgs.get_smoke(arch_name)
    ac = ac.replace(td=TDExecCfg(mode="quant"))
    cfg = ac.model
    api = get_api(cfg)
    pol_q = quant_policy(4, 4)
    params = api["init"](key, cfg, pol_q)

    # brief QAT so next-token top-1 is meaningfully above chance (the
    # paper's networks are trained; an untrained LM has no signal to lose)
    from repro.data.synthetic import DataCfg, SyntheticStream
    stream = SyntheticStream(DataCfg(vocab=cfg.vocab, seq_len=32,
                                     global_batch=8))

    @jax.jit
    def train_step(p, tk, lb, k):
        def loss(p_):
            l, _ = api["train_loss"](p_, {"tokens": tk, "labels": lb},
                                     cfg, pol_q, k)
            return l
        l, g = jax.value_and_grad(loss)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.15 * b, p, g), l

    for i in range(60):
        hb = stream.batch(i)
        params, _ = train_step(params, jnp.asarray(hb["tokens"]),
                               jnp.asarray(hb["labels"]),
                               jax.random.fold_in(key, i))

    hb = stream.batch(999)
    batch = {"tokens": jnp.asarray(hb["tokens"]),
             "labels": jnp.asarray(hb["labels"])}

    from repro.models import transformer as tr
    base = TDPolicy(mode="td", bits_a=4, bits_w=4, n_chain=cfg.d_model,
                    sigma_chain=0.0, tdc_q=1)

    def acc(pol, k):
        logits, _, _ = tr.forward(params, batch, cfg, pol, key=k)
        return noise_tolerance.top1_accuracy(logits, batch["labels"])

    def per_layer_eval(sigma_vec, k):
        pol = NetworkPolicy(
            layers=tuple(base.replace(sigma_chain=sigma_vec[i])
                         for i in range(cfg.n_layers)),
            top=pol_q)
        return acc(pol, k)

    def network_eval(sigma_vec, k):
        pol = NetworkPolicy(
            layers=tuple(base.replace(sigma_chain=sigma_vec[0])
                         for _ in range(cfg.n_layers)),
            top=pol_q)
        return acc(pol, k)

    return per_layer_eval, network_eval, cfg.n_layers, \
        [f"layer{i}" for i in range(cfg.n_layers)], base


def write_artifacts(out_dir, curves, per_layer, policies) -> list[str]:
    """curves: {model: NoiseToleranceResult}, per_layer: {model: (sites,
    BatchedNoiseToleranceResult)}, policies: {model: (sites, sigma_max
    list, NetworkPolicy)}.  Returns the written paths."""
    os.makedirs(out_dir, exist_ok=True)
    paths = []

    p = os.path.join(out_dir, "fig10b_rel_drop.csv")
    with open(p, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["model", "sigma", "rel_drop", "acc_clean", "sigma_max"])
        for model, res in curves.items():
            for s, d in zip(res.sigmas, res.rel_drop):
                w.writerow([model, float(s), float(d),
                            float(res.acc_clean), float(res.sigma_max)])
    paths.append(p)

    p = os.path.join(out_dir, "per_layer_sigma_max.csv")
    with open(p, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["model", "layer_index", "site", "sigma_max",
                    "acc_clean"])
        for model, (sites, res) in per_layer.items():
            for i, site in enumerate(sites):
                w.writerow([model, i, site, float(res.sigma_max[i]),
                            float(res.acc_clean[i])])
    paths.append(p)

    for model, (sites, sigma_table, net) in policies.items():
        p = os.path.join(out_dir, f"per_layer_policies_{model}.json")
        doc = {"model": model, "layers": [
            {"site": site, "sigma_max": float(sig),
             "bits_a": pol.bits_a, "bits_w": pol.bits_w,
             "n_chain": pol.n_chain, "redundancy": pol.redundancy,
             "tdc_q": pol.tdc_q, "sigma_chain": pol.sigma_chain}
            for site, sig, pol in zip(sites, sigma_table, net.layers)]}
        with open(p, "w") as f:
            json.dump(doc, f, indent=1)
        paths.append(p)
    return paths


def run() -> list[str]:
    rows = []
    key = jax.random.PRNGKey(0)
    curves, per_layer, policies = {}, {}, {}

    # --- the paper's CNN: per-site batched sweep -------------------------
    cfg = resnet_smoke()
    params, _ = _train_resnet(cfg, key)
    site_eval, net_eval, n_sites, sites, base = _resnet_eval_fns(
        params, cfg, key)

    traces = 0

    def counted_eval(sv, k):
        nonlocal traces
        traces += 1
        return site_eval(sv, k)

    mesh = _probe_mesh()
    # ~one probe-layer's worth of evals per chunk: bounds the live broadcast
    # of the eval batch across probes while staying one jitted device call
    # (find_sigma_max_batched rounds it up to a multiple of the data axis)
    chunk = len(SIGMAS) * N_REPEATS + 1
    t0 = time.perf_counter()
    res_sites = noise_tolerance.find_sigma_max_batched(
        counted_eval, SIGMAS, key, n_layers=n_sites, n_repeats=N_REPEATS,
        chunk_size=chunk, mesh=mesh)
    t_batched = time.perf_counter() - t0
    # the whole (sites x sigma x repeat [+ clean]) sweep must have traced
    # the eval exactly once: one vmapped+jitted call for the full Fig. 10
    assert traces == 1, f"batched sweep traced eval {traces}x, expected 1"

    # scalar reference timing on ONE site, extrapolated to the full sweep
    # (the python loop pays a fresh eval per (sigma, repeat) point)
    def scalar_site0(s, k):
        sv = jnp.zeros(n_sites).at[0].set(s)
        return float(site_eval(sv, k))

    t0 = time.perf_counter()
    res_scalar0 = noise_tolerance.find_sigma_max(
        scalar_site0, SIGMAS, jax.random.fold_in(key, 0),
        n_repeats=N_REPEATS)
    t_scalar_site = time.perf_counter() - t0
    t_scalar_extrap = t_scalar_site * n_sites
    # timed acceptance gate: one batched call beats the per-layer scalar
    # loop over the same multi-layer sweep.  Enforced where the TD kernel
    # compiles (TPU); interpret-mode CPU CI records the ratio and gates on
    # correctness/structure only (traces == 1 above) — the interpreter's
    # per-grid-step overhead dominates both paths there.
    from repro.kernels.common import default_interpret
    timing_enforced = not default_interpret()
    if timing_enforced:
        assert t_batched < t_scalar_extrap, \
            f"batched {t_batched:.2f}s not faster than scalar " \
            f"{t_scalar_extrap:.2f}s ({n_sites} layers)"
    # per-layer parity vs the scalar run of site 0 (same keys, same grid);
    # vmapped and single-point programs may differ by float re-association
    # (a borderline prediction can flip), so gate at one local grid step —
    # exact parity is property-tested on deterministic evals in
    # tests/test_noise_tolerance_props.py
    d0 = abs(res_scalar0.sigma_max - float(res_sites.sigma_max[0]))
    gaps = np.diff(np.asarray(SIGMAS, np.float64))
    cell = int(np.clip(np.searchsorted(SIGMAS, res_scalar0.sigma_max) - 1,
                       0, len(gaps) - 1))
    assert d0 <= float(gaps[cell]) + 1e-6, \
        f"site0 scalar/batched sigma_max diverge: {d0} > grid step " \
        f"{gaps[cell]}"

    for i, site in enumerate(sites):
        rows.append(f"fig10_noise,model=resnet20,site={site},"
                    f"sigma_max={res_sites.sigma_max[i]:.3f}")

    # network-level Fig. 10b curve (noise in ALL conv outputs, as printed)
    res_net = noise_tolerance.find_sigma_max_batched(
        net_eval, SIGMAS, key, n_layers=1, n_repeats=N_REPEATS).layer(0)
    for s, d in zip(res_net.sigmas, res_net.rel_drop):
        rows.append(f"fig10_noise,model=resnet20,sigma={s},"
                    f"rel_drop={d:.4f}")
    rows.append(f"fig10_noise,model=resnet20,acc_clean={res_net.acc_clean:.3f},"
                f"sigma_max={res_net.sigma_max:.3f}")

    curves["resnet20"] = res_net
    per_layer["resnet20"] = (sites, res_sites)
    net_p = solve_network_policies(res_sites.sigma_max, bits_a=4, bits_w=4,
                                   n_chain=base.n_chain)
    policies["resnet20"] = (sites, [float(s) for s in res_sites.sigma_max],
                            net_p)

    # --- beyond-paper: LM from the assigned pool, per-layer --------------
    lm_name = "granite-8b"
    lm_eval, lm_net_eval, n_lm, lm_sites, lm_base = _lm_eval_fns(lm_name,
                                                                 key)
    # same chunking as the CNN sweep: an unchunked mesh= call would keep
    # the whole (n_lm * (S*R+1)) probe batch live at once
    res_lm_layers = noise_tolerance.find_sigma_max_batched(
        lm_eval, SIGMAS, key, n_layers=n_lm, n_repeats=N_REPEATS,
        chunk_size=chunk, mesh=mesh)
    res_lm = noise_tolerance.find_sigma_max_batched(
        lm_net_eval, SIGMAS, key, n_layers=1, n_repeats=N_REPEATS).layer(0)
    for s, d in zip(res_lm.sigmas, res_lm.rel_drop):
        rows.append(f"fig10_noise,model=granite-smoke-lm,sigma={s},"
                    f"rel_drop={d:.4f}")
    rows.append(f"fig10_noise,model=granite-smoke-lm,"
                f"acc_clean={res_lm.acc_clean:.3f},"
                f"sigma_max={res_lm.sigma_max:.3f}")
    for i, site in enumerate(lm_sites):
        rows.append(f"fig10_noise,model=granite-smoke-lm,site={site},"
                    f"sigma_max={res_lm_layers.sigma_max[i]:.3f}")

    curves["granite-smoke-lm"] = res_lm
    per_layer["granite-smoke-lm"] = (lm_sites, res_lm_layers)
    lm_net_p = solve_network_policies(res_lm_layers.sigma_max, bits_a=4,
                                      bits_w=4, n_chain=lm_base.n_chain)
    policies["granite-smoke-lm"] = (lm_sites,
                                    [float(s) for s in
                                     res_lm_layers.sigma_max], lm_net_p)

    paths = write_artifacts(OUT_DIR, curves, per_layer, policies)
    for p in paths:
        rows.append(f"fig10_noise,artifact={p}")

    us = t_batched * 1e6 / res_sites.n_evals
    rows.append(
        f"fig10_noise,batched_s={t_batched:.2f},"
        f"scalar_s_extrapolated={t_scalar_extrap:.2f}"
        f"(timed={len(SIGMAS) * N_REPEATS + 1}evals x{n_sites}layers),"
        f"speedup={t_scalar_extrap / t_batched:.1f}x,"
        f"us_per_eval={us:.0f},"
        f"probe_mesh_devices={1 if mesh is None else mesh.size},"
        f"timing_gate={'enforced' if timing_enforced else 'recorded_only'},"
        f"derived=single_jitted_sweep=True,"
        f"sigma_max_cnn={res_net.sigma_max:.2f},"
        f"sigma_max_lm={res_lm.sigma_max:.2f}")
    return rows
