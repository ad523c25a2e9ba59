"""Serving driver: batched prefill + greedy decode with KV caches, plus
the continuous-batching scheduler front-end.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --batch 4 --prompt-len 32 --gen 32 --td quant --seed 0

    # ragged concurrent streams through the slot-recycling scheduler
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --scheduler --streams 16 --capacity 4 --td quant

Exercises the same prefill/decode steps the dry-run lowers at production
shapes, including per-token latency stats and the TD energy meter (J/token
under the three hardware domains for the current arch + policy; PER
REQUEST in scheduler mode).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as cfgs
from repro.configs.base import ShapeCfg
from repro.launch import steps as steps_lib
from repro.launch import td_cli
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.scheduler import ContinuousBatchingEngine, Request
from repro.models import common, get_api, matmul_shapes
from repro.tdsim import energy_meter


def run(arch, batch: int, prompt_len: int, gen: int, seed: int = 0):
    cfg = arch.model
    pol = common.resolve_arch_policy(arch)
    api = get_api(cfg)
    # one independent key stream per consumer: reusing a single key would
    # correlate param init, prompt sampling and frontend embeds
    k_params, k_prompts, k_embeds = jax.random.split(jax.random.key(seed), 3)
    # serving holds the weights in the compute dtype, as deployments do
    params = api["init"](k_params, cfg, pol,
                         steps_lib.DTYPES[arch.train.compute_dtype])
    s_cache = prompt_len + gen

    shape = ShapeCfg("serve", s_cache, batch, "decode")
    prefill = jax.jit(steps_lib.build_prefill_step(arch, shape))
    serve_step = jax.jit(steps_lib.build_serve_step(arch, shape),
                         donate_argnums=(2,))

    toks = jax.random.randint(k_prompts, (batch, prompt_len), 3, cfg.vocab)
    batch_in = {"tokens": toks}
    if cfg.family == "encdec" or cfg.frontend is not None:
        batch_in["embeds"] = jax.random.normal(
            k_embeds, (batch, max(8, prompt_len // 2),
                       cfg.d_frontend or cfg.d_model), jnp.bfloat16)

    t0 = time.monotonic()
    logits, state = prefill(params, batch_in)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    jax.block_until_ready(tok)
    t_prefill = time.monotonic() - t0

    out_toks = [tok]
    lat = []
    for _ in range(gen - 1):
        t1 = time.monotonic()
        tok, state = serve_step(params, tok, state)
        jax.block_until_ready(tok)
        lat.append(time.monotonic() - t1)
        out_toks.append(tok)
    gen_ids = jnp.concatenate(out_toks, axis=1)

    lat = np.asarray(lat) if lat else np.asarray([0.0])
    print(f"[serve] prefill({batch}x{prompt_len}): {t_prefill*1e3:.1f} ms; "
          f"decode p50={np.median(lat)*1e3:.1f} ms/tok "
          f"p95={np.percentile(lat, 95)*1e3:.1f} ms/tok")
    print(f"[serve] sample ids[0,:16]: {np.asarray(gen_ids)[0, :16].tolist()}")

    # hardware energy accounting (the paper's axis) for this serving config;
    # with per-layer policies the first layer's policy sets the accounting
    # bit widths / chain length.  A solved TD policy carries its own
    # operating point (vdd + budget, e.g. from --scenario/--corner) and the
    # meter runs at it; quant-mode policies fall back to the representative
    # relaxed budget.
    shapes = matmul_shapes(cfg)
    pol0 = common.pol_at(pol, 0)
    pol_acct = pol0 if pol0.mode != "precise" else None
    if pol_acct is not None:
        sigma_acct = None if pol_acct.sigma_max is not None else 2.0
        reports = energy_meter.compare_domains(shapes, pol_acct,
                                               sigma_max=sigma_acct)
        for dom, rep in reports.items():
            print(f"[energy] {dom:8s}: {rep.total_energy_per_token:.3e} "
                  f"J/token over {rep.total_macs_per_token:.3e} MACs "
                  f"(vdd={pol_acct.vdd:.2f})")
    return gen_ids


def synthetic_requests(n: int, prompt_len: int, gen: int,
                       vocab: int, seed: int = 0) -> list[Request]:
    """Ragged synthetic streams: prompt and generation lengths each vary
    uniformly in [len/2, len] — the bursty traffic shape the fixed-batch
    driver cannot represent."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(max(1, prompt_len // 2), prompt_len + 1))
        glen = int(rng.integers(max(1, gen // 2), gen + 1))
        reqs.append(Request(
            rid=i, prompt=rng.integers(3, vocab, size=plen).astype(np.int32),
            max_new_tokens=glen))
    return reqs


def parse_trace(spec: str):
    """``--trace`` value -> `ft.TrafficTrace`: ``@file.json`` loads a
    saved trace; ``SEED:STEPS[:SEGMENTS]`` generates a seeded one."""
    from repro import ft
    if spec.startswith("@"):
        return ft.TrafficTrace.load(spec[1:])
    parts = spec.split(":")
    if len(parts) not in (2, 3):
        raise ValueError("--trace wants @file.json or SEED:STEPS[:SEGMENTS]"
                         f", got {spec!r}")
    seed, steps = int(parts[0]), int(parts[1])
    n_seg = int(parts[2]) if len(parts) == 3 else 6
    return ft.TrafficTrace.generate(seed, steps, n_segments=n_seg)


def run_scheduler(arch, streams: int, prompt_len: int, gen: int,
                  capacity: int, seed: int = 0, adapt: bool = False,
                  trace=None):
    """Continuous-batching serve: ragged streams through the scheduler."""
    # independent key streams: the engine consumes the params seed, the
    # prompt sampler its own fold — mirrors run()'s per-consumer split
    eng = ContinuousBatchingEngine(arch, capacity=capacity,
                                   s_cache=prompt_len + gen, seed=seed,
                                   adapt=adapt)
    reqs = synthetic_requests(streams, prompt_len, gen, arch.model.vocab,
                              seed=seed + 1)
    t_arrival = time.monotonic()
    for r in reqs:
        r.arrival_s = t_arrival
    out = eng.run(reqs, trace=trace)
    print(f"[serve/sched] {out['requests']} requests, "
          f"{out['new_tokens']} tokens in {out['wall_s']:.2f} s "
          f"({out['tokens_per_s']:.1f} tok/s, {out['steps']} steps, "
          f"capacity {eng.capacity}, slot {eng.s_cache} tok)")
    print(f"[serve/sched] per-request ms/token "
          f"p50={out['ms_per_token_p50']:.2f} "
          f"p99={out['ms_per_token_p99']:.2f}; "
          f"stragglers={out['stragglers']}")
    if "energy_j_total" in out:
        print(f"[serve/sched] TD energy: {out['energy_j_total']:.3e} J "
              f"total, {out['j_per_token']:.3e} J/token "
              f"({eng.meter.domain} domain, per-request rows available)")
    if adapt:
        print(f"[serve/sched] drift: p_x_one={out['p_x_one_measured']:.3f} "
              f"(policy anchor {common.pol_at(eng.pol, 0).p_x_one:.3f}), "
              f"{out['adaptations']} adaptation(s), "
              f"{out['supply_spans']} supply span(s)")
    if trace is not None:
        print(f"[serve/sched] trace: seed={trace.seed} "
              f"{len(trace.segments)} segment(s) / {trace.total_steps} "
              f"steps; swaps={[e['step'] for e in out['swap_log']]}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed; split per consumer (params / prompts "
                    "/ frontend embeds)")
    ap.add_argument("--scheduler", action="store_true",
                    help="continuous-batching engine over ragged synthetic "
                    "streams (admission queue + slot recycling) instead of "
                    "the fixed-batch driver")
    ap.add_argument("--streams", type=int, default=16,
                    help="scheduler mode: number of synthetic streams")
    ap.add_argument("--capacity", type=int, default=4,
                    help="scheduler mode: concurrent KV-cache slots")
    ap.add_argument("--adapt", action="store_true",
                    help="scheduler mode: measure activation activity in "
                    "the decode step and hot-swap the TD operating point "
                    "(policy + energy rate) when it drifts")
    ap.add_argument("--trace", default=None,
                    help="scheduler mode: replay a deterministic traffic "
                    "trace through the drift loop — @file.json or "
                    "SEED:STEPS[:SEGMENTS] for a seeded one (implies the "
                    "activity/sparsity/load excursions of its segments)")
    ap.add_argument("--td", default=None,
                    choices=[None, "precise", "quant", "td"])
    ap.add_argument("--td-per-layer", default=None,
                    help="heterogeneous per-layer TD policies: inline sigma "
                    "list '0.5,1.0,...' or '@per_layer_policies.json' from "
                    "the Fig. 10 batched noise-tolerance search")
    td_cli.add_td_attn_arg(ap)
    td_cli.add_scenario_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    arch = cfgs.get_smoke(args.arch) if args.smoke else cfgs.get(args.arch)
    arch = td_cli.apply_td_args(arch, args.td, args.td_per_layer,
                                args.scenario, args.corner,
                                td_attn=args.td_attn)
    if args.scheduler:
        run_scheduler(arch, args.streams, args.prompt_len, args.gen,
                      args.capacity, seed=args.seed,
                      adapt=args.adapt or args.trace is not None,
                      trace=(parse_trace(args.trace)
                             if args.trace else None))
    else:
        run(arch, args.batch, args.prompt_len, args.gen, seed=args.seed)


if __name__ == "__main__":
    main()
