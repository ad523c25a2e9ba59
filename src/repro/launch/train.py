"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --smoke --steps 200 --td quant

Wires together: config registry -> model zoo -> TD execution policy ->
synthetic data pipeline (prefetch) -> jitted train_step (grad-accum + AdamW)
-> async checkpointing -> watchdog/retry fault tolerance.  On CPU this runs
the reduced smoke configs end-to-end; the same driver lowers the full
configs on a TPU mesh.
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as cfgs
from repro.checkpoint import ckpt
from repro.configs.base import ShapeCfg
from repro.data.pipeline import PrefetchLoader
from repro.data.synthetic import DataCfg, SyntheticStream
from repro import ft
from repro.launch import td_cli
from repro.launch.compile_cache import enable_compile_cache
from repro.launch import steps as steps_lib
from repro.models import get_api
from repro.models import common
from repro.optim import adamw


def build_session(arch, shape, ckpt_dir, seed=0):
    cfg = arch.model
    pol = common.resolve_arch_policy(arch)
    api = get_api(cfg)
    params = api["init"](jax.random.key(seed), cfg, pol)
    opt_state = adamw.init_opt_state(params)
    start_step = 0
    if ckpt_dir and ckpt.latest_steps(ckpt_dir):
        try:
            start_step, (params, opt_state), _ = ckpt.restore(
                ckpt_dir, (params, opt_state))
            print(f"[train] resumed from step {start_step}")
        except ckpt.CorruptCheckpoint as e:
            # every published step failed verification: the run is still
            # recoverable — from scratch (the freshly initialized params
            # above), which beats dying with data on disk we can't trust
            print(f"[train] no intact checkpoint, cold start: {e}")
    train_step = jax.jit(steps_lib.build_train_step(arch, shape),
                         donate_argnums=(0, 1))
    return params, opt_state, train_step, start_step


def run(arch, shape: ShapeCfg, steps: int, ckpt_dir: str | None,
        ckpt_every: int = 50, log_every: int = 10, seed: int = 0,
        fail_at: int | None = None,
        schedule: "ft.FaultSchedule | None" = None,
        record: dict | None = None):
    """One train session from the latest checkpoint to `steps`.

    `schedule` injects a deterministic `ft.FaultSchedule` (fire-once):
    preemptions raise through to the caller's `ft.run_with_retries`,
    stalls sleep through a step (watchdog food), ``ckpt_corrupt`` events
    corrupt the newest published checkpoint on disk — the restore-fallback
    path recovers from the last intact step on the next restart.
    `record`, when given, is filled in place (``starts``: the resume step
    of each session entry; ``faults``: (step, kind) fired) so chaos
    benches can check recovery against a fault-free oracle.
    """
    cfg = arch.model
    params, opt_state, train_step, start = build_session(
        arch, shape, ckpt_dir, seed)
    if record is not None:
        record.setdefault("starts", []).append(start)
        record.setdefault("faults", [])
    stream = SyntheticStream(
        DataCfg(vocab=cfg.vocab, seq_len=shape.seq_len,
                global_batch=shape.global_batch, seed=seed))
    loader = PrefetchLoader(stream, start_step=start)
    watchdog = ft.StepWatchdog()
    pending_save = None
    losses = []
    try:
        for i in range(start, steps):
            step_idx, host_batch = loader.get()
            assert step_idx == i
            batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
            if cfg.frontend is not None:
                n_vis = max(4, min(16, shape.seq_len // 4))
                batch["embeds"] = jnp.asarray(stream.frontend_batch(
                    i, n_vis, cfg.d_frontend or cfg.d_model))
                if cfg.family != "encdec":
                    batch["tokens"] = batch["tokens"][:, :-n_vis]
                    batch["labels"] = batch["labels"][:, n_vis:]
            if fail_at is not None and i == fail_at:
                raise ft.Preemption(f"injected failure at step {i}")
            if schedule is not None:
                for ev in schedule.pop(i):
                    if record is not None:
                        record["faults"].append((i, ev.kind))
                    if ev.kind == "stall":
                        time.sleep(float(ev.params.get("duration_s", 0.05)))
                    elif ev.kind == "ckpt_corrupt" and ckpt_dir:
                        # storage fault against the NEWEST published step;
                        # wait out an in-flight save so the corruption
                        # lands on a complete checkpoint (deterministic)
                        if pending_save is not None:
                            pending_save.join()
                            pending_save = None
                        ft.corrupt_checkpoint(
                            ckpt_dir, ev.params.get("mode", "bitflip"),
                            seed=int(ev.params.get("seed", 0)))
                    elif ev.kind == "preempt":
                        raise ft.Preemption(f"chaos preempt at step {i}")
                    # drift / explorer_outage target the serving half
            watchdog.start(i)
            params, opt_state, metrics = train_step(
                params, opt_state, batch, jnp.uint32(i))
            jax.block_until_ready(metrics["loss"])
            rep = watchdog.stop()
            losses.append(float(metrics["loss"]))
            if rep.is_straggler:
                print(f"[watchdog] step {i} straggler: "
                      f"{rep.duration:.2f}s vs p50 {rep.p50:.2f}s")
            if i % log_every == 0:
                print(f"[train] step {i} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"({rep.duration:.2f}s)")
            if ckpt_dir and (i + 1) % ckpt_every == 0:
                if pending_save is not None:
                    pending_save.join()
                pending_save = ckpt.save(ckpt_dir, i + 1,
                                         (params, opt_state),
                                         meta={"arch": cfg.name})
    finally:
        loader.close()
        if pending_save is not None:
            pending_save.join()
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--td", default=None,
                    choices=[None, "precise", "quant", "td"])
    ap.add_argument("--td-per-layer", default=None,
                    help="heterogeneous per-layer TD policies: inline sigma "
                    "list '0.5,1.0,...' or '@per_layer_policies.json' from "
                    "the Fig. 10 batched noise-tolerance search")
    td_cli.add_td_attn_arg(ap)
    td_cli.add_scenario_args(ap)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    arch = cfgs.get_smoke(args.arch) if args.smoke else cfgs.get(args.arch)
    arch = td_cli.apply_td_args(arch, args.td, args.td_per_layer,
                                args.scenario, args.corner,
                                td_attn=args.td_attn)
    shape = ShapeCfg("cli", args.seq, args.batch, "train")

    def session():
        return run(arch, shape, args.steps, args.ckpt_dir, seed=args.seed)

    _, losses = ft.run_with_retries(
        session, on_restart=lambda n, e: print(f"[ft] restart {n}: {e!r}"))
    n = max(1, len(losses) // 5)
    print(f"[train] done. loss first-5-avg={np.mean(losses[:n]):.4f} "
          f"last-5-avg={np.mean(losses[-n:]):.4f}")


if __name__ == "__main__":
    main()
