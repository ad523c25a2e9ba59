#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate whose backlog
does not grow over the window.

    python chipbench/sweep.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> --rates 2,4,6,8

One process builds the cell's engine once (weights from the first seed)
and runs a window at each rate and seed in turn, with the cell's own mix
at that rate and the seed's order.  For each it prints the requests due
and finished, the backlog (due and unfinished) and the queue (due and
not admitted) at the window's half and end, tokens per second and the
tails.  The cell's rate is then fixed in its traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import record
import refs
import run as harness
import traffic


def reset(eng) -> None:
    eng.queue.clear()
    for s in eng.slots:
        s.request = None
    eng.done.clear()
    eng._reset_device_state()


def backlog_at(run: record.Run, t: float) -> int:
    """Requests due by t and not finished by t."""
    return sum(1 for r in run.requests if r.due <= t and
               not (len(r.tokens) >= r.output_len and r.tokens[-1] <= t))


def queued_at(run: record.Run, t: float) -> int:
    """Requests due by t and not admitted by t."""
    return sum(1 for r in run.requests if r.due <= t and
               (r.admitted is None or r.admitted > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 1
    harness.compile_cache()
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["name"] == args.workload)
    cfg = harness.load_json(harness.BENCH / "configs" /
                            f"{cell['config']}.json")
    mix = traffic.load(cell["traffic"])
    from repro.launch.scheduler import ContinuousBatchingEngine
    seeds = [int(s) for s in args.seeds.split(",")]
    params = refs.load(cfg).make_weights(cfg, harness.seed_words(seeds[0]))
    sizes = mix["engine"]
    eng = ContinuousBatchingEngine(harness.build_arch(cfg),
                                   capacity=sizes["capacity"],
                                   s_cache=sizes["s_cache"],
                                   prompt_pad=sizes["prompt_pad"],
                                   params=params, clock=time.perf_counter)
    eng.warmup()
    harness.warm_slots(eng)
    metrics = {n: harness.load_module(harness.BENCH / "metrics" / f"{n}.py")
               for n in ("tokens_per_s", "ttft_p95_ms", "itl_p95_ms")}
    admit0, decode0 = eng._admit, eng._decode
    for rate, seed in ((float(r), s) for r in args.rates.split(",")
                       for s in seeds):
        reset(eng)
        eng._admit, eng._decode = admit0, decode0
        m = json.loads(json.dumps(mix))
        m["arrivals"]["rate"] = rate
        run = record.Run(cfg=cfg, mix=m, seconds=args.seconds)
        h = harness.Harness(run, eng, seed, cfg["vocab_size"], False)
        specs = traffic.schedule(m, seed, args.seconds)
        harness.drive(h, specs, args.seconds, None, args.seconds)
        row = {"rate": rate, "seed": seed, "due": len(specs),
               "finished": sum(1 for r in run.requests if r.finished),
               "backlog_half": backlog_at(run, run.t0 + args.seconds / 2),
               "backlog_end": backlog_at(run, run.t_end),
               "queued_half": queued_at(run, run.t0 + args.seconds / 2),
               "queued_end": queued_at(run, run.t_end),
               "ttft_p50_ms": record.percentile(record.ttft_ms(run), 50),
               **{n: mod.read(run) for n, mod in metrics.items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
