#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the program's widest gap and
the float8 control's, on several seeds, in one process, each with its
verdict.

    python chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13

For each seed it runs the cell's window (short, at the cell's own load)
and checks the sample of served tokens against the reference, as a
benchmark run does; then it puts the control in the program's place, the
same reference with every matmul input rounded through float8 e4m3, over
the same prompts and tokens, and judges the tokens it puts first by the
same verdict: the control has to come out not correct.  Prints one JSON
line per seed.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 1
    harness.compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               control=True)
        c = out["control"]
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": c["program"],
                          "control_correct": c["correct"],
                          "control": c["readings"],
                          "control_checks": c["checks"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
