"""The CPU rehearsal's small model and mixes: the registry's qwen2.5-3b
smoke preset (2 layers, width 64) under each cell's arrival kind, at
sizes a test run can hold.  Its configuration is the cell's, with the
program's value of each key the family states (`refs.load(cfg).stated`)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import refs  # noqa: E402
import run as harness  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
         "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def assign(cfg: dict, values: dict) -> dict:
    """`cfg` with each published key set to its value; a dotted key names
    one inside a nested group, made where it is missing."""
    for key, value in values.items():
        *groups, leaf = key.split(".")
        group = cfg
        for g in groups:
            group = group.setdefault(g, {})
        group[leaf] = value
    return cfg


def arch_and_cfg():
    import repro.configs as cfgs
    from repro.launch import td_cli
    arch = td_cli.apply_td_args(cfgs.get_smoke("qwen2.5-3b"), "precise",
                                None)
    cfg = json.loads((BENCH / "configs" /
                      "qwen2.5-3b-precise.json").read_text())
    return arch, assign(cfg, refs.load(cfg).stated(arch.model))


MIXES = {
    "poisson": {"engine": {"capacity": 4, "s_cache": 128, "prompt_pad": 32},
                "prompt": {"median": 16, "sigma": 0.8, "min": 4, "max": 32},
                "output": {"median": 8, "sigma": 0.8, "min": 2, "max": 24},
                "arrivals": {"kind": "poisson", "rate": 4.0},
                "trace_seconds": 2, "check": {"tokens": 120}},
    "onoff": {"engine": {"capacity": 4, "s_cache": 128, "prompt_pad": 32},
              "prompt": {"median": 24, "sigma": 0.4, "min": 8, "max": 32},
              "output": {"median": 4, "sigma": 0.6, "min": 2, "max": 8},
              "arrivals": {"kind": "onoff", "rate": 3.0, "burst": 6,
                           "burst_s": 0.25},
              "trace_seconds": 2, "check": {"tokens": 60}},
}


CELLS = {"poisson": "qwen2.5-3b-precise.chat",
         "onoff": "qwen2.5-3b-precise.burst"}


def run(kind: str, seed: int = 7, seconds: float = 3.0,
        trace: bool = False, control: bool = False, cfg: dict | None = None):
    """One run of the cell whose arrivals are `kind`, at the smoke size;
    `cfg` replaces the smoke configuration."""
    arch, smoke_cfg = arch_and_cfg()
    cfg = cfg or smoke_cfg
    return harness.run_cell(CELLS[kind], seed, seconds, trace, cfg=cfg,
                            arch=arch, mix=MIXES[kind], control=control,
                            peaks=PEAKS)
