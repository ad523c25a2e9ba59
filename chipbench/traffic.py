"""The one traffic generator: every mix is a data file of parameters.

A mix file (`chipbench/traffic/<name>.json`) gives the engine's sizes,
the prompt and output length distributions and the arrival process:

    {"engine": {"capacity": 48, "s_cache": 2048, "prompt_pad": 1536},
     "prompt": {"median": 384, "sigma": 0.8, "min": 32, "max": 1536},
     "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
     "arrivals": {"kind": "poisson", "rate": 3.0}, ...}

Arrival kinds, both open loops:

* ``poisson``: independent arrivals at ``rate`` requests per second.
* ``onoff``: bursts of ``burst`` requests, each burst due within
  ``burst_s`` seconds, bursts spaced so the mean is ``rate``.

Every seed gets the same set of lengths and gaps in another order:
lengths are the clipped lognormal's quantiles at (i + 0.5) / n, gaps the
exponential's, each list shuffled by the seed on its own.  So two seeds
offer the same work, and a seed changes only the order and the token
ids.  The shuffled gaps are a Poisson process's in all but their set:
any gap may follow any other, so short gaps cluster as often as
independent draws would let them.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

MIXES = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Spec:
    """One request as the generator offers it."""
    index: int
    prompt_len: int
    output_len: int
    due_s: float                 # offset from the window's opening


def load(name: str) -> dict:
    return json.loads((MIXES / f"{name}.json").read_text())


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n clipped lognormal quantiles at (i + 0.5) / n, as whole tokens."""
    nd = NormalDist(math.log(dist["median"]), dist["sigma"])
    xs = np.exp([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(xs), dist["min"], dist["max"]).astype(int)


def _gaps(rate: float, n: int) -> np.ndarray:
    """n exponential quantiles of mean 1 / rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *salt])


def schedule(mix: dict, seed: int, seconds: float) -> list[Spec]:
    """The requests a run offers, in the order they are sent."""
    arr = mix["arrivals"]
    kind = arr["kind"]
    if kind not in ("poisson", "onoff"):
        raise ValueError(f"unknown arrival kind {kind!r}")
    n = max(1, int(round(arr["rate"] * seconds)))
    rng = _rng(seed, 1)
    prompts = rng.permutation(_quantiles(mix["prompt"], n))
    outputs = rng.permutation(_quantiles(mix["output"], n))
    if kind == "poisson":
        gaps = rng.permutation(_gaps(arr["rate"], n))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        b, width = arr["burst"], arr["burst_s"]
        i = np.arange(n)
        due = (i // b) * (b / arr["rate"]) + (i % b + 0.5) / b * width
    return [Spec(i, int(p), int(o), float(d))
            for i, (p, o, d) in enumerate(zip(prompts, outputs, due))]


def prompt_tokens(seed: int, index: int, length: int, vocab: int
                  ) -> np.ndarray:
    """The token ids of request `index`, from the seed."""
    return _rng(seed, 2, index).integers(0, vocab, length, dtype=np.int32)
