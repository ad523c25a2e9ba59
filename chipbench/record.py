"""What one run leaves for the metric readers: the requests with their
times, the harness's host spans, the decode calls with their occupancy,
and, in a traced run, the reduced device trace.  All times are
`time.perf_counter()` seconds."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Req:
    index: int
    prompt_len: int
    output_len: int
    due: float                   # when it was due
    sent: float | None = None
    admitted: float | None = None
    slot: int | None = None
    tokens: list = dataclasses.field(default_factory=list)   # times
    ids: list = dataclasses.field(default_factory=list)      # token ids

    @property
    def finished(self) -> bool:
        return len(self.ids) >= self.output_len


@dataclasses.dataclass
class Decode:
    """One call of the decode program: when, and the KV length of each
    occupied row after it."""
    t: float
    kv_lens: tuple


@dataclasses.dataclass
class Run:
    cfg: dict
    mix: dict
    seconds: float
    t0: float = 0.0
    t_end: float = 0.0
    setup_s: float = 0.0
    requests: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)  # (name, a, b)
    decodes: list = dataclasses.field(default_factory=list)
    traced: tuple | None = None       # (a, b) host times of the trace
    trace: object = None              # trace.Reduced, traced runs only
    peaks: dict | None = None         # this device's row of peaks.json

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t_end

    def in_traced(self, t: float) -> bool:
        return self.traced is not None and self.traced[0] <= t < self.traced[1]


def percentile(values, q: float) -> float | None:
    """numpy's linear-interpolation percentile; None for no samples."""
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def ttft_ms(run: Run) -> list:
    """Time to first token of every request due in the window, from its
    due time, in ms; one with no first token by the window's end enters
    with its wait so far."""
    waits = []
    for r in run.requests:
        if not run.in_window(r.due):
            continue
        first = r.tokens[0] if r.tokens and r.tokens[0] < run.t_end \
            else run.t_end
        waits.append((first - r.due) * 1e3)
    return waits
