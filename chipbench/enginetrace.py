"""The serving engine's own spans in a traced run, beside the device trace
that `devtrace` reduces.

The engine (`repro.launch.scheduler.ContinuousBatchingEngine`) writes a
host span named ``engine.<what>`` into the profiler's trace around each
tick, admission, admission phase and decode call, with stats: the
tick's ``queue`` and ``t`` (its `clock` at the start), the admission's
``rid`` and ``slot``, the prefill's true ``tokens`` and ``padded`` length,
the decode call's ``rows`` and ``kv_tokens``.  JAX's own
``backend_compile*`` host spans mark compilations (labelled
``compile``).  A program that writes no such spans leaves nothing here,
and every reader of them returns nothing.

`of(run)` finds the run's trace among the traced cells' directories (the
one whose harness spans are the run's), reads these spans once per run,
adds two labellings of the device's idle time to the run's breakdown and
logs a cross-check of the engine's stats against the harness's records.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import statistics
import sys
from pathlib import Path

import devtrace

ENGINE_PREFIX = "engine."
COMPILE_PREFIX = "backend_compile"
TRACES = Path(__file__).resolve().parent.parent / ".chipbench_out" / "trace"
# what an admission's idle time splits into: its phases and its own time
ADMIT_PARTS = ("engine.prep", "engine.prefill", "engine.insert",
               "engine.tok_write", "engine.first_token", "engine.admit")


@dataclasses.dataclass
class Engine:
    events: list       # (name, start, end, stats) by start; "compile" too

    def named(self, name: str) -> list:
        return [e for e in self.events if e[0] == name]

    def clock_offsets(self) -> list:
        """Start of each `engine.step` span less its `t` stamp: what
        maps the engine's clock onto the trace's."""
        return [a - st["t"] for _, a, _, st in self.named("engine.step")
                if "t" in st]

    @property
    def clock_offset(self) -> float | None:
        offsets = self.clock_offsets()
        return statistics.median(offsets) if offsets else None


def _times(e) -> tuple:
    return e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def load(path: str) -> tuple:
    """(harness spans as `devtrace.load` gives them, Engine) of a trace."""
    bench, events = [], []
    for plane in devtrace.profile(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(devtrace.SPAN_PREFIX):
                    bench.append((name[len(devtrace.SPAN_PREFIX):],)
                                 + _times(e))
                elif name.startswith(ENGINE_PREFIX):
                    events.append((name,) + _times(e) + (dict(e.stats),))
                elif name.startswith(COMPILE_PREFIX):
                    events.append(("compile",) + _times(e) + ({},))
    return (sorted(bench, key=lambda s: s[1]),
            Engine(sorted(events, key=lambda s: s[1])))


def in_window(reduced, events: list) -> list:
    w0, w1 = reduced.window
    return [e for e in events if w0 <= e[1] < w1]


def idle_in(reduced, events: list) -> float:
    """Device idle seconds inside the events' intervals, in the window."""
    w0, w1 = reduced.window
    iv = devtrace.merge([(max(e[1], w0), min(e[2], w1)) for e in events
                         if min(e[2], w1) > max(e[1], w0)])
    return sum(b - a for a, b in iv) - reduced.busy_within(iv)


def _labelled(reduced, eng: Engine) -> list:
    """Every span that may label idle time: (label, start, end)."""
    return list(reduced.spans) + [e[:3] for e in eng.events]


def _innermost(spans: list) -> str:
    """The latest to start (the shortest on a tie) of spans that hold a
    point, or "none"."""
    return max(spans, key=lambda s: (s[1], -s[2]))[0] if spans else "none"


def _busy_before(busy: list):
    """t -> busy seconds before t, for merged, sorted busy intervals."""
    starts = [a for a, _ in busy]
    cum = [0.0]
    for a, b in busy:
        cum.append(cum[-1] + b - a)

    def before(t: float) -> float:
        k = bisect.bisect_right(starts, t)
        return cum[k - 1] + min(t, busy[k - 1][1]) - busy[k - 1][0] \
            if k else 0.0
    return before


def idle_by_span(reduced, eng: Engine, top: int | None = 10) -> list:
    """Idle seconds in the window per innermost span label (harness spans
    by their bare name, the engine's by ``engine.<what>``, compilations as
    ``compile``, outside every span ``none``), the `top` largest."""
    spans = sorted(_labelled(reduced, eng), key=lambda s: s[1])
    w0, w1 = reduced.window
    cuts = sorted({w0, w1} | {t for _, a, b in spans for t in (a, b)
                              if w0 < t < w1})
    before = _busy_before(reduced.busy)
    totals: dict = {}
    active: list = []
    i = 0
    for p, q in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][1] <= p:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[2] > p]
        idle = (q - p) - (before(q) - before(p))
        label = _innermost(active)
        totals[label] = totals.get(label, 0.0) + idle
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t] for n, t in ranked if t > 0]


def idle_gaps(reduced, eng: Engine, top: int = 10) -> list:
    """The `top` longest idle gaps in the window, each labelled by the
    innermost span, engine spans included, that holds its midpoint."""
    spans = _labelled(reduced, eng)
    w0, w1 = reduced.window
    gaps, prev = [], w0
    for a, b in reduced.busy + [(w1, w1)]:
        a, b = max(a, w0), min(b, w1)
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        out.append([_innermost([s for s in spans if s[1] <= mid < s[2]]),
                    b - a])
    return out


def crosscheck(run, eng: Engine) -> dict:
    """The engine's stats against the harness's records: the spread of
    the clock offset over the `engine.step` spans, and each of the
    harness's decode calls in the traced part set beside the
    `engine.decode` span that holds it on the trace's clock.  Also the
    idle time of an admission, split by phase, per admission."""
    offsets = eng.clock_offsets()
    out = {"steps": len(offsets)}
    if not offsets:
        return out
    admits = in_window(run.trace, eng.named("engine.admit"))
    if admits:
        by = dict(idle_by_span(run.trace, eng, top=None))
        out["admits"] = len(admits)
        out["admit_idle_split_ms"] = {
            k: by.get(k, 0.0) / len(admits) * 1e3 for k in ADMIT_PARTS}
    out["clock_offset_spread_ms"] = (max(offsets) - min(offsets)) * 1e3
    spans = eng.named("engine.decode")
    starts = [a for _, a, _, _ in spans]
    calls = [d for d in run.decodes if run.in_traced(d.t)]
    same, offset = 0, statistics.median(offsets)
    for d in calls:
        x = d.t + offset
        k = bisect.bisect_right(starts, x) - 1
        if k >= 0 and x <= spans[k][2]:
            st = spans[k][3]
            same += (st.get("rows"), st.get("kv_tokens")) == \
                (len(d.kv_lens), sum(d.kv_lens))
    out.update(decode_spans=len(spans), harness_decodes=len(calls),
               decodes_equal=same)
    return out


_last: list = [None, None]     # (reduced trace, Engine) last read


def attach(run, eng: Engine) -> None:
    """Add the idle labellings to the run's breakdown, log the
    cross-check, and keep `eng` as the run's engine spans."""
    run.trace.breakdown["idle_by_span"] = idle_by_span(run.trace, eng)
    run.trace.breakdown["idle_gaps_engine"] = idle_gaps(run.trace, eng)
    print("chipbench: engine", json.dumps(crosscheck(run, eng)),
          file=sys.stderr, flush=True)
    _last[:] = [run.trace, eng]


def of(run) -> Engine | None:
    """The run's engine spans, or None where its trace holds none."""
    if run.trace is None:
        return None
    if _last[0] is not run.trace:
        _last[:] = [run.trace, None]
        paths = glob.glob(str(TRACES / "**" / "*.xplane.pb*"),
                          recursive=True)
        for path in sorted(paths, key=os.path.getmtime, reverse=True):
            bench, eng = load(path)
            if bench == run.trace.spans:
                attach(run, eng)
                break
    eng = _last[1]
    if eng is None or not any(e[0].startswith(ENGINE_PREFIX)
                              for e in eng.events):
        return None
    return eng
