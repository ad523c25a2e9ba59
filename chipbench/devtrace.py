"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics
read: the device's busy intervals, each program execution and each
kernel with its device time, and the longest idle gaps labelled by what
the harness was doing on the host.

On a TPU each chip is a plane named ``/device:TPU:<n>``.  Its line
``XLA Modules`` holds one event per program execution, named after the
jitted function (``jit_serve_step(<hash>)``), and its line ``XLA Ops`` one
event per HLO operation, named by the instruction's text
(``%_td_vmm_call.28 = f32[8,128]... custom-call(...)``); the reduction
keeps the instruction's name (``_td_vmm_call.28``).  A Pallas kernel is a
custom call named after the jitted wrapper that launches it:
``_td_vmm_call``, ``_decode_gqa_call``, ``_flash_attn_call``.
Operations are given to the program execution whose interval holds their
start.  The harness's own spans are host events named ``bench.<what>``
on the host plane, on the same clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
from pathlib import Path

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    name: str
    start: float                 # seconds on the trace's clock
    end: float
    module: str | None = None


@dataclasses.dataclass
class Reduced:
    window: tuple                # (start, end) of the traced window
    busy: list                   # merged busy intervals of chip 0
    busy_s: float                # busy seconds in the window, mean of chips
    window_s: float
    modules: list                # Op per program execution
    ops: list                    # Op per device operation
    spans: list                  # (name, start, end) harness host spans
    breakdown: dict

    def executions(self, module: str) -> list:
        return [m for m in self.modules if module in m.name]

    def module_time(self, module: str) -> float:
        return sum(m.end - m.start for m in self.executions(module))

    def kernel_time(self, kernel: str, module: str | None = None) -> float:
        return sum(o.end - o.start for o in self.ops if kernel in o.name
                   and (module is None or (o.module or "").find(module) >= 0))

    def spans_named(self, name: str) -> list:
        return [(a, b) for n, a, b in self.spans if n == name]

    def kernel_calls(self, kernel: str, module: str | None = None) -> int:
        return sum(1 for o in self.ops if kernel in o.name
                   and (module is None or (o.module or "").find(module) >= 0))

    def busy_within(self, intervals: list) -> float:
        """Busy seconds inside a list of (start, end) intervals."""
        return sum(overlap(self.busy, [iv]) for iv in merge(intervals))

    def idle_share(self, span: str = "step") -> float | None:
        """Percent of the time inside the harness's `span` spans in which
        no operation ran on the device."""
        spans = self.spans_named(span)
        total = sum(b - a for a, b in merge(spans))
        if not total:
            return None
        return 100.0 * (1.0 - self.busy_within(spans) / total)


def merge(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def overlap(merged: list, intervals: list) -> float:
    """Length of the intersection of sorted, merged intervals with
    others."""
    ends = [d for _, d in merged]
    total = 0.0
    for a, b in intervals:
        for c, d in merged[bisect.bisect_right(ends, a):]:
            if c >= b:
                break
            total += min(b, d) - max(a, c)
    return total


def find_xplane(trace_dir: Path) -> str:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb*"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(text: str) -> str:
    """``%name.3 = f32[...] op(...)`` -> ``name.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def kind(name: str) -> str:
    """An operation's name without its instance number: ``fusion.12`` ->
    ``fusion``."""
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


def _events(line, rename=lambda n: n):
    for e in line.events:
        yield (rename(e.name), e.start_ns * 1e-9,
               (e.start_ns + e.duration_ns) * 1e-9)


def profile(path: str):
    """The trace at `path`, a `.xplane.pb` or a gzipped one."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(path: str):
    """(device planes: {name: {line: [(name, a, b)]}}, host spans)."""
    pd = profile(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = {
                ln.name: list(_events(ln, op_name if ln.name == OPS_LINE
                                      else (lambda n: n)))
                for ln in plane.lines if ln.name in (MODULES_LINE, OPS_LINE)}
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                spans.extend((n[len(SPAN_PREFIX):], a, b)
                             for n, a, b in _events(ln)
                             if n.startswith(SPAN_PREFIX))
    return devices, sorted(spans, key=lambda s: s[1])


def reduce_planes(devices: dict, spans: list, top: int = 10) -> Reduced:
    """The reduction of device planes and host spans (see `load`)."""
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    first = devices[sorted(devices)[0]]
    modules = [Op(n, a, b) for n, a, b in first.get(MODULES_LINE, [])]
    ops = [Op(n, a, b) for n, a, b in first.get(OPS_LINE, [])]
    mods = sorted(modules, key=lambda m: m.start)
    j = 0
    for o in sorted(ops, key=lambda o: o.start):
        while j < len(mods) and mods[j].end <= o.start:
            j += 1
        if j < len(mods) and mods[j].start <= o.start:
            o.module = mods[j].name
    step_like = [s for s in spans if s[0] != "trace_stop"]
    if step_like:
        window = (min(s[1] for s in step_like), max(s[2] for s in step_like))
    else:
        window = (min(o.start for o in ops), max(o.end for o in ops))
    busy_per_chip = []
    busy0: list = []
    for name in sorted(devices):
        iv = merge([(a, b) for _, a, b in devices[name].get(OPS_LINE, [])])
        if not busy0:
            busy0 = iv
        busy_per_chip.append(overlap(iv, [window]))
    busy_s = sum(busy_per_chip) / len(busy_per_chip)

    per_op: dict = {}
    for o in ops:
        k = kind(o.name)
        per_op[k] = per_op.get(k, 0.0) + (o.end - o.start)
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    prev = window[0]
    for a, b in busy0 + [(window[1], window[1])]:
        a, b = max(a, window[0]), min(b, window[1])
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        inside = [s for s in spans if s[1] <= mid < s[2]]
        # the innermost span: the latest to start among those holding it
        what = max(inside, key=lambda s: s[1])[0] if inside else "none"
        labelled.append([what, b - a])
    return Reduced(window=window, busy=busy0, busy_s=busy_s,
                   window_s=window[1] - window[0], modules=modules, ops=ops,
                   spans=spans,
                   breakdown={"device_ops": [[n, t] for n, t in device_ops],
                              "idle_gaps": labelled})


def reduce(trace_dir: Path) -> Reduced:
    devices, spans = load(find_xplane(trace_dir))
    return reduce_planes(devices, spans)
