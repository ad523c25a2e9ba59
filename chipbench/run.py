#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`chipbench/configs/<config>.json`) under a traffic mix
(`chipbench/traffic/<traffic>.json`).  The run makes the weights from the
seed on the device, builds the serving engine at the mix's sizes
(`repro.launch.scheduler.ContinuousBatchingEngine`, the engine behind
``python -m repro.launch.serve --scheduler``), warms its programs, opens
the window and, for ``--seconds``, submits each request when it is due and
steps the engine while it has work.  Then it reads the device's peak
memory, frees the engine, and checks a sample of the served tokens
against the plain reference.  The weight draw, the reference and what the
program is held to come from the configuration's family,
`chipbench/refs/<model_type>.py` (`refs.load`).

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
first ``trace_seconds`` of the window (the window is then lengthened by
the time writing the trace takes).  Each metric is a reader of its own,
`chipbench/metrics/<name>.py` (a metric split by cell, `<base>.<cell>`,
may share the reader of `<base>`), listed for the cell by
BENCHMARK.json.

The last line of standard output is the result, one JSON object.  The
run exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import record  # noqa: E402
import refs  # noqa: E402
import traffic  # noqa: E402

OUT = ROOT / ".chipbench_out"


def log(*parts) -> None:
    print("chipbench:", *parts, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The reader of metric `name`: `metrics/<name>.py`, or, for a name
    split by cell (`<base>.<cell>`) that has no reader of its own, the
    reader of `<base>`."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(path)


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def seed_words(seed: int) -> tuple[int, int]:
    return int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
def build_arch(cfg: dict, arch=None):
    """The registry's architecture in the configuration's mode (or `arch`,
    the CPU rehearsal's), held to the arithmetic the configuration states:
    each published key its family states, and the mode of the matmuls."""
    from repro.models import common
    if arch is None:
        import repro.configs as cfgs
        from repro.launch import td_cli
        arch = td_cli.apply_td_args(cfgs.get(cfg["registry"]), cfg["mode"],
                                    None)
    stated = refs.load(cfg).stated(arch.model)
    off = {k: (v, refs.at(cfg, k)) for k, v in stated.items()
           if refs.at(cfg, k) != v}
    if off:
        raise RuntimeError(f"the program departs from the configuration: "
                           f"{off}")
    pol = common.resolve_arch_policy(arch)
    if pol.mode != cfg["mode"]:
        raise RuntimeError(f"expected {cfg['mode']} matmuls, got "
                           f"{pol.mode}")
    return arch


def count_compiles():
    """A counter of XLA compilations, from JAX's monitoring events."""
    import jax
    box = {"n": 0}

    def on_event(event, *_args, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return box


class Harness:
    """The engine, the traffic and the host-side records of one run."""

    def __init__(self, run: record.Run, eng, seed: int, vocab: int,
                 trace: bool):
        self.run, self.eng, self.seed, self.vocab = run, eng, seed, vocab
        self.trace = trace
        self.by_rid: dict = {}
        self._wrap_engine()

    @contextmanager
    def span(self, name: str):
        import jax
        a = time.perf_counter()
        if self.trace:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        self.run.spans.append((name, a, time.perf_counter()))

    def _wrap_engine(self) -> None:
        """Record each admission's slot and span, and each decode call's
        occupancy, around the engine's own calls."""
        eng = self.eng
        admit, decode = eng._admit, eng._decode

        def admit_spanned(slot):
            rec = self.by_rid.get(eng.queue[0].rid)
            with self.span("admit"):
                admit(slot)
            if rec is not None:
                rec.slot = slot.index

        def decode_recorded(*args):
            kv = tuple(len(s.request.prompt) + len(s.request.generated)
                       for s in eng.slots if not s.free)
            self.run.decodes.append(record.Decode(time.perf_counter(), kv))
            return decode(*args)

        eng._admit = admit_spanned
        eng._decode = decode_recorded

    def submit(self, spec: traffic.Spec, due: float) -> None:
        from repro.launch.scheduler import Request
        prompt = traffic.prompt_tokens(self.seed, spec.index,
                                       spec.prompt_len, self.vocab)
        req = Request(rid=spec.index, prompt=prompt,
                      max_new_tokens=spec.output_len, arrival_s=due)
        rec = record.Req(spec.index, spec.prompt_len, spec.output_len, due)
        with self.span("submit"):
            self.eng.submit(req)
        rec.sent = time.perf_counter()
        self.by_rid[spec.index] = rec
        self.run.requests.append(rec)

    def step(self) -> None:
        with self.span("step"):
            self.eng.step()

    def harvest(self) -> None:
        """Copy the engine's per-token times and ids into the records."""
        eng = self.eng
        live = list(eng.done.values()) + [s.request for s in eng.slots
                                          if not s.free]
        for req in live:
            rec = self.by_rid.get(req.rid)
            if rec is not None:
                rec.admitted = req.t_admitted
                rec.tokens = list(req.token_s)
                rec.ids = list(req.generated)


def warm_slots(eng) -> None:
    """Compile the engine's per-slot token write for every slot (the index
    is a static of that small program)."""
    import jax
    import jax.numpy as jnp
    tok = jnp.zeros((1, 1), jnp.int32)
    for i in range(eng.capacity):
        eng._tok = eng._tok.at[i].set(tok[0])
    jax.block_until_ready(eng._tok)


def drive(h: Harness, specs: list, seconds: float,
          trace_dir: Path | None, trace_s: float) -> None:
    """The measured window: each request is submitted when it is due, and
    the engine stepped while it has work."""
    import jax
    run, eng = h.run, h.eng
    nxt = 0
    run.setup_s = time.perf_counter() - T_START
    run.t0 = time.perf_counter()
    run.t_end = run.t0 + seconds
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(str(trace_dir))
        run.traced = (run.t0, run.t0 + trace_s)
    while True:
        now = time.perf_counter()
        if tracing and now >= run.traced[1]:
            with h.span("trace_stop"):
                jax.profiler.stop_trace()
            tracing = False
            run.traced = (run.traced[0], now)
            # writing the trace stalls the loop for tens of seconds: the
            # window (whose end-to-end metrics a traced run does not
            # report) is lengthened by the stall, so the run still serves
            # its full length for the check of `correct`
            run.t_end += time.perf_counter() - now
        if now >= run.t_end:
            break
        while nxt < len(specs) and run.t0 + specs[nxt].due_s <= now:
            h.submit(specs[nxt], run.t0 + specs[nxt].due_s)
            nxt += 1
        if eng.queue or eng.active:
            h.step()
        else:
            wake = run.t_end if nxt >= len(specs) else \
                run.t0 + specs[nxt].due_s
            if tracing:
                wake = min(wake, run.traced[1])
            with h.span("sleep"):
                time.sleep(max(0.0, wake - time.perf_counter()))
    if tracing:
        with h.span("trace_stop"):
            jax.profiler.stop_trace()
        run.traced = (run.traced[0], time.perf_counter())
    h.harvest()


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def check_sample(run: record.Run, seed: int, n_tokens: int) -> list:
    """Finished requests to check, drawn from the seed: the one with the
    most served tokens first, then others until `n_tokens` are served."""
    done = [r for r in run.requests if r.finished and r.slot is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.output_len, r.index))
    rest = [r for r in done if r is not longest]
    order = traffic._rng(seed, 3).permutation(len(rest))
    out, served = [longest], longest.output_len
    for i in order:
        if served >= n_tokens:
            break
        out.append(rest[i])
        served += rest[i].output_len
    return out


def check(run: record.Run, params, cfg: dict, s_cache: int, seed: int,
          control: bool = False) -> dict:
    """Reference gaps of the sampled requests' served tokens: how far
    each served token's reference logit lies below the reference's best.
    With `control` also the control's: the same gap of the token that the
    float8 reference puts first, on the same prompts and tokens."""
    from refs.common import gaps
    n_rows = -(-run.mix["output"]["max"] // 128) * 128
    ref = refs.load(cfg).Reference(cfg, s_cache, min(n_rows, s_cache))
    vocab = cfg["vocab_size"]
    out = {"served": [], "control": [], "control_ids": []}
    for r in check_sample(run, seed, run.mix["check"]["tokens"]):
        ids = np.asarray(r.ids[:r.output_len], np.int64)
        prompt = traffic.prompt_tokens(seed, r.index, r.prompt_len, vocab)
        toks = np.zeros((s_cache,), np.int32)
        seq = np.concatenate([prompt, np.clip(ids[:-1], 0, vocab - 1)])
        toks[:len(seq)] = seq
        lo, hi = r.prompt_len - 1, r.prompt_len - 1 + len(ids)
        lg = ref.logits(params, toks, lo, hi)
        out["served"].append(gaps(lg, np.clip(ids, 0, vocab - 1)))
        if control:
            pick = ref.logits(params, toks, lo, hi, lowp=True).argmax(-1)
            out["control"].append(gaps(lg, pick))
            out["control_ids"].append(pick)
    return out


def judge(gaps: list, ids, cfg: dict) -> tuple:
    """The verdict on one set of chosen tokens: (correct, checks), each
    number compared beside its limit.  `gaps` are the reference gaps of
    the checked tokens, `ids` every token id chosen."""
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    ids = np.asarray(ids, np.int64)
    bad = int(((ids < 0) | (ids >= cfg["vocab_size"])).sum())
    limit = cfg["check"]["gap_max"]
    gap_max = float(g.max()) if len(g) else None
    checks = {"gap_max": {"value": gap_max, "limit": limit},
              "tokens_checked": {"value": int(len(g)), "limit": 1},
              "ids_outside_vocab": {"value": bad, "limit": 0}}
    correct = gap_max is not None and gap_max <= limit and len(g) >= 1 \
        and bad == 0
    return bool(correct), checks


def readings(gaps: list) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    if not len(g):
        return {"tokens": 0}
    return {"tokens": int(len(g)), "gap_max": float(g.max()),
            "gap_mean": float(g.mean()), "top1_share": float((g == 0).mean())}


def kv_fill(run: record.Run, capacity: int, s_cache: int) -> dict:
    """How full the KV pool was over the window's decode steps: cached
    tokens over capacity x s_cache, and occupied rows over capacity."""
    calls = [d for d in run.decodes if run.in_window(d.t)]
    if not calls:
        return {}
    tok = [sum(d.kv_lens) / (capacity * s_cache) for d in calls]
    rows = [len(d.kv_lens) / capacity for d in calls]
    return {"kv_fill_mean": float(np.mean(tok)),
            "kv_fill_max": float(np.max(tok)),
            "rows_mean": float(np.mean(rows))}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             bench: dict | None = None, cfg: dict | None = None,
             arch=None, mix: dict | None = None, control: bool = False,
             devices=None, peaks: dict | None = None) -> dict:
    """One run of a cell; returns the result object.  `cfg`, `arch`, `mix`
    and `peaks` replace the cell's configuration, the registry's
    architecture, the traffic mix and the device's peaks (the CPU
    rehearsal's small model).  With `control` the result also carries
    `control`: the float8 control put in the program's place, judged by
    the same verdict, and the program's readings on the same sample."""
    import jax
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg = cfg or load_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = mix or traffic.load(cell["traffic"])
    family = refs.load(cfg)
    compiles = count_compiles()
    t_import = time.perf_counter() - T_START

    t = time.perf_counter()
    arch = build_arch(cfg, arch)
    params = family.make_weights(cfg, seed_words(seed))
    jax.block_until_ready(params)
    from repro.launch.scheduler import ContinuousBatchingEngine
    sizes = mix["engine"]
    eng = ContinuousBatchingEngine(arch, capacity=sizes["capacity"],
                                   s_cache=sizes["s_cache"],
                                   prompt_pad=sizes["prompt_pad"],
                                   params=params, clock=time.perf_counter)
    got = {"capacity": eng.capacity, "s_cache": eng.s_cache,
           "prompt_pad": eng.prompt_pad}
    if got != sizes:
        raise RuntimeError(f"engine sizes {got} differ from the mix's "
                           f"{sizes}")
    t_init = time.perf_counter() - t
    t = time.perf_counter()
    eng.warmup()
    warm_slots(eng)
    t_warm = time.perf_counter() - t

    run = record.Run(cfg=cfg, mix=mix, seconds=seconds)
    h = Harness(run, eng, seed, cfg["vocab_size"], trace)
    specs = traffic.schedule(mix, seed, seconds)
    trace_dir = None
    if trace:
        trace_dir = OUT / "trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    t = time.perf_counter()
    n_before = compiles["n"]
    drive(h, specs, seconds, trace_dir,
          float(mix.get("trace_seconds", seconds)))
    t_fill = run.t0 - t
    in_window = compiles["n"] - n_before
    print(f"compilations_in_window={in_window}", flush=True)
    log(f"setup_s={run.setup_s:.3f} import_s={t_import:.3f} "
        f"init_s={t_init:.3f} warmup_s={t_warm:.3f} fill_s={t_fill:.3f}")
    log("kv", json.dumps(kv_fill(run, eng.capacity, eng.s_cache)))

    devices = devices or jax.local_devices()[:cell["chips"]]
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}

    run.peaks = peaks if peaks is not None else device_peaks(dev.device_kind)
    if trace:
        import devtrace
        run.trace = devtrace.reduce(trace_dir)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s

    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        value = reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    attempted = sum(1 for r in run.requests if run.in_window(r.due))
    s_cache = eng.s_cache
    del eng, h
    gc.collect()
    t = time.perf_counter()
    res = check(run, params, cfg, s_cache, seed, control=control)
    check_s = time.perf_counter() - t
    correct, checks = judge(res["served"],
                            [i for r in run.requests for i in r.ids], cfg)
    log("check", json.dumps({**readings(res["served"]),
                             "check_s": check_s}))
    out = {"correct": correct, "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        out["breakdown"] = run.trace.breakdown
    if control:
        c_correct, c_checks = judge(
            res["control"], np.concatenate(res["control_ids"])
            if res["control_ids"] else [], cfg)
        out["control"] = {"correct": c_correct, "checks": c_checks,
                          "readings": readings(res["control"]),
                          "program": readings(res["served"])}
    out["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr,
              flush=True)
    return out


def device_peaks(kind: str) -> dict:
    """This device's row of peaks.json; a kind not in it is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def compile_cache() -> str:
    """JAX's persistent cache: JAX_COMPILATION_CACHE_DIR where set, else
    a fixed directory inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"no cell named {args.workload!r}")
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU found (platform {devices[0].platform}); nothing run")
        return 1
    if len(devices) < cells[args.workload]["chips"]:
        log(f"the cell needs {cells[args.workload]['chips']} chips, "
            f"found {len(devices)}")
        return 1
    log("compile cache", compile_cache())
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   bench=bench,
                   devices=devices[:cells[args.workload]["chips"]])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
