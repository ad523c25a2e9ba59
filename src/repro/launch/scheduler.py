"""Continuous-batching TD serving engine.

Production LM traffic is ragged, bursty and concurrent; the fixed-batch
driver in `launch/serve.py` runs every request in lockstep and reports
energy per RUN.  This module is the real scheduler the ROADMAP north-star
asks for:

  * **Admission queue decoupled from step execution** — requests arrive on
    a FIFO queue (`submit`) at any time; the engine admits them into free
    slots between jitted steps (the actor/worker split: host-side intake
    and bookkeeping never block the device loop).
  * **Continuous batching with slot recycling** — a fixed-capacity batch
    of KV-cache slots; a finished request's slot is recycled to the next
    queued request immediately (bucketed prefill + insert), while the
    other slots keep decoding.  The flash-decode kernel's runtime
    ``kv_len`` SMEM operand masks every slot to its own valid prefix, so
    ANY mix of fill levels reuses one compiled program — zero recompiles.
  * **Block KV slots sized off the device** —
    `roofline.model.plan_kv_cache` rounds slots to block granularity and
    caps capacity against the device's own memory limit less the
    resident weights (held in the compute dtype, bf16).
  * **Per-request TD energy/latency telemetry** —
    `energy_meter.RequestMeter` attributes J/token to each request
    (prefill + decode tokens at the policy's operating point), and
    per-token wall-clock timestamps give per-request p50/p99 ms/token.
  * **Fault tolerance** — with a fault source (`inject` or a
    `ft.FaultSchedule`) the loop runs under `ft.run_with_retries`, with
    the `ft.StepWatchdog` timing every step; a mid-stream `Preemption`
    drains in-flight requests back onto the queue as continuations
    (prompt + tokens generated so far) instead of killing the run, so no
    admitted request is ever lost and greedy outputs are bit-identical to
    an uninterrupted run.  `run(schedule=...)` additionally consumes a
    deterministic `ft.FaultSchedule` (preemptions, stalls, drift
    excursions, explorer outages) — the chaos bench's injection path.
  * **Drift adaptation** (``adapt=True``) — the jitted decode step also
    returns the measured activation bit density (`ft.drift`, masked to
    OCCUPIED slots), smoothed by a `DriftEstimator`; on a threshold
    crossing the engine adapts in TWO PHASES.  Phase 1 (synchronous, the
    same decode step): re-resolve the per-layer (R, q) policies at the
    MEASURED statistics through `resolver` (default: the in-process
    explorer grid; a `ResolverChain` degrades a dead explorer server to
    the local cache) and hot-swap (sigma, q) as runtime operands of the
    SAME compiled decode program (zero recompiles), re-pricing the meter
    forward-only.  Phase 2 (staged, ``supply_span=True``): a
    `ft.StagedRebuild` worker re-resolves the full policy set SPANNING
    the scenario grid's Vdd axis (`solve_td_policies_over_vdd` — per-
    layer supply argmin at the measured statistics through the memoized
    explorer) and pre-prices the meter off-thread; the engine polls at
    each step boundary and installs (ops, policy, J/token rate)
    atomically between decode steps — still zero recompiles (Vdd never
    enters the compiled program; it is physics pricing + the solve's
    operating point), zero dropped requests, and a worker exception
    surfaces on the next step (`StagedRebuild.poll`, the checkpoint
    `SaveHandle` contract).  Every install lands in ``swap_log``;
    replaying that log through a second engine via ``scripted_swaps``
    (drift detection off, same compiled program) must reproduce greedy
    outputs bit-identically — the swap-parity oracle the drift bench
    gates.
  * **Traffic traces** (``run(trace=...)``) — a seeded `ft.TrafficTrace`
    drives the loop through multi-hour workload excursions: each
    segment's ``activity`` scales the measured bit density (the chaos
    ``drift`` event knob), ``sparsity`` overrides the weight-sparsity
    statistic fed to re-resolves, and ``load`` throttles admissions to a
    fraction of capacity.  Deterministic replay: same trace, same
    outputs.
  * **Profiler spans** — every tick (`engine.step`: queue length and a
    `clock` stamp), admission (`engine.admit` and its phases: prep,
    prefill with true and padded tokens, insert, tok_write, first_token)
    and decode call (`engine.decode` with rows and cached tokens,
    `engine.decode_wait`, `engine.harvest`) is a `jax.profiler` span, so
    any profiler capture shows where the host spends a tick beside the
    device ops.  Where the model holds recurrent state, `engine.decode` and
    `engine.insert` also carry its bytes (`state_bytes`).

Scope: decoder-family, token-only models whose mixers are attention and
Mamba2.  Each slot holds per-row KV for the attention layers and per-row
conv and SSM state for the Mamba2 layers, side by side in one state
pytree.  The bucketed prefill keeps pad junk out of both: causal masking
for KV (the slot's fill index is the true length), and, for Mamba2, a
prefill that stops the state at the true length; an admission overwrites
the slot's recurrent state, which no length masks.  RWKV6 and shared
attention (whose tied block's state is not per layer) are refused, as are
modality frontends, which need a pad-aware prefill.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro import ft
from repro.launch import steps as steps_lib
from repro.models import common, get_api, matmul_shapes, transformer
from repro.roofline import model as roofline_model
from repro.tdsim import policy as td_policy
from repro.tdsim.energy_meter import RequestMeter

__all__ = ["Request", "Slot", "ContinuousBatchingEngine"]


@dataclasses.dataclass
class Request:
    """One serving request.  `prompt` is the ORIGINAL prompt; on a
    preemption re-admission the engine prefills prompt + generated-so-far
    as a continuation, so `generated` survives restarts."""
    rid: int
    prompt: np.ndarray                 # int32 token ids, shape (L,)
    max_new_tokens: int
    arrival_s: float = 0.0
    # --- engine bookkeeping ---
    generated: list = dataclasses.field(default_factory=list)
    t_admitted: float | None = None
    t_first_token: float | None = None
    token_s: list = dataclasses.field(default_factory=list)  # per decoded tok
    readmissions: int = 0

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.generated)

    @property
    def context(self) -> np.ndarray:
        """Prompt extended with everything generated (continuation text)."""
        if not self.generated:
            return np.asarray(self.prompt, np.int32)
        return np.concatenate([np.asarray(self.prompt, np.int32),
                               np.asarray(self.generated, np.int32)])


@dataclasses.dataclass
class Slot:
    """One row of the fixed-capacity decode batch."""
    index: int
    request: Request | None = None

    @property
    def free(self) -> bool:
        return self.request is None


class ContinuousBatchingEngine:
    """Admission queue + slot-recycled continuous batching over one
    compiled prefill / insert / decode program triple."""

    def __init__(self, arch, capacity: int = 8, s_cache: int = 128,
                 prompt_pad: int | None = None, seed: int = 0,
                 eos_id: int | None = None, params=None,
                 meter_domain: str = "td", kv_block: int = 64,
                 continuous: bool = True, clock=time.monotonic,
                 adapt: bool = False, drift_threshold: float = 0.2,
                 resolver=None, supply_span: bool = True,
                 supply_resolver=None, vdd_grid=None,
                 scripted_swaps=None):
        cfg = arch.model
        if cfg.family != "decoder":
            raise ValueError("scheduler requires a decoder-family model")
        if cfg.frontend is not None:
            raise ValueError("scheduler serves token-only models (modality "
                             "frontends need pad-aware prefill)")
        bad = {cfg.mixer_at(i) for i in range(cfg.n_layers)} - {"attn",
                                                                "mamba2"}
        if bad:
            raise ValueError("scheduler serves attention and Mamba2 mixers "
                             f"(bucketed prefill); got {sorted(bad)}")
        self.arch, self.cfg = arch, cfg
        self.clock = clock
        self.eos_id = eos_id
        # continuous=False is the FIXED-BATCH baseline the serving bench
        # gates against: admission only when every slot is free (lockstep
        # batches, the slowest request holds the whole batch) — identical
        # compiled programs, only the scheduling policy differs
        self.continuous = continuous

        self.pol = common.resolve_arch_policy(arch)
        api = get_api(cfg)
        # independent key streams per consumer (params here; callers draw
        # prompt keys from their own split — see serve.run).  Serving holds
        # the weights in the compute dtype, as deployments do.
        if params is None:
            params = api["init"](jax.random.key(seed), cfg, self.pol,
                                 steps_lib.DTYPES[arch.train.compute_dtype])
        self.params = params

        # block KV slots: round the slot to blocks, cap capacity at what the
        # device's own memory limit admits once the weights are resident
        self.kv_plan = roofline_model.plan_kv_cache(
            cfg, capacity, s_cache, block=kv_block,
            weight_bytes=roofline_model.tree_bytes(params),
            hbm_bytes=roofline_model.device_bytes_limit())
        self.capacity = min(capacity, max(1, self.kv_plan.max_slots))
        self.s_cache = self.kv_plan.s_cache
        self.prompt_pad = min(prompt_pad or self.s_cache, self.s_cache)

        self._prefill = jax.jit(
            steps_lib.build_ragged_prefill_step(arch, self.prompt_pad))
        self._insert = jax.jit(steps_lib.build_insert_step(),
                               donate_argnums=(0,))
        shape = steps_lib.ShapeCfg("serve", self.s_cache, self.capacity,
                                   "decode")
        self.adapt = adapt
        if adapt:
            self._decode = jax.jit(
                steps_lib.build_adaptive_serve_step(arch, shape),
                donate_argnums=(2,))
        else:
            self._decode = jax.jit(steps_lib.build_serve_step(arch, shape),
                                   donate_argnums=(2,))

        pol0 = common.pol_at(self.pol, 0)
        self.meter = (RequestMeter(matmul_shapes(cfg), pol0,
                                   domain=meter_domain,
                                   sigma_max=(None if pol0.sigma_max
                                              is not None else 2.0))
                      if pol0.mode != "precise" else None)
        self.watchdog = ft.StepWatchdog()

        # drift adaptation + chaos-schedule state (host-side)
        self._ops = common.td_policy_ops(self.pol)
        self.resolver = (td_policy.solve_td_policies if resolver is None
                         else resolver)
        self.supply_span = bool(supply_span)
        self.vdd_grid = vdd_grid     # None = the paper's supply grid
        self.supply_resolver = (
            supply_resolver if supply_resolver is not None
            else lambda specs: td_policy.solve_td_policies_over_vdd(
                specs, self.vdd_grid))
        self.drift = (ft.DriftEstimator(anchor=pol0.p_x_one,
                                        threshold=drift_threshold)
                      if adapt else None)
        self._wsp = (ft.weight_bit_sparsity(self.params["embed"]["table"],
                                            pol0.bits_w) if adapt else None)
        self._drift_gain = 1.0       # chaos drift excursion multiplier
        self.adaptations = 0
        self.explorer_up = True
        self.on_outage = None        # callable(up: bool), wired by benches
        self.fault_log: list = []

        # staged supply swap + trace-replay state
        self._staged: ft.StagedRebuild | None = None
        self._adapt_gen = 0          # bumps per excursion; staleness check
        self._staged_gen = -1        # generation the in-flight rebuild saw
        self._last_measured: tuple[float, float] | None = None
        self.swap_log: list[dict] = []   # installs: step / kind / ops / vdds
        self.supply_spans = 0            # staged installs that moved a Vdd
        self.staged_installs = 0
        self.trace: "ft.TrafficTrace | None" = None
        # scripted_swaps: the swap-parity oracle. A recorded swap_log (or
        # [(step, ops)] pairs) replayed verbatim at step boundaries with
        # drift DETECTION disabled — the same compiled adaptive program,
        # only the swap machinery differs, so greedy outputs must match
        # the live run bit for bit.
        self._scripted = None
        if scripted_swaps is not None:
            ss = [(int(e["step"]), e["ops"]) if isinstance(e, dict)
                  else (int(e[0]), e[1]) for e in scripted_swaps]
            self._scripted = deque(sorted(ss, key=lambda e: e[0]))

        self.queue: deque[Request] = deque()
        self.slots = [Slot(i) for i in range(self.capacity)]
        self.done: dict[int, Request] = {}
        self.steps_run = 0
        self._reset_device_state()
        # bytes of one slot's recurrent state (Mamba2 conv + SSM; 0 for
        # pure attention): what an admission writes, what a tick updates
        recurrent = {jax.tree_util.DictKey("conv"),
                     jax.tree_util.DictKey("ssm")}
        self.state_bytes = sum(
            a.nbytes for path, a in
            jax.tree_util.tree_leaves_with_path(self._state)
            if path[-1] in recurrent) // self.capacity

    # ------------------------------------------------------------------
    # device state
    # ------------------------------------------------------------------
    def _reset_device_state(self) -> None:
        caches = transformer.init_caches(self.capacity, self.s_cache,
                                         self.cfg, jnp.bfloat16,
                                         pol=self.pol, per_row_idx=True)
        self._state = {"layers": caches, "enc_out": None}
        self._tok = jnp.zeros((self.capacity, 1), jnp.int32)

    # ------------------------------------------------------------------
    # intake (the "actor" side: host-only, never touches the device loop)
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.context) + max(0, req.remaining) > self.s_cache:
            raise ValueError(
                f"request {req.rid}: context {len(req.context)} + "
                f"{req.remaining} new tokens exceeds the {self.s_cache}"
                "-token slot")
        self.queue.append(req)

    def submit_all(self, reqs) -> None:
        for r in reqs:
            self.submit(r)

    # ------------------------------------------------------------------
    # admission: bucketed prefill into a free slot
    # ------------------------------------------------------------------
    def _admit(self, slot: Slot) -> None:
        with TraceAnnotation("engine.admit", rid=self.queue[0].rid,
                             slot=slot.index):
            req = self.queue.popleft()
            ctx = req.context
            with TraceAnnotation("engine.prep"):
                padded = np.zeros((1, self.prompt_pad), np.int32)
                padded[0, :len(ctx)] = ctx
                ids = jnp.asarray(padded)
                n = jnp.asarray(len(ctx), jnp.int32)
            with TraceAnnotation("engine.prefill", tokens=len(ctx),
                                 padded=self.prompt_pad):
                tok, pstate = self._prefill(self.params, ids, n)
            with TraceAnnotation("engine.insert", **self._state_stat(1)):
                self._state = self._insert(self._state, pstate,
                                           jnp.asarray(slot.index, jnp.int32),
                                           n)
            with TraceAnnotation("engine.tok_write"):
                self._tok = self._tok.at[slot.index].set(tok[0])
            slot.request = req
            now = self.clock()
            if req.t_admitted is None:
                req.t_admitted = now
            if self.meter is not None:
                self.meter.on_prefill(req.rid, len(ctx))
            # the prefill's argmax IS this request's next token
            with TraceAnnotation("engine.first_token"):
                first = int(tok[0, 0])
            self._record_token(req, first, now)

    def _state_stat(self, rows: int) -> dict:
        """`state_bytes` of `rows` slots, for a span; none for a model
        without recurrent state."""
        return {"state_bytes": rows * self.state_bytes} \
            if self.state_bytes else {}

    def _record_token(self, req: Request, token: int, now: float) -> None:
        req.generated.append(token)
        req.token_s.append(now)
        if req.t_first_token is None:
            req.t_first_token = now
        if self.meter is not None:
            self.meter.on_decode(req.rid)

    def _finished(self, req: Request, last: int) -> bool:
        return req.remaining <= 0 or (self.eos_id is not None
                                      and last == self.eos_id)

    def _retire_or_keep(self, slot: Slot) -> None:
        req = slot.request
        if req is not None and self._finished(req, req.generated[-1]):
            self.done[req.rid] = req
            slot.request = None        # recycled on the next admit round

    # ------------------------------------------------------------------
    # the worker loop: admit -> one batched decode step -> harvest
    # ------------------------------------------------------------------
    @property
    def active(self) -> list[Slot]:
        return [s for s in self.slots if not s.free]

    def step(self) -> bool:
        """One scheduler tick.  Returns False when no work remains.

        Each tick, admission and decode call is a profiler span
        (`engine.*`, on the host thread beside the device ops): any
        `jax.profiler` capture carries them, and with none they cost about
        a microsecond each.  The tick's `t` stamp ties `self.clock` to the
        trace's clock."""
        with StepTraceAnnotation("engine.step", step_num=self.steps_run,
                                 queue=len(self.queue), t=self.clock()):
            return self._tick()

    def _tick(self) -> bool:
        # staged supply swaps and scripted (oracle) swaps install HERE, at
        # the step boundary: the decode below is the first to see new ops
        self._poll_staged()
        if self._scripted is not None:
            while self._scripted and self._scripted[0][0] <= self.steps_run:
                _, ops = self._scripted.popleft()
                self._ops = jnp.asarray(ops, jnp.float32)
        seg = self.trace.at(self.steps_run) if self.trace is not None \
            else None
        if self.continuous or not self.active:
            budget = self.capacity if seg is None else \
                max(1, int(np.ceil(seg.load * self.capacity)))
            for slot in self.slots:
                if budget <= 0:
                    break
                if slot.free and self.queue:
                    self._admit(slot)
                    self._retire_or_keep(slot)   # max_new_tokens == 1
                    budget -= 1
        active = self.active
        if not active:
            return bool(self.queue)
        self.watchdog.start(self.steps_run)
        stats = {"rows": len(active), **self._state_stat(len(active))}
        if TraceAnnotation.is_enabled():
            stats["kv_tokens"] = sum(len(s.request.prompt)
                                     + len(s.request.generated)
                                     for s in active)
        with TraceAnnotation("engine.decode", **stats):
            if self.adapt:
                occupancy = np.zeros((self.capacity,), np.float32)
                for s in active:
                    occupancy[s.index] = 1.0
                self._tok, self._state, px = self._decode(
                    self.params, self._tok, self._state, self._ops,
                    jnp.asarray(occupancy))
            else:
                px = None
                self._tok, self._state = self._decode(self.params, self._tok,
                                                      self._state)
        with TraceAnnotation("engine.decode_wait"):
            jax.block_until_ready(self._tok)
        self.watchdog.stop()
        self.steps_run += 1
        now = self.clock()
        with TraceAnnotation("engine.harvest"):
            toks = np.asarray(self._tok)
            for slot in active:
                self._record_token(slot.request, int(toks[slot.index, 0]),
                                   now)
                self._retire_or_keep(slot)
        if px is not None and self._scripted is None:
            gain = self._drift_gain * (seg.activity if seg is not None
                                       else 1.0)
            if self.drift.update(float(px) * gain):
                self._readapt()
        return bool(self.queue or self.active)

    # ------------------------------------------------------------------
    # drift adaptation: re-resolve at the measured operating point
    # ------------------------------------------------------------------
    def _measured_wsp(self) -> float:
        """Weight-sparsity statistic for re-resolves: the active trace
        segment's traffic mix when it declares one, else the one-shot
        measurement from the deployed params."""
        if self.trace is not None:
            seg = self.trace.at(self.steps_run)
            if seg.sparsity is not None:
                return float(seg.sparsity)
        return self._wsp

    def _td_specs(self, measured: float, wsp: float) -> list:
        """Per-TD-layer re-resolve questions at the measured statistics
        (each layer keeps its own budget/shape/arch/techlib/vdd)."""
        return [td_policy.TDLayerSpec(
                    bits_a=p.bits_a, bits_w=p.bits_w, n_chain=p.n_chain,
                    sigma_max=p.sigma_max, vdd=p.vdd, p_x_one=measured,
                    w_bit_sparsity=wsp, m=p.m, tdc_arch=p.tdc_arch,
                    techlib=p.techlib)
                for p in (common.pol_at(self.pol, i)
                          for i in common.td_layer_indices(self.pol))]

    @staticmethod
    def _td_vdds(pol) -> tuple:
        return tuple(common.pol_at(pol, i).vdd
                     for i in common.td_layer_indices(pol))

    def _meter_sigma(self):
        pol0 = common.pol_at(self.pol, 0)
        return None if pol0.sigma_max is not None else 2.0

    def _readapt(self) -> None:
        """The smoothed activity left the band the current policy was
        priced for — adapt in two phases.  Phase 1, HERE, synchronously:
        re-resolve every TD layer at the MEASURED statistics (supply
        unchanged) and hot-swap (sigma, q) as runtime operands + the
        meter's J/token rate — no recompile (the decode program is
        unchanged).  Phase 2, staged: kick off the supply-spanning full
        rebuild on a worker thread; `_poll_staged` installs it at a later
        step boundary."""
        measured = float(self.drift.value)
        wsp = self._measured_wsp()
        specs = self._td_specs(measured, wsp)
        if specs:
            self.pol = common.replace_td_layers(self.pol,
                                                self.resolver(specs))
            self._ops = common.td_policy_ops(self.pol)
            self.swap_log.append({"step": self.steps_run, "kind": "hot",
                                  "ops": np.asarray(self._ops),
                                  "vdds": self._td_vdds(self.pol)})
        pol0 = common.pol_at(self.pol, 0)
        if self.meter is not None:
            # quant-mode meters re-price at the measured statistics too
            # (their policy carries no solved operating point of its own)
            self.meter.set_policy(
                pol0 if specs else pol0.replace(p_x_one=measured,
                                                w_bit_sparsity=wsp),
                sigma_max=self._meter_sigma())
        self.drift.rearm(measured)
        self.adaptations += 1
        self._adapt_gen += 1
        self._last_measured = (measured, wsp)
        if specs and self.supply_span:
            self._launch_staged(measured, wsp)

    # ------------------------------------------------------------------
    # staged supply swap (phase 2)
    # ------------------------------------------------------------------
    def _launch_staged(self, measured: float, wsp: float) -> None:
        """Start the supply-spanning rebuild off-thread: per-layer Vdd
        argmin over the grid at the measured statistics, full policy
        solve, and the meter re-price — everything expensive happens on
        the worker; the install is a pointer swap at a step boundary.  At
        most one rebuild is in flight (a newer excursion re-arms the
        detector and will stage again after this one lands)."""
        if self._staged is not None:
            return
        self._staged_gen = self._adapt_gen
        base_pol = self.pol
        resolver = self.supply_resolver
        specs = self._td_specs(measured, wsp)
        meter = self.meter
        sigma = self._meter_sigma()

        def rebuild():
            solved = common.replace_td_layers(base_pol, resolver(specs))
            ops = np.asarray(common.td_policy_ops(solved))
            report = (meter.price(common.pol_at(solved, 0), sigma_max=sigma)
                      if meter is not None else None)
            return solved, ops, report

        self._staged = ft.StagedRebuild(
            rebuild, name=f"supply-rebuild@{self.steps_run}")

    def _poll_staged(self) -> None:
        """Install a finished staged rebuild (step boundary: the next
        decode is the first to run at the new operating point).  A worker
        exception re-raises HERE, once — the `SaveHandle` contract — so a
        resolver that died inside the thread fails the run loudly instead
        of silently keeping the stale supply."""
        if self._staged is None or not self._staged.done:
            return
        staged, self._staged = self._staged, None
        res = staged.poll()        # raises once on worker failure
        if res is None:
            return
        if self._staged_gen != self._adapt_gen:
            # a NEWER excursion re-priced phase 1 while this rebuild ran:
            # its statistics are stale — discard and rebuild at the latest
            # measured operating point instead of installing old physics
            measured, wsp = self._last_measured
            self._launch_staged(measured, wsp)
            return
        solved, ops, report = res
        moved = self._td_vdds(solved) != self._td_vdds(self.pol)
        self.pol = solved
        self._ops = jnp.asarray(ops, jnp.float32)
        if self.meter is not None and report is not None:
            self.meter.install(report)
        self.swap_log.append({"step": self.steps_run, "kind": "staged",
                              "ops": np.asarray(ops),
                              "vdds": self._td_vdds(solved)})
        self.staged_installs += 1
        if moved:
            self.supply_spans += 1

    # ------------------------------------------------------------------
    # chaos-schedule consumption
    # ------------------------------------------------------------------
    def _apply_faults(self, events) -> None:
        for ev in events:
            self.fault_log.append((self.steps_run, ev.kind))
            if ev.kind == "preempt":
                raise ft.Preemption(f"chaos preempt at step {self.steps_run}")
            if ev.kind == "stall":
                time.sleep(float(ev.params.get("duration_s", 0.05)))
            elif ev.kind == "drift":
                self._drift_gain = float(ev.params.get("factor", 1.0))
            elif ev.kind == "explorer_outage":
                self.explorer_up = bool(ev.params.get("up", False))
                if self.on_outage is not None:
                    self.on_outage(self.explorer_up)
            # "ckpt_corrupt" targets the training half; logged, no-op here

    def warmup(self) -> None:
        """Compile the prefill/insert/decode programs by running one dummy
        request end-to-end, then reset all telemetry and device state —
        benchmarks call this so timed windows measure SCHEDULING, not XLA
        compilation."""
        self.submit(Request(rid="__warmup__",
                            prompt=np.full((1,), 3, np.int32),
                            max_new_tokens=2))
        while self.step():
            pass
        self.done.clear()
        self.steps_run = 0
        self.watchdog = ft.StepWatchdog()
        if self.meter is not None:
            self.meter._usage.clear()
        if self.drift is not None:
            self.drift.rearm(self.drift.anchor)
        if self._staged is not None:      # don't let a warmup-triggered
            self._staged.wait()           # rebuild land mid-measurement
            self._staged = None
        self.swap_log.clear()
        self.adaptations = 0
        self.supply_spans = 0
        self.staged_installs = 0
        self._reset_device_state()

    # ------------------------------------------------------------------
    # fault tolerance: drain + re-admit instead of dying
    # ------------------------------------------------------------------
    def drain(self) -> int:
        """Preemption recovery: move every in-flight request back onto the
        FRONT of the queue as a continuation and reset device state.
        Generated tokens are kept — greedy decode re-prefilled from
        prompt+generated continues bit-identically."""
        inflight = [s.request for s in self.slots if not s.free]
        for slot in self.slots:
            slot.request = None
        for req in reversed(inflight):
            req.readmissions += 1
            self.queue.appendleft(req)
        self._reset_device_state()
        return len(inflight)

    def run(self, requests=None, retry_policy: ft.RetryPolicy | None = None,
            inject=None, schedule: "ft.FaultSchedule | None" = None,
            trace: "ft.TrafficTrace | None" = None) -> dict:
        """Drive the loop to completion.

        `inject(step_index)` (tests/bench) may raise `ft.Preemption` to
        simulate node loss; the engine drains and re-admits under
        `retry_policy`.  Retries apply only to such injected faults (an
        `inject` or a `schedule`): without one, the first failure
        propagates and fails the run.  `schedule`
        is a deterministic `ft.FaultSchedule` consumed fire-once per step:
        preemptions drain-and-retry, stalls sleep (the watchdog flags
        them), drift events scale the measured activity, explorer outages
        toggle `explorer_up`/`on_outage`.  `trace` is a deterministic
        `ft.TrafficTrace` replayed against the step counter: per-segment
        activity scales the measured bit density, sparsity overrides the
        re-resolve statistic, load throttles admissions.
        """
        if requests is not None:
            self.submit_all(requests)
        if trace is not None:
            self.trace = trace
        t0 = self.clock()

        def body():
            while True:
                if schedule is not None:
                    self._apply_faults(schedule.pop(self.steps_run))
                if inject is not None:
                    inject(self.steps_run)
                if not self.step():
                    return True

        if schedule is None and inject is None:
            body()      # no fault source: a failure fails the run
        else:
            ft.run_with_retries(body, policy=retry_policy,
                                on_restart=lambda n, e: self.drain())
        while self._staged is not None:
            # a rebuild still in flight when the queue drained: land it (or
            # surface its error) so the summary reflects the final policy;
            # a stale result relaunches once at the latest statistics
            self._staged.wait()
            self._poll_staged()
        return self.summary(self.clock() - t0)

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------
    def request_rows(self) -> list[dict]:
        """Per-request telemetry rows (CSV-ready), admission order."""
        rows = []
        for req in self.done.values():
            dts = np.diff(np.asarray(req.token_s)) * 1e3
            row = {"request": req.rid, "prompt_len": len(req.prompt),
                   "new_tokens": len(req.generated),
                   "readmissions": req.readmissions,
                   "ttft_ms": (req.t_first_token - req.arrival_s) * 1e3,
                   "ms_per_token_p50": (float(np.percentile(dts, 50))
                                        if dts.size else 0.0),
                   "ms_per_token_p99": (float(np.percentile(dts, 99))
                                        if dts.size else 0.0)}
            if self.meter is not None:
                rep = self.meter.request_report(req.rid)
                row.update({"energy_j": rep["energy_j"],
                            "j_per_token": rep["j_per_token"],
                            "j_per_decoded_token":
                                rep["j_per_decoded_token"]})
            rows.append(row)
        return rows

    def summary(self, wall_s: float) -> dict:
        rows = self.request_rows()
        new_toks = sum(r["new_tokens"] for r in rows)
        p50 = [r["ms_per_token_p50"] for r in rows if r["new_tokens"] > 1]
        p99 = [r["ms_per_token_p99"] for r in rows if r["new_tokens"] > 1]
        out = {"requests": len(rows), "new_tokens": new_toks,
               "wall_s": wall_s,
               "tokens_per_s": new_toks / wall_s if wall_s else 0.0,
               "steps": self.steps_run,
               "stragglers": self.watchdog.straggler_count,
               "ms_per_token_p50": float(np.median(p50)) if p50 else 0.0,
               "ms_per_token_p99": (float(np.percentile(p99, 99))
                                    if p99 else 0.0),
               "adaptations": self.adaptations,
               "faults": [{"step": s, "kind": k} for s, k in self.fault_log],
               "per_request": rows}
        if self.drift is not None:
            out["p_x_one_measured"] = self.drift.value
            out["drift_excursions"] = self.drift.excursions
            out["supply_spans"] = self.supply_spans
            out["staged_installs"] = self.staged_installs
            out["swap_log"] = [{"step": e["step"], "kind": e["kind"],
                                "vdds": list(e["vdds"])}
                               for e in self.swap_log]
        if self.trace is not None:
            out["trace"] = {"seed": self.trace.seed,
                            "segments": len(self.trace.segments),
                            "total_steps": self.trace.total_steps}
        if self.meter is not None:
            out["energy_j_total"] = self.meter.run_total_energy()
            out["j_per_token"] = (out["energy_j_total"] /
                                  max(1, self.meter.run_total_tokens()))
            out["meter_policy_swaps"] = self.meter.policy_swaps
            out["rate_epochs"] = self.meter.rate_epochs()
            out["static_worst_energy_j"] = self.meter.static_worst_energy()
        return out
