"""Continuous-batching serving engine: scheduling, telemetry, recovery.

Pins the PR-7 serving semantics:
  * FIFO admission order;
  * slot recycling (continuous batching runs fewer decode steps than the
    fixed-batch lockstep baseline on ragged traffic);
  * the ragged bucketed-prefill + per-row kv_len decode path is
    BIT-IDENTICAL to a sequential b=1 exact-length oracle;
  * per-request J/token telemetry sums to the run total;
  * a mid-run Preemption drains + re-admits with zero lost requests and
    bit-identical greedy outputs;
  * `serve --seed`: one seed is bit-reproducible, two seeds differ.
"""
import numpy as np
import pytest

import repro.configs as cfgs
from repro.configs.base import ShapeCfg, TDExecCfg
from repro.launch import ft, serve
from repro.launch import steps as steps_lib
from repro.launch.scheduler import ContinuousBatchingEngine, Request

import jax
import jax.numpy as jnp


def _arch():
    return cfgs.get_smoke("qwen3-8b").replace(td=TDExecCfg(mode="quant"))


S_CACHE = 16
_CACHE: dict = {}


def _engine(capacity: int, continuous: bool = True) -> ContinuousBatchingEngine:
    """One compiled engine per (capacity, mode), reset between tests."""
    key = (capacity, continuous)
    if key not in _CACHE:
        params = None
        if _CACHE:          # share params across every engine in the module
            params = next(iter(_CACHE.values())).params
        _CACHE[key] = ContinuousBatchingEngine(
            _arch(), capacity=capacity, s_cache=S_CACHE, seed=0,
            params=params, kv_block=8, continuous=continuous)
    eng = _CACHE[key]
    eng.queue.clear()
    eng.done.clear()
    eng.steps_run = 0
    eng.watchdog = ft.StepWatchdog()
    if eng.meter is not None:
        eng.meter._usage.clear()
    eng._reset_device_state()
    return eng


def _reqs(lens_gens) -> list[Request]:
    rng = np.random.default_rng(11)
    return [Request(rid=i,
                    prompt=rng.integers(3, 50, size=plen).astype(np.int32),
                    max_new_tokens=glen)
            for i, (plen, glen) in enumerate(lens_gens)]


class TestScheduler:
    def test_fifo_admission_order(self):
        eng = _engine(capacity=1)
        out = eng.run(_reqs([(4, 2), (5, 2), (3, 2)]))
        assert out["requests"] == 3
        # capacity 1 => strictly sequential; done order == submit order
        assert list(eng.done) == [0, 1, 2]
        admits = [eng.done[r].t_admitted for r in (0, 1, 2)]
        assert admits == sorted(admits)

    def test_slot_recycle_beats_fixed_batch(self):
        lens = [(4, 2), (4, 6), (4, 2), (4, 6), (4, 2), (4, 6)]
        cont = _engine(capacity=2, continuous=True).run(_reqs(lens))
        fixed = _engine(capacity=2, continuous=False).run(_reqs(lens))
        assert cont["requests"] == fixed["requests"] == len(lens)
        assert cont["new_tokens"] == fixed["new_tokens"]
        # recycling a finished short request's slot while the long one
        # keeps decoding MUST save whole decode steps on ragged traffic
        assert cont["steps"] < fixed["steps"]

    def test_ragged_matches_sequential_oracle(self):
        """Bucketed prefill + per-row kv_len decode == b=1 exact-length
        serve path, token for token."""
        lens = [(3, 5), (7, 4), (5, 6)]
        eng = _engine(capacity=3)
        reqs = _reqs(lens)
        eng.run([Request(r.rid, r.prompt.copy(), r.max_new_tokens)
                 for r in reqs])
        arch = eng.arch
        for r in reqs:
            s1 = ShapeCfg("oracle", len(r.prompt) + r.max_new_tokens, 1,
                          "decode")
            prefill = jax.jit(steps_lib.build_prefill_step(arch, s1))
            step = jax.jit(steps_lib.build_serve_step(arch, s1))
            logits, state = prefill(eng.params,
                                    {"tokens": jnp.asarray(r.prompt)[None]})
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            want = [int(tok[0, 0])]
            for _ in range(r.max_new_tokens - 1):
                tok, state = step(eng.params, tok, state)
                want.append(int(tok[0, 0]))
            assert eng.done[r.rid].generated == want, f"rid={r.rid}"

    def test_per_request_energy_sums_to_total(self):
        eng = _engine(capacity=3)
        assert eng.meter is not None
        out = eng.run(_reqs([(4, 3), (6, 2), (3, 4), (5, 3)]))
        rows = out["per_request"]
        assert all(r["energy_j"] > 0 and r["j_per_token"] > 0 for r in rows)
        total = eng.meter.run_total_energy()
        assert sum(r["energy_j"] for r in rows) == pytest.approx(total)
        assert out["energy_j_total"] == pytest.approx(total)

    def test_preemption_drains_and_readmits(self):
        lens = [(4, 4), (5, 3), (3, 5), (6, 4), (4, 3)]
        eng = _engine(capacity=2)
        base = eng.run(_reqs(lens))
        base_out = {rid: list(r.generated) for rid, r in eng.done.items()}

        eng = _engine(capacity=2)
        fired = {"n": 0}

        def inject(step):
            if step == 2 and not fired["n"]:
                fired["n"] += 1
                raise ft.Preemption("injected")

        out = eng.run(_reqs(lens),
                      retry_policy=ft.RetryPolicy(backoff_s=0.0),
                      inject=inject)
        assert fired["n"] == 1
        assert out["requests"] == base["requests"] == len(lens)   # zero lost
        assert sum(r.readmissions for r in eng.done.values()) >= 1
        got = {rid: list(r.generated) for rid, r in eng.done.items()}
        assert got == base_out      # greedy outputs bit-identical

    def test_failure_without_fault_source_fails_run(self):
        """Retries serve injected faults only: with no `inject` and no
        schedule, a failure propagates instead of draining and retrying."""
        eng = _engine(capacity=2)
        step = eng.step
        calls = {"n": 0}

        def failing_step():
            calls["n"] += 1
            if calls["n"] == 2:
                raise ft.Preemption("node lost")
            return step()

        eng.step = failing_step
        try:
            with pytest.raises(ft.Preemption):
                eng.run(_reqs([(4, 3), (5, 3)]),
                        retry_policy=ft.RetryPolicy(backoff_s=0.0))
        finally:
            del eng.step
            eng.drain()                  # leave the shared engine idle
            eng.queue.clear()
        assert calls["n"] == 2           # no restart after the failure

    def test_weights_in_compute_dtype_and_kv_plan(self):
        """Serving holds bf16 weights; the KV plan budgets the device limit
        less those weights' real bytes."""
        from repro.roofline import model as roofline_model
        eng = _engine(capacity=2)
        assert {a.dtype for a in jax.tree_util.tree_leaves(eng.params)} \
            == {jnp.dtype(jnp.bfloat16)}
        cfg = eng.cfg
        wb = roofline_model.tree_bytes(eng.params)
        plan = roofline_model.plan_kv_cache(cfg, 8, 100, block=64,
                                            weight_bytes=wb,
                                            hbm_bytes=wb + 10_000_000)
        assert plan.s_cache == 128
        assert plan.budget_bytes == int(10_000_000 * 0.9)
        assert plan.max_slots == plan.budget_bytes // plan.bytes_per_slot
        free = roofline_model.plan_kv_cache(cfg, 8, 100, block=64)
        assert free.budget_bytes is None and free.max_slots == 8
        # the plan counts the cache as the engine stores it (lane-dense)
        kv = [c[n] for c in eng._state["layers"] for n in ("k", "v")]
        assert eng.kv_plan.bytes_per_slot * eng.capacity == sum(
            a.nbytes for a in kv)

    def test_submit_rejects_overflowing_request(self):
        eng = _engine(capacity=1)
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit(Request(rid=99,
                               prompt=np.zeros(S_CACHE, np.int32) + 3,
                               max_new_tokens=4))


class TestServeSeed:
    def test_two_seeds_give_different_prompts(self):
        a = serve.synthetic_requests(8, 16, 8, vocab=1000, seed=1)
        b = serve.synthetic_requests(8, 16, 8, vocab=1000, seed=2)
        assert any(len(x.prompt) != len(y.prompt)
                   or not np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, b))

    def test_same_seed_reproduces_requests(self):
        a = serve.synthetic_requests(8, 16, 8, vocab=1000, seed=5)
        b = serve.synthetic_requests(8, 16, 8, vocab=1000, seed=5)
        for x, y in zip(a, b):
            assert np.array_equal(x.prompt, y.prompt)
            assert x.max_new_tokens == y.max_new_tokens

    def test_serve_run_seed_bit_reproducible(self):
        arch = _arch()
        one = np.asarray(serve.run(arch, batch=2, prompt_len=6, gen=3,
                                   seed=3))
        two = np.asarray(serve.run(arch, batch=2, prompt_len=6, gen=3,
                                   seed=3))
        other = np.asarray(serve.run(arch, batch=2, prompt_len=6, gen=3,
                                     seed=4))
        assert np.array_equal(one, two)       # one seed: bit-reproducible
        assert not np.array_equal(one, other)  # two seeds: different stream
