"""The trace reduction on a hand-made trace, where every number can be
worked out, and on a small trace recorded on the chip."""
from pathlib import Path

import pytest

import devtrace

MOD, OPS = devtrace.MODULES_LINE, devtrace.OPS_LINE


def planes():
    # two decode executions of 10 ms, each with a td_vmm kernel of 6 ms and
    # an attention kernel of 2 ms; a prefill of 20 ms; idle in between
    return {"/device:TPU:0": {
        MOD: [("jit_serve_step(1)", 0.000, 0.010),
              ("jit_prefill_step(2)", 0.030, 0.050),
              ("jit_serve_step(1)", 0.060, 0.070)],
        OPS: [("_td_vmm_call.1", 0.000, 0.006), ("_decode_gqa_call", 0.006, 0.008),
              ("fusion.1", 0.008, 0.010),
              ("_flash_attn_call", 0.030, 0.050),
              ("_td_vmm_call.1", 0.060, 0.066), ("_decode_gqa_call", 0.066, 0.068)]}}


def spans():
    return [("step", 0.0, 0.012), ("sleep", 0.012, 0.030),
            ("step", 0.030, 0.052), ("admit", 0.030, 0.051),
            ("step", 0.058, 0.072)]


def test_hand_made_trace():
    r = devtrace.reduce_planes(planes(), spans())
    assert r.window == (0.0, 0.072)
    assert r.busy_s == pytest.approx(0.010 + 0.020 + 0.008)
    assert len(r.executions("jit_serve_step")) == 2
    assert r.module_time("jit_serve_step") == pytest.approx(0.020)
    assert r.kernel_time("td_vmm", "jit_serve_step") == pytest.approx(0.012)
    attn = [o for o in r.ops if "attn" in o.name or "gqa" in o.name]
    assert [o.module.split("(")[0] for o in attn] == \
        ["jit_serve_step", "jit_prefill_step", "jit_serve_step"]
    assert r.kernel_time("_decode_gqa_call", "jit_serve_step") == \
        pytest.approx(0.004)
    steps = [(a, b) for n, a, b in r.spans if n == "step"]
    assert r.busy_within(steps) == pytest.approx(0.038)
    gaps = dict((round(t, 6), w) for w, t in
                [(w, t) for w, t in r.breakdown["idle_gaps"]])
    assert gaps[0.02] == "sleep"          # 10-30 ms: the host slept
    assert gaps[0.01] == "none"           # 50-60 ms: between two steps
    assert gaps[0.004] == "step"          # 68-72 ms: the step's host tail
    top = dict(r.breakdown["device_ops"])
    assert top["_flash_attn_call"] == pytest.approx(0.020)
    assert top["_td_vmm_call"] == pytest.approx(0.012)   # one kind


RECORDED = Path(__file__).resolve().parent / "data"


def test_recorded_chip_trace():
    """The smoke model under chat's arrivals traced on a v5e
    (record_trace.py)."""
    r = devtrace.reduce(RECORDED)
    decodes = r.executions("jit_serve_step")
    prefills = r.executions("jit_prefill_step")
    assert len(decodes) == len(r.spans_named("step")) == 49
    assert len(prefills) == len(r.executions("jit_insert_step")) == 6
    assert len([s for s in r.spans if s[0] == "admit"]) == 6
    assert 0 < r.busy_s <= r.window_s
    # 2 layers: one decode_gqa per layer per decode, one flash_attn per
    # layer per prefill; bf16 matmuls, no td_vmm
    assert r.kernel_calls("_decode_gqa_call", "jit_serve_step") == 49 * 2
    assert r.kernel_calls("_flash_attn_call", "jit_prefill_step") == 6 * 2
    assert r.kernel_calls("_td_vmm_call") == 0
    assert 0 < r.kernel_time("_decode_gqa_call", "jit_serve_step") \
        < r.module_time("jit_serve_step") <= r.window_s
