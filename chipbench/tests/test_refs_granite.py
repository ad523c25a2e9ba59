"""The granitemoehybrid family (granite-4.0-h-micro) on the CPU: the
registry's smoke model through the rehearsal of its cell, what the family
states and counts held against the program, and the decode step's byte
count by hand."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import refs
import run as harness
import smoke
from work import decode_step

BENCH = Path(__file__).resolve().parents[1]
CELL = "granite-4.0-h-micro-precise.backlog"
FULL = json.loads((BENCH / "configs" /
                   "granite-4.0-h-micro-precise.json").read_text())
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]
# a backlog at the smoke size: more requests than the 4 slots hold
MIX = {"engine": {"capacity": 4, "s_cache": 128, "prompt_pad": 32},
       "prompt": {"median": 12, "sigma": 0.8, "min": 4, "max": 32},
       "output": {"median": 10, "sigma": 0.8, "min": 2, "max": 40},
       "arrivals": {"kind": "poisson", "rate": 4.0},
       "trace_seconds": 2, "check": {"tokens": 120}}


def arch_and_cfg():
    import repro.configs as cfgs
    from repro.launch import td_cli
    arch = td_cli.apply_td_args(cfgs.get_smoke("granite-4.0-h-micro"),
                                "precise", None)
    cfg = json.loads(json.dumps(FULL))
    return arch, smoke.assign(cfg, refs.load(cfg).stated(arch.model))


def rehearse(seed, control=False):
    arch, cfg = arch_and_cfg()
    return harness.run_cell(CELL, seed, 3.0, False, cfg=cfg, arch=arch,
                            mix=MIX, control=control, peaks=smoke.PEAKS)


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 77])
def test_rehearsal_is_correct_and_the_control_is_not(seed, capsys):
    """The cell end to end at the smoke size, at the committed limit: the
    program's served tokens pass, the float8 control in its place fails."""
    out = rehearse(seed, control=True)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["tokens_checked"]["value"] > 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    c = out["control"]
    assert c["correct"] is False, c["checks"]
    assert c["checks"]["gap_max"]["limit"] == FULL["check"]["gap_max"]
    assert "compilations_in_window=0" in capsys.readouterr().out


def test_stated_holds_the_smoke_program():
    arch, cfg = arch_and_cfg()
    assert harness.build_arch(cfg, arch) is arch
    assert cfg["layer_types"] == ["mamba", "attention", "mamba", "mamba"]
    assert cfg["position_embedding_type"] == "nope"
    for key, value in [("residual_multiplier", 0.2),
                       ("position_embedding_type", "rope"),
                       ("layer_types", ["mamba"] * 4),
                       ("mamba_d_state", 8)]:
        off = dict(cfg, **{key: value})
        with pytest.raises(RuntimeError, match="departs"):
            harness.build_arch(off, arch)


def test_stated_matches_the_published_config():
    """The registry's full model states every published key as the
    configuration file holds it."""
    import repro.configs as cfgs
    stated = refs.load(FULL).stated(cfgs.get("granite-4.0-h-micro").model)
    assert {k: FULL[k] for k in stated} == stated


def test_state_bytes_equals_the_programs_state():
    """One slot's recurrent state as the family counts it is the bytes of
    the program's conv and SSM leaves for one row, at smoke and at full
    size (the latter by shapes only)."""
    import repro.configs as cfgs
    from repro.launch.scheduler import ContinuousBatchingEngine
    from repro.models import transformer
    arch, cfg = arch_and_cfg()
    eng = ContinuousBatchingEngine(arch, capacity=2, s_cache=64,
                                   prompt_pad=32, seed=0, kv_block=8)
    assert eng.state_bytes == refs.load(cfg).state_bytes(cfg) > 0
    model = cfgs.get("granite-4.0-h-micro").model
    caches = jax.eval_shape(lambda: transformer.init_caches(
        1, 2560, model, jnp.bfloat16, per_row_idx=True))
    held = sum(a.size * a.dtype.itemsize for c in caches if "ssm" in c
               for a in c.values())
    assert held == refs.load(FULL).state_bytes(FULL) == 76_437_504
    assert decode_step.state_bytes(FULL) == held


def test_dims_by_hand():
    d = refs.load(FULL).dims(FULL)
    assert (d.layers, d.heads, d.kv_heads, d.head_dim) == (4, 32, 8, 64)
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mamba = 2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048
    mlp = 3 * 2048 * 8192
    assert d.matmul_params == 4 * attn + 36 * mamba + 40 * mlp \
        + 2048 * 100352 == 3_190_292_480


def test_decode_step_bytes_by_hand():
    """Two occupied rows holding 100 and 2000 tokens: the weights once,
    their K and V in 4 layers of 8 heads of 64, their state in and out."""
    weights = 2 * 3_190_292_480
    kv = 2 * 4 * (100 + 2000) * 8 * 64 * 2
    state = 2 * 2 * 76_437_504
    assert decode_step.least_bytes(FULL, [100, 2000]) == \
        weights + kv + state
    assert decode_step.least_time(FULL, [100, 2000], PEAKS) == \
        pytest.approx((weights + kv + state) / 819e9)


def test_decode_step_bytes_without_state():
    """A family with no recurrent state (qwen2) counts none."""
    cfg = json.loads((BENCH / "configs" /
                      "qwen2.5-3b-precise.json").read_text())
    assert decode_step.state_bytes(cfg) == 0
    assert decode_step.least_bytes(cfg, [512]) == \
        2 * 3_085_697_024 + 2 * 36 * 512 * 2 * 128 * 2


class _Trace:
    """What the reader asks of a reduced device trace: 4 executions of
    the decode program, 0.1 s of device time in all."""
    window = (0.0, 10.0)

    def executions(self, name):
        return [0] * 4 if name == "jit_serve_step" else []

    def module_time(self, name):
        return 0.1 if name == "jit_serve_step" else 0.0


def _run(decodes):
    import record
    run = record.Run(cfg=FULL, mix=MIX, seconds=10.0)
    run.traced, run.trace, run.peaks = (0.0, 10.0), _Trace(), PEAKS
    run.decodes = [record.Decode(t, kv) for t, kv in decodes]
    return run


def test_reader_share_by_hand(monkeypatch):
    """Two traced decode calls (rows of 100 and 2000 tokens, then one of
    300) against 25 ms of device time per execution."""
    import enginetrace
    monkeypatch.setattr(enginetrace, "of", lambda run: None)
    run = _run([(1.0, (100, 2000)), (2.0, (300,))])
    least = [decode_step.least_bytes(FULL, [100, 2000]),
             decode_step.least_bytes(FULL, [300])]
    want = 100 * (sum(least) / 2 / 819e9) / 0.025
    assert harness.reader("decode_hbm_roofline").read(run) == \
        pytest.approx(want)
    assert harness.reader("decode_hbm_roofline").read(_run([])) is None


def test_reader_holds_the_family_against_the_engine_spans(monkeypatch):
    """`decode_hbm_roofline` refuses a trace whose `engine.decode` spans
    hold another count of the state than the family's."""
    import enginetrace
    reader = harness.reader("decode_hbm_roofline")
    per_slot = decode_step.state_bytes(FULL)
    run = _run([(1.0, (100, 2000, 5))])
    spans = [("engine.decode", 1.0, 1.1, {"rows": 3,
                                          "state_bytes": 3 * per_slot})]
    monkeypatch.setattr(enginetrace, "of",
                        lambda r: enginetrace.Engine(spans))
    assert reader.read(run) > 0
    spans[0][3]["state_bytes"] = 2 * per_slot
    with pytest.raises(RuntimeError, match="family counts"):
        reader.read(run)
