"""Each cell's traffic end to end on the CPU at the registry's smoke size,
through `run.run_cell` (the command itself refuses a CPU), and the run
with its timed path broken underneath: `correct` has to come out false."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

import smoke

BENCH = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("kind", ["poisson", "onoff"])
def test_cell_rehearsal(kind, capsys):
    out = smoke.run(kind, seed=2 ** 31 + 77, seconds=3.0)
    assert out["device"]["platform"] == "cpu"
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    assert m["tokens_per_s"]["value"] > 0
    assert m["setup_s"]["value"] > 0
    # chat reports the ITL tail, burst the TTFT tail
    chat = kind == "poisson"
    assert ("itl_p95_ms" in m) == chat
    assert ("ttft_p95_ms" in m) == (not chat)
    assert out["checks"]["tokens_checked"]["value"] > 0
    assert "compilations_in_window=0" in capsys.readouterr().out
    assert out["correct"], out["checks"]


def _broken_serve_step(fault):
    from repro.launch import steps

    real = steps.build_serve_step

    def build(arch, shape):
        step = real(arch, shape)

        def broken(params, tok, state):
            nxt, new_state = step(params, tok, state)
            if fault == "token":
                return (nxt + 1) % arch.model.vocab, new_state
            return nxt, state                # the state left unchanged
        return broken
    return build


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.launch import steps
    monkeypatch.setattr(steps, "build_serve_step", _broken_serve_step(fault))
    out = smoke.run("poisson", seed=5, seconds=3.0)
    assert out["correct"] is False, out["checks"]


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "qwen2.5-3b-precise.chat", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("kind", ["poisson", "onoff"])
def test_control_is_not_correct(kind):
    """The float8 control put in the program's place, at the smoke size,
    through the same verdict and at the committed limit: not correct,
    while the program on the same sample is."""
    out = smoke.run(kind, seed=11, seconds=6.0, control=True)
    c = out["control"]
    assert out["correct"] is True, out["checks"]
    assert c["correct"] is False, c["checks"]
    assert c["checks"]["gap_max"]["limit"] == out["checks"]["gap_max"][
        "limit"] == json.loads((BENCH / "configs" /
                                "qwen2.5-3b-precise.json").read_text())[
        "check"]["gap_max"]
    assert c["readings"]["gap_max"] > 3 * max(c["program"]["gap_max"], 1e-2)


def test_traced_window_is_lengthened_by_the_trace_stop():
    """Writing the trace stalls the loop; the window still serves its
    full length after the stall, for the check of `correct`."""
    import run as harness
    from repro.launch.scheduler import ContinuousBatchingEngine
    import record
    import refs
    import traffic
    import time
    arch, cfg = smoke.arch_and_cfg()
    mix = dict(smoke.MIXES["poisson"], trace_seconds=0.5)
    params = refs.load(cfg).make_weights(cfg, (1, 0))
    sizes = mix["engine"]
    eng = ContinuousBatchingEngine(arch, capacity=sizes["capacity"],
                                   s_cache=sizes["s_cache"],
                                   prompt_pad=sizes["prompt_pad"],
                                   params=params, clock=time.perf_counter)
    eng.warmup()
    run = record.Run(cfg=cfg, mix=mix, seconds=2.0)
    h = harness.Harness(run, eng, 1, cfg["vocab_size"], True)
    out = harness.OUT / "trace" / "rehearsal"
    harness.shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    harness.drive(h, traffic.schedule(mix, 1, 2.0), 2.0, out, 0.5)
    stop = [b - a for n, a, b in run.spans if n == "trace_stop"]
    assert len(stop) == 1
    assert run.t_end - run.t0 == pytest.approx(2.0 + stop[0], abs=0.05)
    assert run.traced[1] - run.traced[0] == pytest.approx(0.5, abs=0.3)
