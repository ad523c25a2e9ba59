"""The serving engine's profiler spans (`engine.*`), read back from a
`jax.profiler` capture of the smoke qwen2.5-3b engine: every span is
there, nested inside its caller, with the stats the engine's own state
gives, and tracing leaves the greedy tokens bit for bit as they are."""
import glob

import numpy as np
import pytest

import jax
import repro.configs as cfgs
from repro.launch import td_cli
from repro.launch.scheduler import ContinuousBatchingEngine, Request

SPANS = ["engine.step", "engine.admit", "engine.prep", "engine.prefill",
         "engine.insert", "engine.tok_write", "engine.first_token",
         "engine.decode", "engine.decode_wait", "engine.harvest"]
PAD = 16
# (prompt length, new tokens): more requests than slots, so slots recycle
SHAPES = [(5, 3), (12, 6), (3, 2), (16, 4), (7, 5)]


def _requests():
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(3, 200, n).astype(np.int32),
                    max_new_tokens=g) for i, (n, g) in enumerate(SHAPES)]


def _events(path):
    """Every `engine.*` host event: (name, start_s, end_s, stats)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One run traced and one not, on the same engine, and what the
    engine's slots held before each decode call of the traced run."""
    arch = td_cli.apply_td_args(cfgs.get_smoke("qwen2.5-3b"), "precise",
                                None)
    eng = ContinuousBatchingEngine(arch, capacity=3, s_cache=64,
                                   prompt_pad=PAD, seed=0, kv_block=8)
    eng.warmup()
    decodes = []
    decode = eng._decode

    def recorded(*args):
        decodes.append([len(s.request.prompt) + len(s.request.generated)
                        for s in eng.active])
        return decode(*args)

    eng._decode = recorded
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        eng.run(_requests())
    on = {r: list(q.generated) for r, q in eng.done.items()}
    eng.done.clear()
    eng.steps_run = 0
    eng._reset_device_state()
    eng._decode = decode
    eng.run(_requests())
    off = {r: list(q.generated) for r, q in eng.done.items()}
    path, = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    return {"events": _events(path), "decodes": decodes, "on": on,
            "off": off}


def _named(traced, name):
    return [e for e in traced["events"] if e[0] == name]


@pytest.mark.parametrize("name", SPANS)
def test_span_is_recorded(traced, name):
    assert _named(traced, name)


@pytest.mark.parametrize("child,parent", [
    ("engine.prefill", "engine.admit"), ("engine.admit", "engine.step"),
    ("engine.prep", "engine.admit"), ("engine.insert", "engine.admit"),
    ("engine.tok_write", "engine.admit"),
    ("engine.first_token", "engine.admit"),
    ("engine.decode", "engine.step"), ("engine.decode_wait", "engine.step"),
    ("engine.harvest", "engine.step")])
def test_span_nests_inside_its_caller(traced, child, parent):
    outer = _named(traced, parent)
    for _, a, b, _ in _named(traced, child):
        assert any(c <= a and b <= d for _, c, d, _ in outer), (child, a)


def test_one_admit_per_admission(traced):
    admits = _named(traced, "engine.admit")
    prefills = _named(traced, "engine.prefill")
    assert [e[3]["rid"] for e in admits] == list(range(len(SHAPES)))
    assert len({e[3]["slot"] for e in admits}) == 3
    assert [e[3]["tokens"] for e in prefills] == [n for n, _ in SHAPES]
    assert {e[3]["padded"] for e in prefills} == {PAD}


def test_one_decode_span_per_decode_call(traced):
    spans = _named(traced, "engine.decode")
    assert len(spans) == len(traced["decodes"]) > 0
    assert [(e[3]["rows"], e[3]["kv_tokens"]) for e in spans] == \
        [(len(kv), sum(kv)) for kv in traced["decodes"]]


def test_step_stamp_ties_the_clocks(traced):
    steps = _named(traced, "engine.step")
    offsets = [a - e["t"] for _, a, _, e in steps]
    assert max(offsets) - min(offsets) < 5e-3
    assert [e[3]["step_num"] for e in steps] == sorted(
        e[3]["step_num"] for e in steps)
    assert steps[0][3]["queue"] == len(SHAPES)


def test_tracing_leaves_tokens_bit_identical(traced):
    assert traced["on"] == traced["off"]
    assert sorted(traced["on"]) == list(range(len(SHAPES)))
