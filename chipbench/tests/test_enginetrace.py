"""The engine's spans beside the device trace: the readers, the clock
offset and the idle labels on a hand-made trace, where every number can
be worked out, and on a small trace recorded on the chip with the
engine's spans in it."""
import shutil
from pathlib import Path

import pytest

import devtrace
import enginetrace
import record
import run as harness

MOD, OPS = devtrace.MODULES_LINE, devtrace.OPS_LINE
MS = 1e-3


def ms(*pairs):
    return [(n, a * MS, b * MS) for n, a, b in pairs]


def planes():
    # a prefill of 10 ms, an insert of 1 ms, two decodes of 10 and 9 ms
    ex = ms(("jit_prefill_step(1)", 6, 16), ("jit_insert_step(2)", 17, 18),
            ("jit_serve_step(3)", 24, 34), ("jit_serve_step(3)", 67, 76))
    return {"/device:TPU:0": {MOD: ex,
                              OPS: [("fusion.1",) + e[1:] for e in ex]}}


def spans():
    return ms(("step", 0, 40), ("admit", 2.2, 20), ("sleep", 40, 60),
              ("step", 60, 80))


def engine():
    """One tick that admits and decodes, then a tick that decodes and
    compiles; the clock stamps put the trace 1 s behind the engine's
    clock, the second 0.1 ms off."""
    ev = [("engine.step", 2, 39, {"step_num": 0, "queue": 1, "t": 1.002}),
          ("engine.admit", 2.5, 19, {"rid": 7, "slot": 0}),
          ("engine.prep", 2.5, 5, {}),
          ("engine.prefill", 5, 7, {"tokens": 100, "padded": 400}),
          ("engine.insert", 7, 8, {}), ("engine.tok_write", 8, 9, {}),
          ("engine.first_token", 9, 18, {}),
          ("engine.decode", 20.5, 23, {"rows": 1, "kv_tokens": 101}),
          ("engine.decode_wait", 23, 33, {}), ("engine.harvest", 33, 35, {}),
          ("engine.step", 61, 79, {"step_num": 1, "queue": 0,
                                   "t": 1.0611}),
          ("engine.decode", 62, 66, {"rows": 2, "kv_tokens": 230}),
          ("compile", 63, 65, {}),
          ("engine.decode_wait", 66, 76, {}),
          ("engine.harvest", 76, 77.5, {})]
    return enginetrace.Engine([(n, a * MS, b * MS, st)
                               for n, a, b, st in ev])


@pytest.fixture
def hand_run():
    run = record.Run(cfg={}, mix={}, seconds=1.0)
    run.trace = devtrace.reduce_planes(planes(), spans())
    run.traced = (1.0, 1.08)
    run.decodes = [record.Decode(1.0215, (101,)),
                   record.Decode(1.0625, (100, 130))]
    enginetrace.attach(run, engine())
    return run


@pytest.mark.parametrize("metric,value", [
    # admit 2.5-19 ms: 16.5 ms, 11 ms of it busy
    ("admit_idle_ms", 5.5), ("admit_idle_ms.burst", 5.5),
    # ticks 2-39 and 61-79 idle 16 + 9 ms, less the admission's 5.5,
    # over two decode calls
    ("decode_idle_ms", 9.75),
    ("prefill_pad_share", 75.0), ("prefill_pad_share.burst", 75.0)])
def test_reader_on_hand_made_trace(hand_run, metric, value):
    assert harness.reader(metric).read(hand_run) == pytest.approx(value)


def test_existing_reduction_unchanged_by_engine_spans(hand_run):
    r = hand_run.trace
    assert r.window == (0.0, 0.080)
    assert r.busy_s == pytest.approx(0.030)
    assert r.idle_share("step") == pytest.approx(100 * 30 / 60)
    assert sorted(g[0] for g in r.breakdown["idle_gaps"]) == \
        ["admit", "admit", "sleep", "step", "step"]


def test_clock_offset(hand_run):
    eng = enginetrace.of(hand_run)
    assert eng.clock_offsets() == pytest.approx([-1.0, -1.0001])
    assert eng.clock_offset == pytest.approx(-1.00005)


def test_crosscheck_against_harness_records(hand_run):
    c = enginetrace.crosscheck(hand_run, enginetrace.of(hand_run))
    assert c["clock_offset_spread_ms"] == pytest.approx(0.1)
    assert c["decode_spans"] == c["harness_decodes"] == \
        c["decodes_equal"] == 2
    assert c["admits"] == 1
    assert c["admit_idle_split_ms"] == pytest.approx({
        "engine.prep": 2.5, "engine.prefill": 1, "engine.insert": 0,
        "engine.tok_write": 0, "engine.first_token": 1, "engine.admit": 1})
    hand_run.decodes[1] = record.Decode(1.0625, (100, 131))
    c = enginetrace.crosscheck(hand_run, enginetrace.of(hand_run))
    assert c["decodes_equal"] == 1


def test_idle_by_span(hand_run):
    by = dict(enginetrace.idle_by_span(hand_run.trace,
                                       enginetrace.of(hand_run), top=20))
    want = {"sleep": 20, "engine.step": 7.2, "engine.decode": 4.5,
            "step": 5, "engine.prep": 2.5, "engine.harvest": 2.5,
            "admit": 1.3, "engine.decode_wait": 2, "compile": 2,
            "engine.prefill": 1, "engine.first_token": 1,
            "engine.admit": 1}      # insert and tok_write: never idle
    assert by == pytest.approx({k: v * MS for k, v in want.items()})
    top = hand_run.trace.breakdown["idle_by_span"]
    assert len(top) == 10 and top[0] == ["sleep", pytest.approx(0.020)]
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    # the admission's idle, 5.5 ms, all in its leaves and its own time
    inside = ["engine.prep", "engine.prefill", "engine.insert",
              "engine.tok_write", "engine.first_token", "engine.admit"]
    assert sum(by.get(k, 0) for k in inside) == pytest.approx(5.5 * MS)


def test_idle_gaps_labelled_by_engine_spans(hand_run):
    gaps = hand_run.trace.breakdown["idle_gaps_engine"]
    assert {w: pytest.approx(t) for w, t in gaps} == {
        "sleep": 0.033,                   # 34-67 ms, no engine span
        "engine.prep": 0.006,             # 0-6 ms
        "engine.decode": 0.006,           # 18-24 ms
        "engine.step": 0.004,             # 76-80 ms
        "engine.first_token": 0.001}      # 16-17 ms


def test_no_engine_spans_reads_nothing(hand_run):
    enginetrace.attach(hand_run, enginetrace.Engine(
        [("compile", 0.063, 0.065, {})]))
    assert enginetrace.of(hand_run) is None
    for m in ["admit_idle_ms", "decode_idle_ms", "prefill_pad_share"]:
        assert harness.reader(m).read(hand_run) is None
    assert dict(hand_run.trace.breakdown["idle_by_span"])["compile"] == \
        pytest.approx(0.002)


DATA = Path(__file__).resolve().parent
OLD = DATA / "data" / "smoke_chat.xplane.pb.gz"
NEW = DATA / "data_engine" / "smoke_chat_engine.xplane.pb.gz"


def test_trace_without_engine_spans():
    """The trace recorded before the engine wrote spans: nothing read."""
    bench, eng = enginetrace.load(str(OLD))
    assert bench == devtrace.load(str(OLD))[1]
    assert not [e for e in eng.events if e[0].startswith("engine.")]
    assert eng.clock_offset is None


@pytest.fixture(scope="module")
def recorded():
    bench, eng = enginetrace.load(str(NEW))
    devices, spans = devtrace.load(str(NEW))
    assert bench == spans
    return devtrace.reduce_planes(devices, spans), eng


def test_recorded_prefills_map_to_admissions(recorded):
    """The smoke model under chat's arrivals traced on a v5e
    (record_engine_trace.py): each prefill execution lies inside its own
    admission, in order."""
    r, eng = recorded
    admits = eng.named("engine.admit")
    prefills = r.executions("jit_prefill_step")
    assert len(prefills) == len(admits) > 0
    for i, ex in enumerate(prefills):
        a, b = admits[i][1:3]
        assert a <= ex.start and ex.end <= b


def test_recorded_decodes_map_to_decode_calls(recorded):
    """Each decode execution lies between its own dispatch and the end of
    its wait, in order.  The device's clock is mapped onto the host's to
    within about 0.1 ms in this trace (an execution may read as starting
    up to 0.09 ms before its dispatch), far less than the 0.4 ms or more
    between one wait's end and the next dispatch."""
    r, eng = recorded
    calls = eng.named("engine.decode")
    waits = eng.named("engine.decode_wait")
    decodes = r.executions("jit_serve_step")
    assert len(decodes) == len(calls) == len(waits) > 0
    skew = 0.2e-3
    for i, (ex, call, wait) in enumerate(zip(decodes, calls, waits)):
        assert call[1] - skew <= ex.start and ex.end <= wait[2]
        if i:
            assert waits[i - 1][2] < call[1] - skew


def test_recorded_clock_offset(recorded):
    _, eng = recorded
    offsets = eng.clock_offsets()
    assert len(offsets) == len(eng.named("engine.step")) > 0
    assert max(offsets) - min(offsets) < 0.5e-3


def test_of_finds_the_runs_own_trace(tmp_path, monkeypatch):
    """Among the traced cells' directories, the trace whose harness spans
    are the run's; the one written before the engine had spans reads as
    none."""
    for cell, src in (("a", NEW), ("b", OLD)):
        (tmp_path / cell).mkdir()
        shutil.copy(src, tmp_path / cell / src.name)
    monkeypatch.setattr(enginetrace, "TRACES", tmp_path)
    for src, found in ((OLD, False), (NEW, True)):
        run = record.Run(cfg={}, mix={}, seconds=1.0)
        run.trace = devtrace.reduce_planes(*devtrace.load(str(src)))
        assert (enginetrace.of(run) is not None) is found
        assert "idle_by_span" in run.trace.breakdown
