"""Share of the HBM roofline that the decode step reaches: the least
time of one decode step at the chip's HBM peak (`work.decode_step`: the
matmul weights once, the occupied rows' true K/V, their recurrent state
read and written) over the decode program's device time per execution,
in the traced window.  The family's count of the state is held against
the engine's own, the `state_bytes` of its `engine.decode` spans."""
import enginetrace
from work import decode_step

DECODE = "jit_serve_step"


def check_state(run) -> None:
    """Each traced `engine.decode` span's `state_bytes` (absent: none)
    equals its rows times the family's state of one slot."""
    eng = enginetrace.of(run)
    if eng is None:
        return
    per_slot = decode_step.state_bytes(run.cfg)
    for _, _, _, st in enginetrace.in_window(run.trace,
                                             eng.named("engine.decode")):
        if st.get("state_bytes", 0) != st["rows"] * per_slot:
            raise RuntimeError(
                f"engine.decode holds {st.get('state_bytes', 0)} bytes of "
                f"state for {st['rows']} rows; the family counts "
                f"{per_slot} a slot")


def read(run):
    tr = run.trace
    ex = tr.executions(DECODE)
    calls = [d for d in run.decodes if run.in_traced(d.t) and d.kv_lens]
    if not ex or not calls:
        return None
    check_state(run)
    least = sum(decode_step.least_time(run.cfg, d.kv_lens, run.peaks)
                for d in calls) / len(calls)
    return 100.0 * least / (tr.module_time(DECODE) / len(ex))
