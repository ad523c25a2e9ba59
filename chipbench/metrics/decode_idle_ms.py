"""Device idle time inside the engine's ticks (`engine.step` spans) and
outside its admissions (`engine.admit`), per decode call (`engine.decode`)
in the traced window: the host's share of a decode tick."""
import enginetrace


def read(run):
    eng = enginetrace.of(run)
    if eng is None:
        return None
    tr = run.trace
    calls = enginetrace.in_window(tr, eng.named("engine.decode"))
    if not calls:
        return None
    idle = enginetrace.idle_in(tr, eng.named("engine.step")) - \
        enginetrace.idle_in(tr, eng.named("engine.admit"))
    return idle / len(calls) * 1e3
