"""Device idle time inside the engine's admissions (`engine.admit`
spans in the traced window, on the trace's clock), per admission: what
the host round trip of one admission leaves the chip waiting."""
import enginetrace


def read(run):
    eng = enginetrace.of(run)
    admits = enginetrace.in_window(run.trace, eng.named("engine.admit")) \
        if eng is not None else []
    if not admits:
        return None
    return enginetrace.idle_in(run.trace, admits) / len(admits) * 1e3
