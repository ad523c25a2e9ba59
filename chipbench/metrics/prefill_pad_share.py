"""Share of the prefilled positions that are padding: the engine's
`engine.prefill` spans in the traced window, 100 x (sum of `padded` less
sum of true `tokens`) over sum of `padded`."""
import enginetrace


def read(run):
    eng = enginetrace.of(run)
    if eng is None:
        return None
    spans = enginetrace.in_window(run.trace, eng.named("engine.prefill"))
    padded = sum(st["padded"] for *_, st in spans)
    if not padded:
        return None
    return 100.0 * (padded - sum(st["tokens"] for *_, st in spans)) / padded
