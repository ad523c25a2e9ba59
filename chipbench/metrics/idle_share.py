"""Share of the time the engine had work (the harness's `step` spans in
the traced window, on the trace's clock) in which no operation ran on
the device."""


def read(run):
    return run.trace.idle_share("step")
