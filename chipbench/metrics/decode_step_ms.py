"""Device time per execution of the decode program in the trace."""
DECODE = "jit_serve_step"


def read(run):
    ex = run.trace.executions(DECODE)
    if not ex:
        return None
    return run.trace.module_time(DECODE) / len(ex) * 1e3
