"""Device time of an admission: the prefill program and the insert
program in the trace, per prefill execution."""
PREFILL, INSERT = "jit_prefill_step", "jit_insert_step"


def read(run):
    tr = run.trace
    n = len(tr.executions(PREFILL))
    if not n:
        return None
    return (tr.module_time(PREFILL) + tr.module_time(INSERT)) / n * 1e3
