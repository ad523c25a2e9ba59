"""Model FLOPs of the decode steps' occupied rows (2 x matmul weights per
token plus attention over each row's true KV length, the same count
whatever the matmuls run on) over the decode program's device time times the
chip's bf16 peak, in the traced window."""
from work import model

DECODE = "jit_serve_step"


def read(run):
    tr = run.trace
    ex = tr.executions(DECODE)
    calls = [d for d in run.decodes if run.in_traced(d.t)]
    if not ex or not calls:
        return None
    flops = sum(model.decode_flops(run.cfg, d.kv_lens) for d in calls)
    per_step = tr.module_time(DECODE) / len(ex)
    return 100.0 * flops / len(calls) / (per_step
                                         * run.peaks["bf16_flops_per_s"])
