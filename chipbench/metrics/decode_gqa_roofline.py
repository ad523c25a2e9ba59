"""Share of its roofline that the decode attention kernel
(`_decode_gqa_call`) reaches: the least time of reading every occupied
row's true K/V (and its FLOPs) at the chip's peaks (`work.decode_gqa`),
over the kernel's device time per decode execution."""
from work import decode_gqa

DECODE, KERNEL = "jit_serve_step", "_decode_gqa_call"


def read(run):
    tr = run.trace
    n = len(tr.executions(DECODE))
    calls = [d for d in run.decodes if run.in_traced(d.t) and d.kv_lens]
    t_kernel = tr.kernel_time(KERNEL, DECODE)
    if not n or not calls or not t_kernel:
        return None
    least = sum(decode_gqa.least_time(run.cfg, d.kv_lens, run.peaks)[0]
                for d in calls) / len(calls)
    return 100.0 * least / (t_kernel / n)
