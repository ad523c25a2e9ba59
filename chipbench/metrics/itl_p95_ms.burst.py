"""95th percentile of every inter-token gap whose later token falls in
the traced part of the window, in the burst cell, where it is read as a
per-layer metric: one `step()` admits a whole burst before it decodes,
so the gaps are bimodal (a decode step, or a run of admissions) and the
95th percentile jumps between the two modes from run to run."""
from record import percentile


def read(run):
    gaps = [(b - a) * 1e3 for r in run.requests
            for a, b in zip(r.tokens, r.tokens[1:]) if run.in_traced(b)]
    return percentile(gaps, 95)
