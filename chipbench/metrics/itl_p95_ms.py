"""95th percentile of every inter-token gap, over all requests, whose
later token falls in the window."""
from record import percentile


def read(run):
    gaps = [(b - a) * 1e3 for r in run.requests
            for a, b in zip(r.tokens, r.tokens[1:]) if run.in_window(b)]
    return percentile(gaps, 95)
