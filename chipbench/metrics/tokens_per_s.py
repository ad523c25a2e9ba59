"""Output tokens emitted inside the window, over the window's seconds
(first tokens from the prefill included)."""


def read(run):
    n = sum(1 for r in run.requests for t in r.tokens if run.in_window(t))
    return n / run.seconds
