"""95th percentile of time to first token over all requests due in the
window, timed from the due time; a request with no first token by the
window's end enters with its wait so far."""
from record import percentile, ttft_ms


def read(run):
    return percentile(ttft_ms(run), 95)
