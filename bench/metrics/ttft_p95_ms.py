"""95th percentile of due time to first token over all requests due in the window; a failed request is a miss."""
from harness import readers


def read(run):
    return readers.ttft_ms(run, 95)
