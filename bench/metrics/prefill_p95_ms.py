"""95th percentile over requests due in the window of admission to first emitted token; a request without one is a miss."""
from harness import spans


def read(run):
    return spans.prefill_ms(run, 95)
