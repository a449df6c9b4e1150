"""95th percentile over requests due in the window of due time to the dispatch that admitted them; never admitted is a miss."""
from harness import spans


def read(run):
    return spans.queue_wait_ms(run, 95)
