"""Share of the traced window with no device op running while the host is outside engine.enqueue and engine.readback."""
from harness import spans


def read(run):
    return spans.host_bound_idle_share(run)
